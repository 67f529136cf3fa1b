"""Experts: routed-FFN layer calls that ran ONE pass on the buffer of the pairs
this chip's experts take (``kernels/moe_ffn.py`` ``_capacity``), not on a slot
for every pick and not in several passes, over all layer calls; from the
window's delta of the engine's counters (``serving_moe_compact_calls_total``
over ``serving_moe_layer_calls_total``). 0 where every expert is held (there
is no smaller buffer), nothing for a program that does not count it, 100 where
every call's held pairs fit."""
import readers


def reduce(src):
    compact = readers.delta(src, "serving_moe_compact_calls_total")
    calls = readers.delta(src, "serving_moe_layer_calls_total")
    if compact is None or not calls:
        return None
    return 100.0 * compact / calls
