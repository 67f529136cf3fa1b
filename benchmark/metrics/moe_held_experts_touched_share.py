"""Experts: held experts some live pair touched, per layer call, over the
experts this chip holds (``n_routed_experts``); from the window's delta of the
engine's counters (``serving_moe_experts_touched_total`` over
``serving_moe_layer_calls_total``). What the held experts' weight stream of a
step follows; ``moe_experts_touched_share`` divides by ``num_experts``, which
this configuration does not have."""
import readers


def reduce(src):
    touched = readers.delta(src, "serving_moe_experts_touched_total")
    calls = readers.delta(src, "serving_moe_layer_calls_total")
    n = src.get("model", {}).get("n_routed_experts")
    if touched is None or not calls or not n:
        return None
    return 100.0 * touched / calls / n
