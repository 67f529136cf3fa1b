"""Experts: experts some live pair touched, per layer call, over the number
of experts; from the window's delta of the engine's counters
(``serving_moe_experts_touched_total`` over ``serving_moe_layer_calls_total``).
What the weight stream of a step follows."""
import readers


def reduce(src):
    touched = readers.delta(src, "serving_moe_experts_touched_total")
    calls = readers.delta(src, "serving_moe_layer_calls_total")
    n = src.get("model", {}).get("num_experts")
    if touched is None or not calls or not n:
        return None
    return 100.0 * touched / calls / n
