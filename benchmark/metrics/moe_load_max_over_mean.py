"""Experts: the fullest expert's pairs over the mean pairs an expert, per
layer call, from the window's delta of the engine's counters
(``serving_moe_max_expert_pairs_total`` x experts over
``serving_moe_pairs_total``). 1 is a perfectly even load; the fullest group
is the longest walk of the grouped matmul."""
import readers


def reduce(src):
    fullest = readers.delta(src, "serving_moe_max_expert_pairs_total")
    pairs = readers.delta(src, "serving_moe_pairs_total")
    n = src.get("model", {}).get("num_experts")
    if fullest is None or not pairs or not n:
        return None
    return fullest * n / pairs
