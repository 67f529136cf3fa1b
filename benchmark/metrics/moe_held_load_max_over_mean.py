"""Experts: the fullest held expert's pairs over the mean pairs a held expert,
per layer call, from the window's delta of the engine's counters
(``serving_moe_max_expert_pairs_total`` x held experts over
``serving_moe_pairs_total``). 1 is a perfectly even load over the experts this
chip holds; the fullest group is the longest walk of the grouped matmul."""
import readers


def reduce(src):
    fullest = readers.delta(src, "serving_moe_max_expert_pairs_total")
    pairs = readers.delta(src, "serving_moe_pairs_total")
    n = src.get("model", {}).get("n_routed_experts")
    if fullest is None or not pairs or not n:
        return None
    return fullest * n / pairs
