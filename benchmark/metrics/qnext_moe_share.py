"""Experts: device time of the ops under ``moe`` (all twelve layers' routed
FFN: router, ordering, the grouped matmuls, weighted sum) plus ``moe_shared``
(the gated shared expert beside it) over device busy time, in the traced part
of the window."""
import qwen3_next_trace


def reduce(src):
    return qwen3_next_trace.share_of_busy(src, "moe", "moe_shared")
