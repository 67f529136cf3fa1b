"""Experts: device time of the ops under the ``moe`` scope (the whole routed
FFN: router, ordering, grouped matmuls, weighted sum) over device busy time,
in the traced part of the window."""
import moe_trace


def reduce(src):
    secs, x = moe_trace.of(src), src.get("xplane")
    if not secs or not x or not x["busy_s"]:
        return None
    return 100.0 * secs["moe"] / x["busy_s"]
