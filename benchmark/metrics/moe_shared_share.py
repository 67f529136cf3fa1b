"""Experts: device time of the ops under the ``moe_shared`` scope (the shared
expert every row runs, beside the routed FFN's ``moe`` scope) over device
busy time, in the traced part of the window."""
import mla_trace


def reduce(src):
    return mla_trace.share_of_busy(src, "moe_shared")
