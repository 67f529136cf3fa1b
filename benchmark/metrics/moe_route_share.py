"""Experts: of the routed FFN's device time (the ``moe`` scope), the part
under ``moe_route``: router, top-k, ordering, gather and weighted sum,
everything but the grouped matmuls."""
import moe_trace


def reduce(src):
    secs = moe_trace.of(src)
    if not secs:
        return None
    return 100.0 * secs["moe_route"] / secs["moe"]
