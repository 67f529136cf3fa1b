"""Kernels: device time of the ops under the ``yoco_attn`` scope (the middle
full layer and the seven cross layers that read its one cache: projections,
the ragged kernel's calls, the differential combine) over device busy time,
in the traced part of the window."""
import ssm_trace


def reduce(src):
    return ssm_trace.share_of_busy(src, "yoco_attn")
