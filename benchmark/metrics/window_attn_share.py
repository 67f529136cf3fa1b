"""Step programs: device time of the ops under the ``window_attn`` scope (a
window layer's projections, the ragged kernel's windowed calls over the
ring, the differential combine) over device busy time, in the traced part of
the window."""
import ssm_trace


def reduce(src):
    return ssm_trace.share_of_busy(src, "window_attn")
