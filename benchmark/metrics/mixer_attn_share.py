"""Step programs: device time of the ops under the ``mixer_attn`` scope (an
attention block's projections, the ragged kernel's call and the output
projection; units without one run nothing under it) over device busy time, in
the traced part of the window."""
import ssd_trace


def reduce(src):
    return ssd_trace.share_of_busy(src, "mixer_attn")
