"""Step programs: device time of the ops under the ``ssd_proj`` scope (a
Mamba-2 block's two projections) over device busy time, in the traced part of
the window."""
import ssd_trace


def reduce(src):
    return ssd_trace.share_of_busy(src, "ssd_proj")
