"""Step programs: device time of the ops under the ``ssd_mix`` scope (a
Mamba-2 block's convolution, gates, the two kernels and the gated norm) over
device busy time, in the traced part of the window."""
import ssd_trace


def reduce(src):
    return ssd_trace.share_of_busy(src, "ssd_mix")
