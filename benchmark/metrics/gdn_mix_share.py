"""Step programs: device time of the ops under the ``gdn_mix`` scope (a linear
layer's convolution, gates, the two delta-rule kernels and the gated norm)
over device busy time, in the traced part of the window."""
import gdn_trace


def reduce(src):
    return gdn_trace.share_of_busy(src, "gdn_mix")
