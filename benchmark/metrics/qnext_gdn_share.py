"""Step programs: device time of the ops under the ``gdn`` scope (the nine
Gated DeltaNet mixers: projections, convolution, gates, the two kernels, the
gated norm, ``W_out``) over device busy time, in the traced part of the
window."""
import qwen3_next_trace


def reduce(src):
    return qwen3_next_trace.share_of_busy(src, "gdn")
