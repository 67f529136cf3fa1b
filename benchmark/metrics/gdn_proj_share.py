"""Step programs: device time of the ops under the ``gdn_proj`` scope (a
linear layer's input projections and ``W_o``) over device busy time, in the
traced part of the window."""
import gdn_trace


def reduce(src):
    return gdn_trace.share_of_busy(src, "gdn_proj")
