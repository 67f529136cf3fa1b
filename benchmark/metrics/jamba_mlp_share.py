"""Step programs: device time of the ops under the ``jamba_mlp`` scope (every
layer's dense SwiGLU of 8,192 and the norm before it, 28 a step) over device
busy time, in the traced part of the window."""
import jamba_trace


def reduce(src):
    return jamba_trace.share_of_busy(src, "jamba_mlp")
