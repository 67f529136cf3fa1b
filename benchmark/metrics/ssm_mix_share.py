"""Step programs: device time of the ops under the ``ssm_mix`` scope (a Mamba
layer's convolution, gates and the two scan kernels) over device busy time,
in the traced part of the window."""
import ssm_trace


def reduce(src):
    return ssm_trace.share_of_busy(src, "ssm_mix")
