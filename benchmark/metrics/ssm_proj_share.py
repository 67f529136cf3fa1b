"""Step programs: device time of the ops under the ``ssm_proj`` scope (a Mamba
layer's in_proj, x_proj, dt_proj and out_proj) over device busy time, in the
traced part of the window."""
import ssm_trace


def reduce(src):
    return ssm_trace.share_of_busy(src, "ssm_proj")
