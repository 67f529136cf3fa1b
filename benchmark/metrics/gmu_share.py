"""Step programs: device time of the ops under the ``gmu`` scope (the Gated
Memory Units' two projections and gate, at one row a slot) over device busy
time, in the traced part of the window."""
import ssm_trace


def reduce(src):
    return ssm_trace.share_of_busy(src, "gmu")
