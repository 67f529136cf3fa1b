"""Step programs: device time of leaf ops under none of the scopes
``mimo_trace`` knows (``attn``, ``window_attn``, ``moe``, ``mlp``,
``lm_head``) over device busy time, in the traced part of the window: the
embedding gather, the final norm, sampling, and whatever a refactor moves out
from under its scope."""
import mimo_trace


def reduce(src):
    return mimo_trace.share_of_busy(src, "unscoped")
