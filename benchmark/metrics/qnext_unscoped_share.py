"""Step programs: device time of leaf ops under none of the scopes
``qwen3_next_trace`` knows (``gdn``, ``attn``, ``moe``, ``moe_shared``,
``lm_head``) over device busy time, in the traced part of the window: the
embedding gather, the final norm, sampling, and whatever a refactor moves out
from under its scope."""
import qwen3_next_trace


def reduce(src):
    return qwen3_next_trace.share_of_busy(src, "unscoped")
