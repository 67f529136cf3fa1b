"""Step programs: device time of the ops under the ``attn`` scope (the three
gated full-attention mixers: projections, a head's norms, the partial rotation,
the ragged kernel's calls, the output gate and ``W_o``) over device busy time,
in the traced part of the window."""
import qwen3_next_trace


def reduce(src):
    return qwen3_next_trace.share_of_busy(src, "attn")
