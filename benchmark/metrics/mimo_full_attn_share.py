"""Step programs: device time of the ops under the ``attn`` scope (the two
full-attention mixers: projections at 64 heads of 192 on 4 KV heads, the
partial rotation, the pool's write, the ragged kernel's calls and ``W_o``)
over device busy time, in the traced part of the window."""
import mimo_trace


def reduce(src):
    return mimo_trace.share_of_busy(src, "attn")
