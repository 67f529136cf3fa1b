"""Step programs: device time of the ops under the ``window_attn`` scope (the
five window mixers: projections at 64 heads on 8 KV heads, the partial
rotation, the ring's write, the ragged kernel's calls under the window with
the sink, and ``W_o``) over device busy time, in the traced part of the
window."""
import mimo_trace


def reduce(src):
    return mimo_trace.share_of_busy(src, "window_attn")
