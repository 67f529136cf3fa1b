"""Step programs: device time of the ops under the ``jamba_attn`` scope (the
two attention layers' projections, the ragged kernel's calls and ``W_o``)
over device busy time, in the traced part of the window."""
import jamba_trace


def reduce(src):
    return jamba_trace.share_of_busy(src, "jamba_attn")
