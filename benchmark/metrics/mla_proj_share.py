"""Step programs: device time of the ops under the ``mla_proj`` scope (the
query's and the cache's down- and up-projections, the absorption of ``W_UK``
and ``W_UV``, and ``W_o``) over device busy time, in the traced part of the
window."""
import mla_trace


def reduce(src):
    return mla_trace.share_of_busy(src, "mla_proj")
