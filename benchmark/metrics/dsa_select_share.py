"""Kernels: device time of the ops under the ``dsa_select`` scope (the search
for the k-th largest index score of every query, the tie rule, and the
selection laid out for the attention kernel) over device busy time, in the
traced part of the window."""
import dsa_trace


def reduce(src):
    return dsa_trace.share_of_busy(src, "dsa_select")
