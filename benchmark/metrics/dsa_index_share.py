"""Kernels: device time of the ops under the indexer's scopes
(``dsa_index_proj``: its projections, the key's LayerNorm, the rotation and
the index-key write; ``dsa_index_score``: the scores kernel) over device busy
time, in the traced part of the window."""
import dsa_trace


def reduce(src):
    return dsa_trace.share_of_busy(src, "dsa_index_proj", "dsa_index_score")
