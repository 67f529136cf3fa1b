"""The operations and bytes that learned sparse attention over a latent cache
*requires* (an indexer's scores, then the absorbed-form attention over the
selected rows only), from what the program counted, for a configuration with
GLM-5.2's keys (``index_n_heads``, ``index_head_dim``, ``index_topk``,
``kv_lora_rank``, ``qk_rope_head_dim``, ``num_attention_heads``). The least
work, **whatever implements it**: a kernel that walks every cached row and
masks does more, and its share of the roofline then reads low by
construction.

Conventions as in ``flops_bytes.py``: a multiply-add is 2 FLOPs, only matrix
multiplications are counted (the ReLU, the heads' weighted sum and the
selection itself are not).

**The indexer.** A (query, key) pair of a layer that has an indexer costs a
dot of ``index_head_dim`` for each of ``index_n_heads`` heads. A cached index
key (``index_head_dim`` values, one head) is read once a (row, layer) for all
of the row's queries; each query's ``index_n_heads x index_head_dim`` values
are read once. The program's ``dispatch`` span counts, summed over the step's
layers that have an indexer, ``index_key_rows`` ((query, key) pairs scored)
and ``index_query_rows`` (queries); the keys read are those layers times the
span's ``kv_tokens``. Decode rows are bound by the keys' bytes, a 512-token
chunk by the FLOPs.

**The attention.** A query attends over ``min(position + 1, index_topk)``
rows a layer; ``selected_rows`` is their sum over queries and ALL layers (a
layer that borrows a selection still attends over it). A selected (query,
key, head) triple costs the absorbed form's ``2 (rank + rope) + 2 rank``
FLOPs (``flops_bytes_mla.triple_flops``); a selected row's ``rank + rope``
values are read once a (query, layer) for all heads, and each query's
``heads`` wide rows are read (``rank + rope``) and written (``rank``) a
layer.
"""
import flops_bytes_mla


def indexer_layers(c):
    """The layers that have an indexer."""
    return sum(1 for kind in c["indexer_types"] if kind == "full")


def index_work(c, index_query_rows, index_key_rows, kv_tokens,
               bytes_per_el=2):
    """(FLOPs, bytes) of the index scores of steps that counted
    ``index_query_rows`` / ``index_key_rows`` (summed over the layers with
    an indexer) and ``kv_tokens`` (one layer call's cached rows)."""
    heads, dim = c["index_n_heads"], c["index_head_dim"]
    flops = 2 * dim * heads * index_key_rows
    values = indexer_layers(c) * kv_tokens * dim \
        + index_query_rows * heads * dim
    return flops, values * bytes_per_el


def attention_work(c, selected_rows, query_rows, bytes_per_el=2):
    """(FLOPs, bytes) of the attention over ``selected_rows`` rows (summed
    over queries and layers) for ``query_rows`` (query, layer) pairs."""
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    heads = c["num_attention_heads"]
    flops = selected_rows * heads * flops_bytes_mla.triple_flops(c)
    values = selected_rows * (rank + rope) \
        + query_rows * heads * (2 * rank + rope)
    return flops, values * bytes_per_el
