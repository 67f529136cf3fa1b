"""The operations and bytes that a MiMo-V2-Flash model's kernels *require*,
from what the program counted, for a configuration with MiMo-V2-Flash's keys
(``hybrid_layer_pattern``, ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``swa_num_key_value_heads``, ``head_dim``,
``v_head_dim``). Conventions as in
``flops_bytes.py``: a multiply-add is 2 FLOPs; no function here counts
padding, a block fetched beyond the live rows (a window layer's walk fetches
whole groups of blocks from the group its window starts in: that is in the
kernel's time and not in these bytes), or anything read twice, so no share of
a roofline computed from them can pass 100 %.

**Which layer is which.** ``hybrid_layer_pattern[i]`` is 0 for a full layer
(its keys and values in the pool, 4 KV heads) and 1 for a window layer (in its
ring, 8 KV heads): 2 and 5 of the cell's 7.

**Attention** at ``nh`` query heads on ``nkv`` KV heads, keys ``hd`` wide and
values ``vd``: ``2 (hd + vd)`` FLOPs a (query, key) pair a QUERY head (the
score's ``hd`` multiply-adds and the value's ``vd``); each live row's cached
keys and values read once whatever the number of query heads that share them
(``kv_tokens`` rows of ``nkv (hd + vd)`` values: 2,560 B a token a full layer
at 4 heads of 192 | 128 in bfloat16, 5,120 B a window layer at 8), the step's
queries read (``nh hd``) and their outputs written (``nh vd``). A window layer
counts the keys inside the window only (``window_kv_tokens``: ``min(kv_len,
span + window - 1)`` a span; ``window_attn_pairs``: ``min(position + 1,
window)`` a query).

**The routed FFN** has no function here: the held experts' grouped matmuls
are ``flops_bytes_mla.held_experts_work``'s, which ``moe_held_experts_roofline``
reads for this cell as for the other cells that hold a share of the experts.

The program's ``dispatch`` span counts, for ONE layer call of each kind,
``kv_tokens`` / ``attn_pairs`` (a full layer's) and ``window_kv_tokens`` /
``window_attn_pairs`` (a window layer's); every layer of a kind runs the same
spans.
"""


def full_layers(c):
    return sum(1 for p in c["hybrid_layer_pattern"] if p == 0)


def window_layers(c):
    return sum(1 for p in c["hybrid_layer_pattern"] if p == 1)


def kv_row_bytes(c, window, itemsize=2):
    """A cached token's keys and values in one layer of the kind."""
    nkv = c["swa_num_key_value_heads"] if window else c["num_key_value_heads"]
    return nkv * (c["head_dim"] + c["v_head_dim"]) * itemsize


def attention_work(c, attn_pairs, kv_tokens, query_tokens, window,
                   itemsize=2):
    """(FLOPs, bytes) of every layer of the kind (``window``: the window
    layers, else the full ones) for one layer call's ``attn_pairs`` (query,
    key) pairs over ``kv_tokens`` cached rows from ``query_tokens`` packed
    queries."""
    layers = window_layers(c) if window else full_layers(c)
    nh, wide = c["num_attention_heads"], c["head_dim"] + c["v_head_dim"]
    return (layers * 2 * nh * wide * attn_pairs,
            layers * (kv_tokens * kv_row_bytes(c, window, itemsize)
                      + query_tokens * nh * wide * itemsize))
