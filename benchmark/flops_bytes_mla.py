"""The operations and bytes that latent (MLA) attention in the ABSORBED form
and a chip's HELD share of a routed FFN *require*, from what the program
counted, for a configuration with DeepSeek-V2's keys (``kv_lora_rank``,
``qk_rope_head_dim``, ``num_attention_heads``, ``num_hidden_layers``,
``hidden_size``, ``moe_intermediate_size``).

Conventions as in ``flops_bytes.py``: a multiply-add is 2 FLOPs, only matrix
multiplications are counted.

**Attention.** With ``W_UK`` folded into the query and ``W_UV`` into the
output, every head of a query token scores the same cached row: a (query,
key, head) triple costs a dot over the row's ``rank + rope`` values and a
weighted sum over its ``rank`` latent values, ``2 (rank + rope) + 2 rank``
FLOPs (2,176 at 512 + 64; the expanded form's is ``2 (nope + rope) + 2 v`` =
640, which is why a prefill chunk is dearer here: PERF.md). A cached token's
row of ``rank + rope`` values is read once a layer call for all heads (the
lanes it is padded to are not required work), and each query token's
``heads`` wide rows are read (``rank + rope``) and written (``rank``). The
program's ``dispatch`` span counts ``attn_pairs`` (causal query-key pairs)
and ``kv_tokens`` (cached rows the live spans attend over) for ONE layer
call; every layer runs the same spans.

**Held experts.** A live (token, expert) pair on an expert this chip holds
is multiplied by that expert's gate, up and down matrices at
``moe_intermediate_size`` (NOT ``intermediate_size``, the dense layers'
width); a held expert some pair touched is read once a layer call. The
program's ``moe_pairs`` / ``moe_experts_touched`` are already summed over
the layer calls.
"""


def triple_flops(c):
    """FLOPs of one (query, key, head) triple in the absorbed form."""
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return 2 * (rank + rope) + 2 * rank


def absorbed_attention_work(c, attn_pairs, kv_tokens, query_tokens=0,
                            bytes_per_el=2):
    """(FLOPs, bytes) of the absorbed attention of every layer for spans
    that one layer call counts as ``attn_pairs`` / ``kv_tokens`` (and
    ``query_tokens`` packed live tokens)."""
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    heads, layers = c["num_attention_heads"], c["num_hidden_layers"]
    flops = layers * attn_pairs * heads * triple_flops(c)
    rows = kv_tokens * (rank + rope) \
        + query_tokens * heads * (2 * rank + rope)
    return flops, layers * rows * bytes_per_el


def expert_params(c):
    """Weights of ONE routed expert (gate, up, down)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def held_experts_work(c, pairs, experts_touched, bytes_per_el=2):
    """(FLOPs, bytes) of the grouped matmuls for ``pairs`` live pairs on
    held experts over ``experts_touched`` held experts read (both summed
    over layer calls)."""
    flops = 2 * expert_params(c) * pairs
    rows = pairs * (2 * c["hidden_size"] + 2 * c["moe_intermediate_size"])
    return flops, (experts_touched * expert_params(c) + rows) * bytes_per_el
