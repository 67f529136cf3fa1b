"""The operations and bytes that a Qwen3-Next model's kernels *require*, from
what the program counted, for a configuration with Qwen3-Next's keys
(``num_hidden_layers``, ``full_attention_interval``, ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``linear_num_key_heads``, ``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``moe_intermediate_size``). Conventions as in
``flops_bytes.py``: a multiply-add is 2 FLOPs; no function here counts
padding, a block fetched beyond the live rows or anything read twice, so no
share of a roofline computed from them can pass 100 %.

**Which layer is which.** Layer ``i`` is full attention where ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet: 3 and 9 of the cell's 12.

**The gated delta rule** at ``hv`` value heads on ``hk`` key heads. What is
counted is the RECURRENCE's own work, whichever form computed it: a token of
a VALUE head, with ``dk x dv`` the state: the decay (``dk dv``), the read
``S^T k`` (``2 dk dv``), the rank-one write (``2 dk dv``) and the output ``S^T
q`` (``2 dk dv``): ``7 dk dv`` VECTOR operations (114,688 at 128 x 128; the
chunked form's Gram matrices and triangular solve are overhead, not required
work). ``flops_bytes.least_seconds`` divides them by the MXU's bf16 peak,
which vector work cannot reach: a share against it is a LOWER bound of the
share of the true peak. Bytes: a span's float32 state ``hv dk dv`` is read
once and written once a layer call, whatever the span's length; a token's
``q`` and ``k`` are read ONCE at the ``hk`` key heads (float32 as the kernels
take them; a value head reads its key head's, the kernel repeats nothing in
HBM), its ``v`` read and its ``o`` written at the ``hv`` value heads, and its
two gates a value head.

**Attention** at ``nh`` query heads on ``nkv`` KV heads of ``hd``: ``4 hd``
FLOPs a (query, key) pair a QUERY head; each live row's cached keys and
values read once whatever the number of query heads that share them
(``kv_tokens`` rows of ``2 nkv hd`` values: 2 KiB at 2 heads of 256 in
bfloat16), the step's queries read and their outputs written.

**The routed FFN** over the HELD experts: a live pair is multiplied by its
expert's gate, up and down matrices (``3 x hidden x width`` multiply-adds), a
touched expert's three matrices are read once a layer call, an expert nobody
picked is not read; the pairs' rows are read and written (hidden in, ``2 x
width`` between, hidden out).

The program's ``dispatch`` span counts, for ONE layer call, ``state_rows``
(live spans), ``scan_spans`` / ``scan_tokens`` (those longer than one token,
through the chunked scan), ``kv_tokens`` / ``attn_pairs``; every layer of a
kind runs the same spans. The routing's counters (``moe_pairs``,
``moe_experts_touched``) are summed over the layer calls already.
"""


def full_layers(c):
    return c["num_hidden_layers"] // c["full_attention_interval"]


def linear_layers(c):
    return c["num_hidden_layers"] - full_layers(c)


def token_ops(c):
    """Vector operations of one token of one VALUE head."""
    return 7 * c["linear_key_head_dim"] * c["linear_value_head_dim"]


def state_bytes(c):
    """One linear layer's float32 state of one sequence."""
    return 4 * c["linear_num_value_heads"] * c["linear_key_head_dim"] \
        * c["linear_value_head_dim"]


def token_bytes(c):
    """What one token brings to and takes from one linear layer's kernel:
    q, k once at the key heads, v and o at the value heads, two gates a
    value head; float32."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    return 4 * (2 * hk * c["linear_key_head_dim"]
                + 2 * hv * c["linear_value_head_dim"] + 2 * hv)


def recurrence_work(c, tokens, spans):
    """(operations, bytes) of every linear layer for ``tokens`` tokens in
    ``spans`` spans as one layer call counts them."""
    layers = linear_layers(c)
    return (layers * tokens * c["linear_num_value_heads"] * token_ops(c),
            layers * (2 * spans * state_bytes(c) + tokens * token_bytes(c)))


def update_work(c, rows):
    """Decode rows: one token a span."""
    return recurrence_work(c, rows, rows)


def kv_row_bytes(c, itemsize=2):
    """A cached token's keys and values in one full layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def attention_work(c, attn_pairs, kv_tokens, query_tokens, itemsize=2):
    """(FLOPs, bytes) of every full layer's kernel call for one layer call's
    ``attn_pairs`` (query, key) pairs over ``kv_tokens`` cached rows from
    ``query_tokens`` packed queries."""
    layers = full_layers(c)
    wide = c["num_attention_heads"] * c["head_dim"]
    return (layers * 4 * wide * attn_pairs,
            layers * (kv_tokens * kv_row_bytes(c, itemsize)
                      + 2 * query_tokens * wide * itemsize))


def expert_params(c):
    """Weights of ONE routed expert (gate, up, down)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def experts_work(c, pairs, experts_touched, itemsize=2):
    """(FLOPs, bytes) of the three grouped matmuls for ``pairs`` live pairs
    on held experts over ``experts_touched`` held experts read (both summed
    over the layer calls)."""
    rows = pairs * (2 * c["hidden_size"] + 2 * c["moe_intermediate_size"])
    return (2 * expert_params(c) * pairs,
            (experts_touched * expert_params(c) + rows) * itemsize)
