"""The operations and bytes that a Jamba model's kernels *require*, from what
the program counted, for a configuration with Jamba's keys
(``num_hidden_layers``, ``attn_layer_period``, ``attn_layer_offset``,
``hidden_size``, ``mamba_expand``, ``mamba_d_state``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``). Conventions as in ``flops_bytes.py``:
a multiply-add is 2 FLOPs.

**Which layer is which.** Layer ``l`` is attention where ``l %
attn_layer_period == attn_layer_offset``, else Mamba: 2 and 26 of the
published 28.

**The selective scan** is ``kernels.selective_scan``'s at Phi-4-mini-flash's
channel and state sizes, so a token's and a span's work are
``flops_bytes_ssm``'s rules, imported: ``7 N d_inner`` VECTOR operations a
token a layer (573,440), a span's float32 state read once and written once a
layer call whatever its length, a token's ``dt``, ``u``, ``y`` rows and ``B``,
``C``. ``flops_bytes.least_seconds`` divides the operations by the MXU's bf16
peak, which vector work cannot reach: a share against it is a LOWER bound of
the share of the true peak. Only the number of layers differs (that file's
``ssm_layers`` is Phi-4-mini-flash's layout).

**Attention.** An attention layer call multiplies each query by the keys it
may see and the probabilities by their values: ``attn_pairs`` causal (query,
key) pairs of the ``dispatch`` span, ``4 head_dim`` FLOPs a pair a QUERY head
(20). It must read each live row's cached keys and values once, whatever the
number of query heads that share them: ``kv_tokens`` rows of ``2 x
num_key_value_heads x head_dim`` values (512 B at one head of 128 in
bfloat16), and read the step's queries and write their outputs
(``query_tokens`` rows of ``num_attention_heads x head_dim`` each way). Whole
blocks the kernel's walk fetches beyond them, and keys fetched again for a
second query block, are waste, not required work.

The program's ``dispatch`` span counts, for ONE layer call, ``state_rows``
(live spans), ``scan_spans`` / ``scan_tokens`` (those longer than one token,
through the chunked scan), ``kv_tokens`` / ``attn_pairs``; every layer of a
kind runs the same spans.
"""
import flops_bytes_ssm


def attn_layers(c):
    period, offset = c["attn_layer_period"], c["attn_layer_offset"]
    return sum(1 for l in range(c["num_hidden_layers"])
               if l % period == offset)


def ssm_layers(c):
    return c["num_hidden_layers"] - attn_layers(c)


def recurrence_work(c, tokens, spans):
    """(operations, bytes) of every Mamba layer for ``tokens`` tokens in
    ``spans`` spans as one layer call counts them."""
    layers = ssm_layers(c)
    token_bytes = 4 * (3 * flops_bytes_ssm.d_inner(c)
                       + 2 * flops_bytes_ssm.d_state(c))
    return (layers * tokens * flops_bytes_ssm.token_ops(c),
            layers * (2 * spans * flops_bytes_ssm.state_bytes(c)
                      + tokens * token_bytes))


def update_work(c, rows):
    """Decode rows: one token a span."""
    return recurrence_work(c, rows, rows)


def head_dim(c):
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def kv_row_bytes(c, itemsize=2):
    """A cached token's keys and values in one attention layer."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * itemsize


def attention_work(c, attn_pairs, kv_tokens, query_tokens, itemsize=2):
    """(FLOPs, bytes) of every attention layer's kernel call for one layer
    call's ``attn_pairs`` (query, key) pairs over ``kv_tokens`` cached rows
    from ``query_tokens`` packed queries."""
    layers = attn_layers(c)
    wide = c["num_attention_heads"] * head_dim(c)
    return (layers * 4 * wide * attn_pairs,
            layers * (kv_tokens * kv_row_bytes(c, itemsize)
                      + 2 * query_tokens * wide * itemsize))
