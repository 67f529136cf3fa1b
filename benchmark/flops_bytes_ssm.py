"""The operations and bytes that a decoder-hybrid-decoder model's new kernels
*require*, from what the program counted, for a configuration with
Phi-4-mini-flash's keys (``hidden_size``, ``num_hidden_layers``,
``num_key_value_heads``, ``num_attention_heads``, ``sliding_window``) and the
family's Mamba sizes (``mamba_expand`` 2, ``mamba_d_state`` 16 where the file
states neither).

**The selective scan.** A token of a channel, with ``N = d_state`` state
elements: the decay's exponent (``N`` multiplies), its ``exp`` (``N``,
counted as one operation each), the decay (``N``), the input ``B_t u_t``
(``N``) and its add (``N``), the output ``C_t . S`` (``2 N``): ``7 N``
operations (112 at 16), ``7 N d_inner`` a token a layer (573,440). These are
VECTOR operations: nothing in the recurrence is a matrix product, so the peak
that bounds a compute-bound scan is the VPU's, which ``peaks.json`` does not
state; ``flops_bytes.least_seconds`` divides by the MXU's bf16 peak, 197
TFLOP/s, which the VPU cannot reach, so a reading against it is a LOWER bound
of the share of the true peak (``ssm_scan_roofline``'s docstring).

Bytes: a span's float32 state ``[N, d_inner]`` is read once and written once a
layer call, whatever the span's length (a decode row's every step; a chunk's
once for its hundreds of tokens); a token's ``dt`` and ``u`` rows (float32,
``d_inner`` each) and ``B``, ``C`` (``N`` each) are read and its ``y`` row
written. The broadcast of ``B`` and ``C`` to whole lanes on the device is not
required work.

**The window and the one cache.** A window layer's call must read the keys
and values its queries may see: ``min(kv_len, span + window - 1)`` rows of ``2
x KD`` values a span (512 a decode row), which the program counts as
``window_kv_tokens``, a layer call (``ragged_grid_counts(window=)``'s
``kv_tokens``). Whole blocks and whole groups that the kernel's walk fetches
beyond them are waste, not required work: a walk that fetched less would take
less time for the same bytes and the share would rise. The middle full layer
and every cross layer must read each live row's whole cache once a call:
``kv_tokens`` of the ``dispatch`` span (the middle layer's call; a cross
layer's one row a slot sees the same rows).

The program's ``dispatch`` span counts, for ONE Mamba layer call,
``state_rows`` (live spans), ``scan_spans`` / ``scan_tokens`` (those longer
than one token, through the chunked scan); every Mamba layer runs the same
spans.
"""


def d_inner(c):
    return c.get("mamba_expand", 2) * c["hidden_size"]


def d_state(c):
    return c.get("mamba_d_state", 16)


def ssm_layers(c):
    """Mamba layers: every second layer of the self-decoder and the one
    that makes the memory."""
    return c["num_hidden_layers"] // 4 + 1


def window_layers(c):
    return c["num_hidden_layers"] // 4


def token_ops(c):
    """Vector operations of one token of one layer."""
    return 7 * d_state(c) * d_inner(c)


def state_bytes(c):
    """One layer's float32 state of one sequence."""
    return 4 * d_state(c) * d_inner(c)


def _token_bytes(c):
    return 4 * (3 * d_inner(c) + 2 * d_state(c))


def recurrence_work(c, tokens, spans):
    """(operations, bytes) of every Mamba layer for ``tokens`` tokens in
    ``spans`` spans as one layer call counts them."""
    layers = ssm_layers(c)
    return (layers * tokens * token_ops(c),
            layers * (2 * spans * state_bytes(c) + tokens * _token_bytes(c)))


def update_work(c, rows):
    """Decode rows: one token a span."""
    return recurrence_work(c, rows, rows)


def kv_row_bytes(c, itemsize=2):
    """A cached token's keys and values in one layer."""
    head_dim = c["hidden_size"] // c["num_attention_heads"]
    return 2 * c["num_key_value_heads"] * head_dim * itemsize


def cache_readers(c):
    """Layers that read the one cache: the middle full layer and the cross
    layers."""
    return c["num_hidden_layers"] // 4


def window_bytes(c, kv_tokens, itemsize=2):
    """Bytes of the ``kv_tokens`` cached rows a window layer call must read,
    over all the window layers."""
    return window_layers(c) * kv_tokens * kv_row_bytes(c, itemsize)


def cache_bytes(c, kv_tokens, itemsize=2):
    """Bytes of the ``kv_tokens`` rows of the one cache that a call over it
    must read, over the layers that read it."""
    return cache_readers(c) * kv_tokens * kv_row_bytes(c, itemsize)
