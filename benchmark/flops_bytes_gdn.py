"""The operations and bytes that the gated delta rule *requires*, from what
the program counted, for a configuration with Olmo Hybrid's keys
(``layer_types``, ``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``).

What is counted is the RECURRENCE's own work, whichever form computed it. A
token of a head, with ``dk x dv`` the state: the decay (``dk dv``), the read
``S^T k`` (``2 dk dv``), the rank-one write (``2 dk dv``) and the output ``S^T
q`` (``2 dk dv``): ``7 dk dv`` FLOPs (129,024 at 96 x 192). The chunked form's
extra products (the chunk's Gram matrices, the triangular solve) are
overhead, not required work, so no reading passes 100 %. Unlike
``flops_bytes.py`` this counts vector operations: the decode-row update has no
matrix multiplication at all.

Bytes: a span's float32 state is read once and written once a layer call,
whatever the span's length (a decode row's every step; a chunk's once for its
hundreds of tokens), and a token's ``q, k, v`` rows are read (float32 as the
kernels take them) and its ``o`` row written. The padding of the state's minor
dim to whole lanes on the device is not required work.

The program's ``dispatch`` span counts, for ONE linear layer call,
``state_rows`` (live spans), ``scan_spans`` / ``scan_tokens`` (those longer
than one token, through the chunked scan); every linear layer runs the same
spans.
"""


def linear_layers(c):
    return sum(1 for t in c["layer_types"] if t == "linear_attention")


def token_flops(c):
    """FLOPs of one token of one head."""
    return 7 * c["linear_key_head_dim"] * c["linear_value_head_dim"]


def state_bytes(c):
    """One layer's float32 state of one sequence."""
    return 4 * c["linear_num_value_heads"] * c["linear_key_head_dim"] \
        * c["linear_value_head_dim"]


def _token_bytes(c):
    return 4 * c["linear_num_value_heads"] * (
        2 * c["linear_key_head_dim"] + 2 * c["linear_value_head_dim"])


def recurrence_work(c, tokens, spans):
    """(FLOPs, bytes) of every linear layer for ``tokens`` tokens in
    ``spans`` spans as one layer call counts them."""
    layers = linear_layers(c)
    flops = layers * tokens * c["linear_num_value_heads"] * token_flops(c)
    return flops, layers * (2 * spans * state_bytes(c)
                            + tokens * _token_bytes(c))


def update_work(c, rows):
    """Decode rows: one token a span."""
    return recurrence_work(c, rows, rows)
