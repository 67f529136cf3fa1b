"""The operations and bytes that a one-mixer-a-block model's new kernels and
its two-matrix experts *require*, from what the program counted, for a
configuration with Nemotron-H's keys (``mamba_num_heads``, ``mamba_head_dim``,
``n_groups``, ``ssm_state_size``, ``chunk_size``, ``hidden_size``,
``moe_intermediate_size``). Conventions as in ``flops_bytes.py``: a
multiply-add is 2 FLOPs.

**A decode row** (``ssd_recurrent_update``), a (row, block): a head's state
``[P, N]`` float32 is decayed, takes the rank-one ``dt x (x) B`` and is read
out by ``C``: ``5 P N`` vector operations a head (a multiply for the decay,
a multiply-add for the update, a multiply-add for the read-out); nothing here
is a matrix product. Bytes: the state ``H P N`` float32 read once and written
once (2 x 2 MiB at 64 x 64 x 128: what bounds the row), the row's ``x``
(``H P``), ``dt`` (``H``), ``B`` and ``C`` (``G N`` each) read and ``y`` (``H
P``) written, float32.

**A prefill span** (``ssd_chunk_scan``), a (token, block), in the chunked
(dual) form at the configuration's ``chunk_size`` ``Q`` (the published
kernel's block; the kernel here walks blocks of its own size, which is its
business): a head multiplies ``((C B^T) * L) X`` (``2 Q P`` a token, and
``C B^T`` once a group: ``2 Q N`` a token shared by ``H / G`` heads), reads
the carried state out (``2 N P``) and adds the token to it (``2 P N``):
matrix products, on the MXU. Bytes: a token's ``x`` and ``y`` (``H P`` each),
``B``, ``C`` (``G N`` each) and ``dt`` (``H``), float32; and a SPAN's state
read once and written once whatever its length.

The program's ``dispatch`` span counts ``ssd_update_rows``, ``ssd_scan_tokens``
and ``ssd_scan_spans`` already summed over the step's Mamba-2 blocks.

**Two-matrix experts.** A live (token, expert) pair on a held expert is
multiplied by that expert's ``W_up`` and ``W_down`` at
``moe_intermediate_size``: TWO matrices (``flops_bytes_mla.expert_params``
reckons a SwiGLU's three); a held expert some pair touched is read once a
layer call. ``moe_pairs`` / ``moe_experts_touched`` are already summed over
the layer calls.
"""


def _sizes(c):
    return (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
            c["ssm_state_size"])


def state_bytes(c):
    """One block's float32 state of one sequence."""
    H, P, _, N = _sizes(c)
    return 4 * H * P * N


def _token_bytes(c):
    H, P, G, N = _sizes(c)
    return 4 * (2 * H * P + 2 * G * N + H)


def update_work(c, rows):
    """(operations, bytes) of ``rows`` (row, block) pairs of one token."""
    H, P, _, N = _sizes(c)
    return (rows * 5 * H * P * N,
            rows * (2 * state_bytes(c) + _token_bytes(c)))


def scan_work(c, tokens, spans):
    """(FLOPs, bytes) of ``tokens`` (token, block) pairs in ``spans`` (span,
    block) pairs through the dual form."""
    H, P, G, N = _sizes(c)
    Q = c.get("chunk_size", 128)
    per_token = H * (2 * Q * P + 4 * P * N) + G * 2 * Q * N
    return (tokens * per_token,
            tokens * _token_bytes(c) + spans * 2 * state_bytes(c))


def expert_params(c):
    """Weights of ONE routed expert (up, down)."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def mlp_experts_work(c, pairs, experts_touched, bytes_per_el=2):
    """(FLOPs, bytes) of the grouped matmuls for ``pairs`` live pairs on
    held experts over ``experts_touched`` held experts read (both summed
    over layer calls)."""
    flops = 2 * expert_params(c) * pairs
    rows = pairs * (2 * c["hidden_size"] + 2 * c["moe_intermediate_size"])
    return flops, (experts_touched * expert_params(c) + rows) * bytes_per_el
