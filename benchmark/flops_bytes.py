"""The operations and bytes that the work *requires*, from shapes alone.
Every roofline share and the MFU in this benchmark divide by these, so they
live here, where a PR that claims a gain cannot change them.

Conventions (stated again in PERF.md):

- a multiply-add is 2 FLOPs; only matrix multiplications are counted (norms,
  rotary embedding, softmax and activations are a few percent and are left
  out, which makes every share a slight under-estimate);
- causal attention is counted at its causal half: query i reads keys 0..i,
  so a sequence of S positions has S (S + 1) / 2 query-key pairs;
- training is forward + backward = 3 x forward: a weight matmul has a
  d-input and a d-weight matmul of its own size, and attention's backward
  (dV, dP, dQ, dK) is twice its forward (QK^T, PV). Recomputation under
  ``jax.checkpoint`` and the flash backward's own recomputation of the
  scores are *not* required work and are not counted in MFU;
- the embedding lookup is a gather (0 FLOPs); the untied head is a matmul.
"""


import re

_SHAPE = re.compile(r"([a-z]+\d+)\[([\d,]*)\]")


def layer_matmul_params(c):
    """Weights of one decoder layer that a token is multiplied by."""
    h, kv = c["hidden_size"], c["num_key_value_heads"] * head_dim(c)
    return (h * h            # wq
            + 2 * h * kv     # wk, wv
            + h * h          # wo
            + 3 * h * c["intermediate_size"])   # gate, up, down


def head_dim(c):
    return c["hidden_size"] // c["num_attention_heads"]


def param_count(c):
    """All parameters: layers (matmuls + two norms), embedding, final norm,
    untied head."""
    h, v = c["hidden_size"], c["vocab_size"]
    return (c["num_hidden_layers"] * (layer_matmul_params(c) + 2 * h)
            + v * h + h + v * h)


def forward_flops_per_token(c, seq_len):
    """Required forward FLOPs per token of a sequence of ``seq_len``:
    2 x matmul weights (layers and head) + causal attention, which per
    layer and per query-key pair is 2 (QK^T) + 2 (PV) FLOPs for each of
    ``hidden_size`` lanes, over (S + 1) / 2 pairs per token on average."""
    dense = 2 * (c["num_hidden_layers"] * layer_matmul_params(c)
                 + c["hidden_size"] * c["vocab_size"])
    attn = c["num_hidden_layers"] * 4 * c["hidden_size"] * (seq_len + 1) / 2
    return dense + attn


def train_flops_per_token(c, seq_len):
    """Forward + backward, no recomputation: 3 x forward."""
    return 3 * forward_flops_per_token(c, seq_len)


# ------------------------------------------------------------ flash kernels
def flash_call(role, bh, s, d, bytes_per_el=2):
    """(FLOPs, bytes) one causal flash-attention call needs, heads folded
    into ``bh``. ``role``: ``fwd`` (QK^T, PV: 4 per pair-lane), ``dkv``
    (scores again, dV, dP, dK: 8), ``dq`` (scores again, dP, dQ: 6). The
    scores are recomputed by construction of a two-kernel flash backward,
    so each call is charged what it cannot avoid given its inputs. Bytes:
    every input and output tensor read or written once ([bh, s, d] each;
    the [bh, s] float32 row statistics are counted too)."""
    pairs = bh * s * (s + 1) / 2
    per_pair = {"fwd": 4, "dkv": 8, "dq": 6}[role]
    tensors = {"fwd": 4, "dkv": 6, "dq": 5}[role]      # q k v o | +do | ...
    stats = {"fwd": 1, "dkv": 2, "dq": 2}[role]        # lse | lse, delta
    flops = per_pair * pairs * d
    nbytes = tensors * bh * s * d * bytes_per_el + stats * bh * s * 4
    return flops, nbytes


def classify_flash(signature):
    """(role, bh, s, d) of a Mosaic call signature from the trace
    (``xplane_reduce.mosaic_signature``), or None if it is not one of the
    flash kernels: ``fwd`` returns (o [bh,s,d], lse [bh,s,1]); ``dkv``
    returns two [bh,s,d]; ``dq`` returns one [bh,s,d] from six or more
    operands."""
    outs, _, args = signature.partition(" <- ")
    outs, args = _SHAPE.findall(outs), _SHAPE.findall(args)
    outs = [(t, tuple(int(x) for x in d.split(",") if x)) for t, d in outs]
    big = [o for o in outs if len(o[1]) == 3 and o[1][2] > 1]
    if not big:
        return None
    bh, s, d = big[0][1]
    if len(outs) == 2 and len(big) == 1 and len(args) <= 5:
        return "fwd", bh, s, d
    if len(big) == 2:
        return "dkv", bh, s, d
    if len(outs) == 1 and len(args) >= 5:
        return "dq", bh, s, d
    return None


# ------------------------------------------------- ragged (serving) attention
def ragged_work(c, contexts_decoded, prompts_prefilled, bytes_per_el=2):
    """(FLOPs, bytes) the attention of serving work needs, over all layers.

    ``contexts_decoded``: for every token decoded, the context length it
    attended to. ``prompts_prefilled``: for every prompt prefilled, its
    length P (its queries see P (P + 1) / 2 pairs, however it is chunked).
    FLOPs: 4 per pair-lane over ``hidden_size`` lanes. Bytes: the K and V
    a query block must read: a decoded token reads its whole context once;
    a prompt reads each of its keys and values at least once. Query, output
    and table traffic are left out (under 1 % at these context lengths)."""
    layers, hid = c["num_hidden_layers"], c["hidden_size"]
    kv_row = 2 * c["num_key_value_heads"] * head_dim(c) * bytes_per_el
    pairs = sum(contexts_decoded) \
        + sum(p * (p + 1) / 2 for p in prompts_prefilled)
    rows = sum(contexts_decoded) + sum(prompts_prefilled)
    return layers * 4 * hid * pairs, layers * kv_row * rows


def least_seconds(flops, nbytes, peaks):
    """(seconds, which bound) the chip cannot beat for this work."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
