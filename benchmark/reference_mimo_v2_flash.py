"""Plain reference for MiMo-V2-Flash (``model_type`` ``mimo_v2_flash``): the
forward pass as ISSUE 56 writes it from the catalog row's config, in
straightforward ``jax.numpy`` and float32, matmul precision ``highest``. No
kernel, no cache, no ring, no batching: a window layer is a dense masked
softmax with the sink as one more column; a layer at a time, one sequence at a
time, a block of queries at a time, so that it fits beside the engine.

Every layer, ``x`` the residual stream, no bias anywhere:

    x = x + Attn(N(x; ln1));   x = x + FFN(N(x; ln2))
    N(x; w) = x / sqrt(mean(x^2) + eps) * w

Layer ``i`` is a window layer where ``pattern[i]`` is 1, else full; its FFN is
the routed one where ``moe[i]`` is 1, else a dense SwiGLU.

- attention, both kinds (64 query heads, keys 192 wide, values 128): ``q = x
  W_q``, ``k = x W_k``, ``v = v_scale * (x W_v)``; the first ``rotary`` values
  of a head of ``q`` and ``k`` rotated (half-split inside them), the rest
  left; ``s_ij = q_i . k_j * 192^-0.5``; a KV head serves ``heads / kv heads``
  query heads; ``o = concat_h(p v) W_o``.
- full layer (4 KV heads, theta 5,000,000): ``j <= i``, plain softmax.
- window layer (8 KV heads, theta 10,000): ``i - window < j <= i`` and a sink
  ``b_h`` a query head: ``p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))``.
- routed FFN: ``s = sigmoid(x W_r)``; the ``top_k`` largest of ``s + c``;
  weights ``s_e / sum_picked s``, times ``routed_scale``; the HELD experts'
  part of the sum only (ids ``first_held .. + held``: this chip's share; what
  the absent experts would add is left out, as in the program); an expert is
  ``(silu(x W_g) * (x W_u)) W_d``. No shared expert.

**Teacher-forced routing**: where the model has a record of the experts the
serving programs picked (``served_router_picks``), those are used in place of
this file's own top-k (``benchmark/reference_deepseek_v2.py`` has the reason:
near a tie the float32 reference and the bfloat16 program may pick
differently, and the logits then differ by a pick and not by an error); the
router's ``s + c`` are returned for the check's margin on the picks
themselves.

Departures from the published description (each also in the configuration's
``assumed``): the three multi-token prediction layers are not built (the
published config has no key for them; plain decoding does not use them);
``attention_chunk_size`` equals the window and changes no equation; the value
scale multiplies ``v`` after its projection (attention is linear in ``v``);
the rotated values are the first of a head, half-split (with random weights
an interleaved pairing relabels columns of ``W_q``, ``W_k``).
"""
import functools

import jax
import jax.numpy as jnp

BLOCK = 128
VOCAB_BLOCK = 8192
FFN_BLOCK = 2048
ATTN = ("wq", "wk", "wv", "wo", "input_ln", "post_ln")
DENSE = ATTN + ("w_gate", "w_up", "w_down")
ROUTED = DENSE + ("router", "router_bias")
EXPERTS = ("w_gate", "w_up", "w_down")


def weights_of(model):
    """The arrays of a ``MiMoV2FlashForCausalLM`` by the names used here: the
    dense layers' under ``dense`` ``[dense layers, ...]``, the periods' full
    layers' under ``full`` ``[periods, ...]``, the window layers' under
    ``window``, one tree ``[periods, ...]`` for each place in the period."""
    def tree(prefix, names):
        return {n: getattr(model, prefix + n).value for n in names}

    return dict(
        dense=tree("dense_", DENSE), full=tree("", ROUTED),
        window=tuple(tree(f"window{j}_", ROUTED + ("sink",))
                     for j in range(model.config.window_per_period)),
        embed=model.embed_tokens.value, final_norm=model.final_norm.value,
        lm_head=model.lm_head.value,
        # ids [B, S] -> the experts the serving programs used, [L_routed, B,
        # S, top_k], -1 where they did not run; or None
        served_picks=getattr(model, "served_router_picks", None))


def hyper_of(config):
    return {"pattern": tuple(int(p) for p in config.hybrid_layer_pattern),
            "moe": tuple(int(m) for m in config.moe_layer_freq),
            "num_heads": int(config.num_attention_heads),
            "head_dim": int(config.head_dim),
            "v_head_dim": int(config.v_head_dim),
            "rotary": int(config.head_dim * config.partial_rotary_factor),
            "theta": float(config.rope_theta),
            "swa_theta": float(config.swa_rope_theta),
            "window": int(config.sliding_window),
            "v_scale": float(config.attention_value_scale),
            "eps": float(config.layernorm_epsilon),
            "top_k": int(config.num_experts_per_tok),
            "norm_topk_prob": bool(config.norm_topk_prob),
            "routed_scale": float(config.routed_scaling_factor or 1.0),
            "first_held": int(config.first_held_expert)}


def _f32(x):
    return x.astype(jnp.float32)


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotate(x, rot, theta):
    """``x [S, heads, hd]`` with the first ``rot`` values of every head
    rotated at the row's position (half-split), the rest left."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def attention(u, w, hy, window):
    """One layer's attention on one sequence ``u [S, hidden]`` (already
    normalised), a block of queries at a time: a full layer (``window``
    False: causal, no sink) or a window layer (the last ``hy["window"]`` keys
    and the sink ``w["sink"]`` as one more column of the softmax)."""
    nh, hd, vd = hy["num_heads"], hy["head_dim"], hy["v_head_dim"]
    S = u.shape[0]
    nkv = w["wk"].shape[-1] // hd
    theta = hy["swa_theta"] if window else hy["theta"]
    q = rotate((u @ w["wq"]).reshape(S, nh, hd), hy["rotary"], theta)
    k = rotate((u @ w["wk"]).reshape(S, nkv, hd), hy["rotary"], theta)
    v = hy["v_scale"] * (u @ w["wv"]).reshape(S, nkv, vd)
    pad = -S % BLOCK
    # query head h reads KV head h // (nh / nkv)
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        S + pad, nkv, nh // nkv, hd)
    cols = jnp.arange(S)[None, :]

    def one(start):
        rows = (start + jnp.arange(BLOCK))[:, None]
        seen = cols <= rows
        if window:
            seen = seen & (cols > rows - hy["window"])
        scores = jnp.einsum(
            "qkgd,skd->kgqs", jax.lax.dynamic_slice_in_dim(qp, start, BLOCK),
            k) * hd ** -0.5
        scores = jnp.where(seen, scores, -jnp.inf)
        if window:
            scores = jnp.concatenate([scores, jnp.broadcast_to(
                w["sink"].reshape(nkv, -1, 1, 1),
                scores.shape[:3] + (1,))], -1)
        probs = jax.nn.softmax(scores, -1)[..., :S]
        return jnp.einsum("kgqs,skd->qkgd", probs, v)

    o = jax.lax.map(one, jnp.arange(0, S + pad, BLOCK))
    return o.reshape(S + pad, nh * vd)[:S] @ w["wo"]


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def dense_ffn(u, w):
    """The dense SwiGLU, a block of its intermediate units at a time (the sum
    over the blocks is the whole: no float32 copy of the three matrices)."""
    width = w["w_gate"].shape[-1]
    block = min(FFN_BLOCK, width)
    if width % block:
        raise ValueError(f"a dense FFN of {width} units is no whole number "
                         f"of blocks of {block}")

    def one(acc, start):
        cut = [_f32(jax.lax.dynamic_slice_in_dim(
            w[n], start, block, 0 if n == "w_down" else 1))
            for n in EXPERTS]
        return acc + _swiglu(u, *cut), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          jnp.arange(0, width, block))
    return out


def route(scores, bias, forced, hy):
    """(experts [S, top_k], weights [S, top_k]) of one sequence from the
    sigmoid ``scores [S, E]``: the ``top_k`` largest of ``scores + bias``,
    or ``forced`` where it is not -1; weights the unbiased scores, divided
    by their sum over the picks (``norm_topk_prob``), times the scale."""
    _, top_e = jax.lax.top_k(scores + bias, hy["top_k"])
    top_e = jnp.where(forced[:, :1] >= 0, forced, top_e)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    if hy["norm_topk_prob"]:
        top_s = top_s / jnp.sum(top_s, -1, keepdims=True)
    return top_e, hy["routed_scale"] * top_s


def routed_ffn(u, w, forced, hy):
    """(the HELD experts' part of the routed sum, the router's ``s + c``) of
    one sequence; nothing beside it."""
    scores = jax.nn.sigmoid(u @ w["router"])
    top_e, top_s = route(scores, w["router_bias"], forced, hy)

    def one_expert(acc, j):
        weight = jnp.sum(
            jnp.where(top_e == hy["first_held"] + j, top_s, 0.0), -1)
        cut = (_f32(jax.lax.dynamic_index_in_dim(w[n], j, 0, False))
               for n in EXPERTS)
        return acc + weight[:, None] * _swiglu(u, *cut), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          jnp.arange(w["w_up"].shape[0]))
    return out, scores + w["router_bias"]


@functools.partial(jax.jit, static_argnames=("hyper", "window", "routed"))
def _layer(x, stacked, p, forced, *, hyper, window, routed):
    """Layer ``p`` of a stack on ONE sequence's hidden states x [S, H]
    (float32): (x', the router's ``s + c`` [S, E] or None)."""
    hy = dict(hyper)
    w = {n: jax.lax.dynamic_index_in_dim(a, p, 0, keepdims=False)
         for n, a in stacked.items()}
    w = {n: (a if n in EXPERTS else _f32(a)) for n, a in w.items()}
    x = x + attention(norm(x, w["input_ln"], hy["eps"]), w, hy, window)
    u = norm(x, w["post_ln"], hy["eps"])
    if not routed:
        return x + dense_ffn(u, w), None
    out, probs = routed_ffn(u, w, forced, hy)
    return x + out, probs


def layer_plan(hyper):
    """The layers in order as ``(stack, place in the period or None, index in
    the stack, window layer, routed FFN)``: the dense layers, then the
    periods' layers, a period's window layers before its full one."""
    pattern, moe = hyper["pattern"], hyper["moe"]
    n_dense = moe.index(1) if 1 in moe else len(moe)
    per = pattern[n_dense:].index(0) + 1
    plan = [("dense", None, i, False, False) for i in range(n_dense)]
    for i in range(len(pattern) - n_dense):
        p, j = divmod(i, per)
        plan.append(("full", None, p, False, True) if j == per - 1
                    else ("window", j, p, True, True))
    return plan


def sequence_states(weights, hyper, row, forced):
    """One sequence's final-norm hidden states [S, H], float32, and every
    routed layer's ``s + c`` [L_routed, S, E]; ``forced [L_routed, S,
    top_k]`` the picks to use (-1: this file's own)."""
    static = tuple(sorted(hyper.items()))
    x = _f32(jnp.take(weights["embed"], row, axis=0))
    probs, n = [], 0
    for stack, place, p, window, routed in layer_plan(hyper):
        stacked = weights[stack] if place is None else weights[stack][place]
        told = forced[n] if routed else forced[0]
        x, pr = _layer(x, stacked, jnp.int32(p), told, hyper=static,
                       window=window, routed=routed)
        if routed:
            probs.append(pr)
            n += 1
    return norm(x, _f32(weights["final_norm"]), hyper["eps"]), probs


def logits_at(weights, hyper, ids, at, with_router=False):
    """Float32 logits [B, K, V] at the K positions ``at[b]`` of each row;
    with ``with_router`` also the routed layers' float32 ``s + c`` at those
    positions, [L_routed, B, K, E]: their ``top_k`` largest are the experts
    the layer used."""
    hyper = dict(hyper)
    ids, at = jnp.asarray(ids, jnp.int32), jnp.asarray(at, jnp.int32)
    n_routed = sum(hyper["moe"])
    logits, probs = [], []
    with jax.default_matmul_precision("highest"):
        served = weights.get("served_picks")
        served = None if served is None else served(ids)
        head = weights["lm_head"]
        for b in range(ids.shape[0]):
            told = jnp.full((n_routed, ids.shape[1], hyper["top_k"]), -1,
                            jnp.int32) if served is None \
                else jnp.asarray(served[:, b], jnp.int32)
            x, pr = sequence_states(weights, hyper, ids[b], told)
            picked = jnp.take(x, at[b], axis=0)
            logits.append(jnp.concatenate(
                [picked @ _f32(head[:, v:v + VOCAB_BLOCK])
                 for v in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1))
            probs.append(jnp.stack([jnp.take(p, at[b], axis=0)
                                    for p in pr]))
    if not with_router:
        return jnp.stack(logits)
    return jnp.stack(logits), jnp.stack(probs, axis=1)
