"""The plain reference of Qwen3-Next (``model_type`` ``qwen3_next``;
Qwen/Qwen3-Next-80B-A3B-Instruct): periods of Gated DeltaNet layers
(arXiv:2412.06464) and then one gated full-attention layer, every layer
followed by a routed FFN with a gated shared expert; in ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. The delta rule is
the recurrence itself, token by token in a ``lax.scan``: no chunked form, no
kernel, no cache, no batching, no sorting of tokens by expert, and nothing
imported from ``paddle_tpu``: the program hands over its weights
(``weights_of``) and its sizes (``hyper_of``) and is then judged by this file,
through the same three entry points as ``reference.py``.

``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``: every norm weight is
zero-centred but the linear mixer's output norm. Layer ``i`` (from 0), ``x``
the residual stream, no bias anywhere:

    x = x + Mixer_i(N(x; ln1));   x = x + FFN_i(N(x; ln2))

``Mixer_i`` is full attention where ``(i + 1) % full_attention_interval == 0``,
else Gated DeltaNet. After the last layer ``N(x; final_norm)`` and the untied
head.

- Full attention (``nh`` query heads on ``nkv`` KV heads of ``hd``): ``[q |
  gate] = x W_q``, a head's ``2 hd`` columns its query then its gate; ``k = x
  W_k``, ``v = x W_v``; ``q = N(q; q_norm)``, ``k = N(k; k_norm)`` a HEAD; the
  first ``rot`` values of a head of q and k rotated at the token's position
  (``[a | b] -> [a cos - b sin | b cos + a sin]`` over the two halves of those
  ``rot``, frequencies ``theta^(-2 i / rot)``), the other ``hd - rot`` left;
  query head ``h`` reads KV head ``h // (nh / nkv)``; scores ``q . k *
  hd^-0.5``, causal softmax; ``o = (softmax . v) * sigmoid(gate)``;
  ``concat_heads(o) W_o``.
- Gated DeltaNet, a token ``t``: ``u_t = x_t [W_q | W_k | W_v]`` (``hk dk |
  hk dk | hv dv`` channels); a channel ``c``: ``u'_t[c] = silu(sum_j w[j, c] *
  u_{t-(width-1)+j}[c])``, rows before the sequence's start zero, no bias;
  split into ``q_t, k_t`` (``hk`` heads of ``dk``) and ``v_t`` (``hv`` heads of
  ``dv``); ``q_t = q_t / sqrt(|q_t|^2 + 1e-6) * dk^-0.5``, ``k_t`` likewise
  without the scale; VALUE head ``h`` uses q, k of key head ``h // (hv /
  hk)``; ``beta_t = sigmoid(x_t W_b)`` (never doubled); ``g_t = -exp(A_log) *
  softplus(x_t W_a + dt_bias)``; ``S`` (``dk x dv`` a value head, zero at the
  start): ``S = exp(g_t) S``; ``r = v_t - S^T k_t``; ``S = S + k_t (beta_t
  r)^T``; ``o_t = S^T q_t``; ``y_t = (o_t / sqrt(mean(o_t^2) + eps) * w_o) *
  silu(x_t W_z)`` a head (``w_o`` of ``dv``, one vector for all heads, NOT
  zero-centred); ``concat_heads(y_t) W_out``.
- FFN: ``p = softmax(x W_r)`` over the router's whole width; the token's
  experts are the ``top_k`` largest; their weights ``p_e / sum_picked p``
  (``norm_topk_prob``); ``SwiGLU(x; W_g, W_u, W_d) = (silu(x W_g) * (x W_u))
  W_d``; output ``sigmoid(x w_sg) * SwiGLU(x; shared) + sum_e weight_e
  SwiGLU(x; expert e)``.

**The share** and **teacher-forced routing** are ``reference_deepseek_v2``'s,
word for word: the sum over ``e`` runs over the token's experts that this chip
HOLDS (``first_held ..``), and where the program says which experts its
serving programs used, the routed sum runs over exactly those, each at THIS
router's float32 probability, normalised over the ``top_k`` told.
``logits_at(..., with_router=True)`` returns ``p``, the selection's own score.

One sequence at a time, one layer at a time, a block of ``BLOCK`` queries at a
time, one expert's float32 weights at a time, the head a block of the
vocabulary at a time, so that it fits beside the engine it judges.

Departures from the published description (each also in the configuration's
``assumed``): no multi-token prediction module (the published config has no
key for it; plain decoding does not use it); the program stores a linear
layer's ``W_q | W_k | W_v`` as one matrix, ``W_z`` as one and ``W_a | W_b`` as
one, each head-major, where the published code interleaves ``q, k, v, z`` and
``b, a`` by key head: ``weights_of`` hands them over as they lie and this file
cuts them where the equations above do; ``intermediate_size`` is read by
nothing (``decoder_sparse_step`` 1, ``mlp_only_layers`` empty).
"""
import functools

import jax
import jax.numpy as jnp

BLOCK = 256
VOCAB_BLOCK = 8192
FFN = ("input_ln", "post_ln", "router", "w_gate", "w_up", "w_down",
       "ws_gate", "ws_up", "ws_down", "ws_sgate")
FULL = ("wq", "wk", "wv", "wo", "q_norm", "k_norm") + FFN
LINEAR = ("gdn_wqkv", "gdn_wz", "gdn_wab", "gdn_conv", "gdn_A_log",
          "gdn_dt_bias", "gdn_o_norm", "gdn_wo") + FFN
EXPERTS = ("w_gate", "w_up", "w_down")


def weights_of(model):
    """The arrays of a ``Qwen3NextForCausalLM`` by the names used here: the
    full layers' ``[periods, ...]``, the linear layers' under ``linear``, one
    tree ``[periods, ...]`` for each place in the period."""
    w = {n: getattr(model, n).value for n in FULL}
    w["linear"] = tuple(
        {n: getattr(model, f"linear{j}_{n}").value for n in LINEAR}
        for j in range(model.config.linear_per_period))
    w.update(embed=model.embed_tokens.value,
             final_norm=model.final_norm.value, lm_head=model.lm_head.value,
             # ids [B, S] -> the experts the serving programs used, [L, B, S,
             # top_k], -1 where they did not run; or None
             served_picks=getattr(model, "served_router_picks", None))
    return w


def hyper_of(config):
    return {"interval": int(config.full_attention_interval),
            "num_heads": int(config.num_attention_heads),
            "num_kv_heads": int(config.num_key_value_heads),
            "head_dim": int(config.head_dim),
            "rotary": int(config.head_dim * config.partial_rotary_factor),
            "theta": float(config.rope_theta),
            "eps": float(config.rms_norm_eps),
            "key_heads": int(config.linear_num_key_heads),
            "value_heads": int(config.linear_num_value_heads),
            "dk": int(config.linear_key_head_dim),
            "dv": int(config.linear_value_head_dim),
            "top_k": int(config.num_experts_per_tok),
            "norm_topk_prob": bool(config.norm_topk_prob),
            "first_held": int(config.first_held_expert)}


def _f32(x):
    return x.astype(jnp.float32)


def norm(x, w, eps):
    """``N(x; w)``: the weight is zero-centred."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rotate(x, hy):
    """``x [S, heads, hd]`` with the first ``rotary`` values of every head
    rotated at the row's position, the rest left."""
    rot, half = hy["rotary"], hy["rotary"] // 2
    inv = hy["theta"] ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def attention(u, w, hy):
    """The gated full-attention mixer on one sequence ``u [S, hidden]``
    (already normalised), a block of queries at a time."""
    nh, nkv, hd = hy["num_heads"], hy["num_kv_heads"], hy["head_dim"]
    S = u.shape[0]
    qg = (u @ w["wq"]).reshape(S, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (u @ w["wk"]).reshape(S, nkv, hd)
    v = (u @ w["wv"]).reshape(S, nkv, hd)
    q = rotate(norm(q, w["q_norm"], hy["eps"]), hy)
    k = rotate(norm(k, w["k_norm"], hy["eps"]), hy)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    pad = -S % BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    cols = jnp.arange(S)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, BLOCK)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        seen = cols[None, :] <= (start + jnp.arange(BLOCK))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    o = jax.lax.map(one, jnp.arange(0, S + pad, BLOCK))
    o = o.reshape(S + pad, nh, hd)[:S] * jax.nn.sigmoid(gate)
    return o.reshape(S, nh * hd) @ w["wo"]


def delta_net(u, w, hy):
    """The Gated DeltaNet mixer on one sequence ``u [S, hidden]`` (already
    normalised), the recurrence token by token from a zero state."""
    hk, hv, dk, dv = (hy[n] for n in ("key_heads", "value_heads", "dk", "dv"))
    S, nk = u.shape[0], hk * dk
    x = u @ w["gdn_wqkv"]
    width = w["gdn_conv"].shape[0]
    ext = jnp.pad(x, ((width - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(w["gdn_conv"][j] * ext[j:j + S]
                        for j in range(width)))

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = unit(x[:, :nk].reshape(S, hk, dk)) * dk ** -0.5
    k = unit(x[:, nk:2 * nk].reshape(S, hk, dk))
    # value head h reads key head h // (hv / hk)
    q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
    v = x[:, 2 * nk:].reshape(S, hv, dv)
    ab = u @ w["gdn_wab"]
    g = -jnp.exp(w["gdn_A_log"]) * jax.nn.softplus(ab[:, :hv]
                                                   + w["gdn_dt_bias"])
    beta = jax.nn.sigmoid(ab[:, hv:])

    def token(s, t):
        qt, kt, vt, gt, bt = t
        s = s * jnp.exp(gt)[:, None, None]
        r = vt - jnp.einsum("hkv,hk->hv", s, kt)
        s = s + kt[:, :, None] * (bt[:, None] * r)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + hy["eps"]) \
        * w["gdn_o_norm"]
    z = (u @ w["gdn_wz"]).reshape(S, hv, dv)
    return (o * jax.nn.silu(z)).reshape(S, hv * dv) @ w["gdn_wo"]


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def route(probs, forced, hy):
    """(experts [S, top_k], weights [S, top_k]) of one sequence from the
    softmax ``probs [S, E]``: the ``top_k`` largest, or ``forced`` where it
    is not -1; weights their probabilities, divided by their sum over the
    picks (``norm_topk_prob``)."""
    _, top_e = jax.lax.top_k(probs, hy["top_k"])
    top_e = jnp.where(forced[:, :1] >= 0, forced, top_e)
    top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    if hy["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_e, top_p


def routed_ffn(u, w, forced, hy):
    """(gated shared expert + the HELD experts' part of the routed sum, the
    router's probabilities) of one sequence."""
    probs = jax.nn.softmax(u @ w["router"], axis=-1)
    top_e, top_p = route(probs, forced, hy)

    def one_expert(acc, j):
        weight = jnp.sum(
            jnp.where(top_e == hy["first_held"] + j, top_p, 0.0), -1)
        w_gate, w_up, w_down = (
            _f32(jax.lax.dynamic_index_in_dim(w[n], j, 0, False))
            for n in EXPERTS)
        return acc + weight[:, None] * _swiglu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          jnp.arange(w["w_up"].shape[0]))
    shared = jax.nn.sigmoid(u @ w["ws_sgate"]) * _swiglu(
        u, w["ws_gate"], w["ws_up"], w["ws_down"])
    return out + shared, probs


@functools.partial(jax.jit, static_argnames=("hyper", "full"))
def _layer(x, stacked, p, forced, *, hyper, full):
    """Layer ``p`` of a stack ``[periods, ...]`` on hidden states x [B, S,
    H] (float32): (x', the router's probabilities [B, S, E])."""
    hy = dict(hyper)
    w = {n: jax.lax.dynamic_index_in_dim(a, p, 0, keepdims=False)
         for n, a in stacked.items()}
    w = {n: (a if n in EXPERTS else _f32(a)) for n, a in w.items()}
    mixer = attention if full else delta_net

    def one_sequence(args):
        xs, told = args
        xs = xs + mixer(norm(xs, w["input_ln"], hy["eps"]), w, hy)
        out, probs = routed_ffn(norm(xs, w["post_ln"], hy["eps"]), w, told,
                                hy)
        return xs + out, probs

    return jax.lax.map(one_sequence, (x, forced))


def hidden_states(weights, hyper, ids, with_router=False):
    """Final-norm hidden states [B, S, H], float32; with ``with_router``
    also every layer's router probabilities [L, B, S, E]."""
    hyper = dict(hyper)
    static = tuple(sorted(hyper.items()))
    per = hyper["interval"]
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(weights["embed"], ids, axis=0))
        served = weights.get("served_picks")
        served = None if served is None else served(ids)
        own = jnp.full(ids.shape + (hyper["top_k"],), -1, jnp.int32)
        probs = []
        for i in range(per * weights["wo"].shape[0]):
            p, j = divmod(i, per)
            full = j == per - 1
            stacked = {n: weights[n] for n in FULL} if full \
                else weights["linear"][j]
            told = own if served is None \
                else jnp.asarray(served[i], jnp.int32)
            x, pr = _layer(x, stacked, jnp.int32(p), told, hyper=static,
                           full=full)
            if with_router:
                probs.append(pr)
        x = norm(x, _f32(weights["final_norm"]), hyper["eps"])
        return (x, jnp.stack(probs)) if with_router else x


def logits_at(weights, hyper, ids, at, with_router=False):
    """Float32 logits [B, K, V] at the K positions ``at[b]`` of each row;
    with ``with_router`` also the layers' float32 router probabilities at
    those positions, [L, B, K, E]: their ``top_k`` largest are the experts
    the layer used."""
    out = hidden_states(weights, hyper, ids, with_router)
    x, probs = out if with_router else (out, None)
    at = jnp.asarray(at, jnp.int32)
    with jax.default_matmul_precision("highest"):
        picked = jnp.take_along_axis(x, at[..., None], axis=1)
        head = weights["lm_head"]
        logits = jnp.concatenate(
            [picked @ _f32(head[:, v:v + VOCAB_BLOCK])
             for v in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
    if not with_router:
        return logits
    return logits, jnp.take_along_axis(probs, at[None, ..., None], axis=2)
