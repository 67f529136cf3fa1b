"""The plain reference of OLMoE (``model_type`` ``olmoe``): a pre-norm
decoder with QK-norm and a top-k mixture-of-experts SwiGLU FFN, in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
No kernel, no cache, no batching trick, no sorting of tokens by expert, and
nothing imported from ``paddle_tpu``: the program hands over its weights
(``weights_of``) and its sizes (``hyper_of``) and is then judged by this file,
through the same three entry points as ``reference.py``.

Per layer, ``x`` the residual stream, no bias:

- ``h = RMSNorm(x; input_ln)``; ``q, k, v = h Wq, h Wk, h Wv``;
- ``q = RMSNorm(q; q_norm)``, ``k = RMSNorm(k; k_norm)`` over the WHOLE
  projection (all heads at once), before the heads are split;
- heads of ``head_dim``, rotary embedding in the half-split layout on q and
  k, causal softmax attention with scale ``1 / sqrt(head_dim)``,
  ``x = x + attn Wo``;
- ``h = RMSNorm(x; post_ln)``; ``p = softmax(h Wr)``; the ``top_k`` largest
  ``p_e`` of each token, as they are (``norm_topk_prob`` false) or divided
  by their sum (true); ``x = x + sum_e p_e W_down,e (silu(h W_gate,e) * (h
  W_up,e))``. The experts are a loop over all of them with a mask: every
  expert sees every token and a token keeps only its own.

After the last layer ``RMSNorm(x; final_norm)`` and the untied head. One
layer's float32 weights exist at a time (1.6 GB at the published widths).

Departures from the published description: ``clip_qkv`` is null in the
published config and is not implemented; the router's auxiliary losses
belong to training and are not here.
"""
import functools

import jax
import jax.numpy as jnp

BLOCK = 512
STACKED = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "router", "w_gate",
           "w_up", "w_down", "input_ln", "post_ln")


def weights_of(model):
    """The arrays of an ``OlmoeForCausalLM``, by the names used here."""
    w = {n: getattr(model, n).value for n in STACKED}
    w["embed"] = model.embed_tokens.value
    w["final_norm"] = model.final_norm.value
    w["lm_head"] = (model.embed_tokens.value.T if model.lm_head is None
                    else model.lm_head.value)
    return w


def hyper_of(config):
    return {"num_heads": int(config.num_attention_heads),
            "num_kv_heads": int(config.num_key_value_heads),
            "head_dim": int(config.hidden_size
                            // config.num_attention_heads),
            "eps": float(config.rms_norm_eps),
            "theta": float(config.rope_theta),
            "top_k": int(config.num_experts_per_tok),
            "norm_topk_prob": bool(config.norm_topk_prob)}


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, heads, D]; position s rotates pair (d, d + D/2) by
    s * theta^(-2d/D)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal softmax attention of one sequence. q: [S, H, D]; k, v:
    [S, Hkv, D]; query head h reads kv head h // (H / Hkv)."""
    s, h, d = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    blk = min(BLOCK, s)
    pad = (-s) % blk
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    starts = jnp.arange(0, s + pad, blk)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, blk, 0)
        logits = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(float(d))
        qpos = start + jnp.arange(blk)[:, None]
        mask = jnp.arange(s)[None, :] <= qpos
        probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(one_block, starts).reshape(s + pad, h, d)
    return out[:s]


def _experts(hn, probs, w, top_k, norm_topk_prob):
    """The mixture for one sequence. hn: [S, H]; probs: [S, E]."""
    num_experts = probs.shape[-1]
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)

    def one_expert(acc, e):
        # this token's weight for expert e: its probability if e is among
        # the token's top_k, else 0
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0.0), -1)
        wg = _f32(jax.lax.dynamic_index_in_dim(w["w_gate"], e, 0, False))
        wu = _f32(jax.lax.dynamic_index_in_dim(w["w_up"], e, 0, False))
        wd = _f32(jax.lax.dynamic_index_in_dim(w["w_down"], e, 0, False))
        y = (jax.nn.silu(hn @ wg) * (hn @ wu)) @ wd
        return acc + weight[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(hn),
                          jnp.arange(num_experts))
    return out


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "head_dim", "eps", "theta", "top_k",
    "norm_topk_prob"))
def _layer(x, stacked, i, *, num_heads, num_kv_heads, head_dim, eps, theta,
           top_k, norm_topk_prob):
    """Layer i on hidden states x [B, S, H] (float32): (x', router
    probabilities [B, S, E])."""
    big = ("w_gate", "w_up", "w_down")     # stay in their dtype: one
    w = {n: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
         for n, a in stacked.items()}      # expert is upcast at a time
    w = {n: (a if n in big else _f32(a)) for n, a in w.items()}
    b, s, _ = x.shape

    def one_sequence(xs):
        hn = _rms(xs, w["input_ln"], eps)
        q = _rms(hn @ w["wq"], w["q_norm"], eps)
        k = _rms(hn @ w["wk"], w["k_norm"], eps)
        q = q.reshape(s, num_heads, head_dim)
        k = k.reshape(s, num_kv_heads, head_dim)
        v = (hn @ w["wv"]).reshape(s, num_kv_heads, head_dim)
        a = _attention(_rope(q, theta), _rope(k, theta), v)
        xs = xs + a.reshape(s, num_heads * head_dim) @ w["wo"]
        hn = _rms(xs, w["post_ln"], eps)
        probs = jax.nn.softmax(hn @ w["router"], -1)
        return xs + _experts(hn, probs, w, top_k, norm_topk_prob), probs

    return jax.lax.map(one_sequence, x)


def hidden_states(weights, hyper, ids, with_router=False):
    """Final-norm hidden states [B, S, H], float32; with ``with_router``
    also the router's probabilities [L, B, S, E]."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(weights["embed"], ids, axis=0))
        stacked = {n: weights[n] for n in STACKED}
        probs = []
        for i in range(weights["wq"].shape[0]):
            x, p = _layer(x, stacked, jnp.int32(i), **hyper)
            if with_router:
                probs.append(p)
        x = _rms(x, _f32(weights["final_norm"]), hyper["eps"])
        return (x, jnp.stack(probs)) if with_router else x


def logits_at(weights, hyper, ids, at, with_router=False):
    """Float32 logits [B, K, V] at the K positions ``at[b]`` of each row;
    with ``with_router`` also the router's float32 probabilities at those
    positions, [L, B, K, E]."""
    out = hidden_states(weights, hyper, ids, with_router)
    x, probs = out if with_router else (out, None)
    at = jnp.asarray(at, jnp.int32)
    with jax.default_matmul_precision("highest"):
        picked = jnp.take_along_axis(x, at[..., None], axis=1)
        logits = picked @ _f32(weights["lm_head"])
    if not with_router:
        return logits
    return logits, jnp.take_along_axis(probs, at[None, ..., None], axis=2)
