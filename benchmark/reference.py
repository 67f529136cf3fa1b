"""The plain reference: a pre-norm decoder (RMSNorm, rotary embedding in the
half-split "neox" layout, grouped-query causal attention, SwiGLU, untied
head) and its next-token cross-entropy, as the Mistral / Llama papers give
them, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
batching trick, and nothing imported from ``paddle_tpu``: the program hands
over its weights (``weights_of``) and its sizes (``hyper_of``) and is then
judged by this file.

One layer's float32 weights exist at a time (0.9 GB at 7B widths), so the
reference fits beside the system it judges. Attention and the loss head run
in blocks of ``BLOCK`` positions to bound the [heads, q, k] and [q, vocab]
float32 intermediates; blocking changes no value.

Departures from the published description: none. ``sliding_window`` is null
in Mistral-7B-v0.3, so attention is fully causal.
"""
import functools

import jax
import jax.numpy as jnp

BLOCK = 512
STACKED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
           "input_ln", "post_ln")


def weights_of(model):
    """The arrays of a ``LlamaForCausalLM``, by the names used here."""
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
            "theta": float(config.rope_theta)}


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


@functools.partial(jax.jit, static_argnames=("num_heads", "num_kv_heads",
                                             "head_dim", "eps", "theta"))
def _layer(x, stacked, i, *, num_heads, num_kv_heads, head_dim, eps, theta):
    """Layer i on hidden states x [B, S, H] (float32)."""
    w = {n: _f32(jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False))
         for n, a in stacked.items()}
    b, s, _ = x.shape

    def one_sequence(xs):
        hn = _rms(xs, w["input_ln"], eps)
        q = (hn @ w["wq"]).reshape(s, num_heads, head_dim)
        k = (hn @ w["wk"]).reshape(s, num_kv_heads, head_dim)
        v = (hn @ w["wv"]).reshape(s, num_kv_heads, head_dim)
        a = _attention(_rope(q, theta), _rope(k, theta), v)
        xs = xs + a.reshape(s, num_heads * head_dim) @ w["wo"]
        hn = _rms(xs, w["post_ln"], eps)
        return xs + (jax.nn.silu(hn @ w["w_gate"]) * (hn @ w["w_up"])) \
            @ w["w_down"]

    return jax.lax.map(one_sequence, x)


def hidden_states(weights, hyper, ids):
    """Final-norm hidden states [B, S, H], float32."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(weights["embed"], ids, axis=0))
        stacked = {n: weights[n] for n in STACKED}
        for i in range(weights["wq"].shape[0]):
            x = _layer(x, stacked, jnp.int32(i), **hyper)
        return _rms(x, _f32(weights["final_norm"]), hyper["eps"])


def logits_at(weights, hyper, ids, at):
    """Float32 logits [B, K, V] at the K positions ``at[b]`` of each row."""
    x = hidden_states(weights, hyper, ids)
    with jax.default_matmul_precision("highest"):
        picked = jnp.take_along_axis(
            x, jnp.asarray(at, jnp.int32)[..., None], axis=1)
        return picked @ _f32(weights["lm_head"])


def token_nll(weights, hyper, ids):
    """Next-token cross-entropy of every position but the last of each
    row, [B, S - 1] float32: entry [b, s] is the loss of predicting
    ``ids[b, s + 1]`` from ``ids[b, :s + 1]``."""
    x = hidden_states(weights, hyper, ids)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        head = _f32(weights["lm_head"])
        b, s, h = x.shape
        xs, tgt = x[:, :-1].reshape(-1, h), ids[:, 1:].reshape(-1)
        n = xs.shape[0]
        pad = (-n) % BLOCK
        xs = jnp.pad(xs, ((0, pad), (0, 0))).reshape(-1, BLOCK, h)
        tg = jnp.pad(tgt, (0, pad)).reshape(-1, BLOCK)

        def nll(args):
            xb, tb = args
            lg = xb @ head
            return jax.scipy.special.logsumexp(lg, -1) \
                - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0]

        return jax.lax.map(nll, (xs, tg)).reshape(-1)[:n].reshape(b, s - 1)


def loss(weights, hyper, ids):
    """Mean next-token cross-entropy over every position but the last of
    each row: labels are the inputs shifted by one."""
    return jnp.mean(token_nll(weights, hyper, ids))
