"""The plain reference of Jamba (``model_type`` ``jamba``; ai21labs/
AI21-Jamba2-3B): Mamba-1 layers with one grouped-query attention layer a
period (arXiv:2403.19887), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. The selective scan is the
recurrence itself, token by token in a ``lax.scan``; attention is a masked
softmax over the whole sequence (a block of queries at a time, each against
every key: 20 heads x 12k x 12k float32 scores would not fit beside the
served model); no kernel, no cache, no store, no batching, and nothing
imported from ``paddle_tpu``: the program hands over its weights
(``weights_of``) and its sizes (``hyper_of``) and is then judged by this
file, through the same three entry points as ``reference.py``.

``L`` layers, ``x`` the residual stream, RMSNorm with a weight, eps
``rms_norm_eps``, every layer ``l``:

    x = x + Mixer_l(RMSNorm(x; ln1));  x = x + W_down (silu(x' W_gate) * (x' W_up)),
    x' = RMSNorm(x; ln2)

After the last layer ``RMSNorm(x; final)`` and logits ``x E^T`` with the
embedding ``E``. No rotary or other positional term. ``Mixer_l`` is attention
where ``l % period == offset``, else Mamba; every FFN is the dense MLP.

- Mamba, a token ``t``, input ``h_t``: ``[a_t | z_t] = h_t W_in``; ``c_t =
  silu(b_conv + sum_{j=0..3} w_conv[j] * a_{t-3+j})`` (zeros before the
  sequence); ``[r_t | B_t | C_t] = c_t W_x``; ``r_t = RMSNorm(r_t; dt_ln)``,
  ``B_t = RMSNorm(B_t; b_ln)``, ``C_t = RMSNorm(C_t; c_ln)`` (eps as the
  others'); ``delta_t = softplus(r_t W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t
  = exp(delta_t A) * S_{t-1} + B_t (delta_t c_t)``, ``S_{-1} = 0``; ``y_t = C_t
  . S_t + D * c_t``; output ``(y_t * silu(z_t)) W_out``.
- Attention: ``q = h W_q`` (``num_heads`` heads of ``head_dim``), ``k = h
  W_k``, ``v = h W_v`` (``num_kv_heads`` heads; query head ``n`` reads KV head
  ``n // (num_heads / num_kv_heads)``); ``softmax(q k^T * head_dim^-0.5)`` over
  keys ``j <= t``; output ``concat_n(o_n) W_o``. No bias.

Departures from the published description: none known. The published code
could not be read here (no network): every equation above is the issue's
statement of the architecture (ISSUE 50, Tentpole 1), the Jamba family's; the
configuration file lists what was ASSUMED where the catalog's row is silent
(``assumed``): ``head_dim``, the layer order from period and offset, no
positional term, no attention bias, the three inner norms. What the program
stores differently, and this file reads as it lies: the layers by their PLACE
in the period, ``[periods, ...]`` each (``mamba_layers = (places before the
attention layer, places after it)``, ``attn_layers``); ``A_log`` as ``[d_state,
d_inner]``; the convolution's weight as ``[width, d_inner]``, its last row the
current token's.

One layer's float32 weights exist at a time (0.42 GB at the published widths)
and one sequence at a time; the head is applied to the judged positions only,
in blocks of the vocabulary.
"""
import functools
import math

import jax
import jax.numpy as jnp

HEAD_BLOCK = 16384
#: queries one block of the attention scores holds
QUERY_BLOCK = 512


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def weights_of(model):
    """The program's own parameter tree, as it lies (bf16 on the device)."""
    return model.decode_params()[0]


def hyper_of(config):
    return {"num_heads": config.num_attention_heads,
            "num_kv_heads": config.num_key_value_heads,
            "head_dim": config.head_dim,
            "eps": float(config.rms_norm_eps)}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mlp(x, w, eps):
    h = _rms(x, w["ln2"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def mamba(h, w, eps):
    """One sequence ``h [S, H]`` (already normalised). Returns ``out [S,
    H]``."""
    s = h.shape[0]
    c_dim, n = w["ssm_out"].shape[0], w["ssm_A_log"].shape[0]
    rank = w["ssm_dt"].shape[0]
    az = h @ w["ssm_in"]
    a, z = az[:, :c_dim], az[:, c_dim:]
    width = w["ssm_conv"].shape[0]
    ext = jnp.pad(a, ((width - 1, 0), (0, 0)))
    c = jax.nn.silu(w["ssm_conv_b"] + sum(
        w["ssm_conv"][j] * ext[j:j + s] for j in range(width)))
    xdb = c @ w["ssm_x"]
    r = _rms(xdb[:, :rank], w["ssm_dt_ln"], eps)
    bm = _rms(xdb[:, rank:rank + n], w["ssm_b_ln"], eps)
    cm = _rms(xdb[:, rank + n:], w["ssm_c_ln"], eps)
    delta = jax.nn.softplus(r @ w["ssm_dt"] + w["ssm_dt_b"])
    a_mat = -jnp.exp(w["ssm_A_log"])                    # [N, C]

    def token(state, x):
        d, ct, bt, cmt = x
        state = jnp.exp(d[None, :] * a_mat) * state \
            + bt[:, None] * (d * ct)[None, :]
        return state, cmt @ state

    _, y = jax.lax.scan(token, jnp.zeros_like(a_mat), (delta, c, bm, cm))
    y = y + w["ssm_D"] * c
    return (y * jax.nn.silu(z)) @ w["ssm_out"]


def attention(q, k, v):
    """q ``[S, nh, hd]``, k, v ``[S, nkv, hd]``, causal. Returns ``[S, nh *
    hd]`` before ``W_o``."""
    s, nh, hd = q.shape
    per = nh // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    keys = jnp.arange(s)

    def rows(args):
        qb, at = args                                   # [block, nh, hd]
        logits = jnp.einsum("qnd,knd->nqk", qb, k) / math.sqrt(hd)
        mask = keys[None, :] <= at[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), -1)
        return jnp.einsum("nqk,knd->qnd", p, v)

    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, nh, hd)
    at = jnp.minimum(jnp.arange(s + pad), s - 1).reshape(-1, block)
    out = jax.lax.map(rows, (qp, at))
    return out.reshape(s + pad, nh * hd)[:s]


def _pick(tree, index):
    return {n: _f32(a[index]) for n, a in tree.items()}


_STATIC = ("num_heads", "num_kv_heads", "head_dim", "eps")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _mamba_layer(x, tree, index, *, eps, **_):
    w = _pick(tree, index)

    def one_sequence(xs):
        return _mlp(xs + mamba(_rms(xs, w["ln1"], eps), w, eps), w, eps)

    return jax.lax.map(one_sequence, x)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _attn_layer(x, tree, index, *, num_heads, num_kv_heads, head_dim, eps):
    w = _pick(tree, index)
    s = x.shape[1]

    def one_sequence(xs):
        h = _rms(xs, w["ln1"], eps)
        a = attention((h @ w["wq"]).reshape(s, num_heads, head_dim),
                      (h @ w["wk"]).reshape(s, num_kv_heads, head_dim),
                      (h @ w["wv"]).reshape(s, num_kv_heads, head_dim))
        return _mlp(xs + a @ w["wo"], w, eps)

    return jax.lax.map(one_sequence, x)


def hidden_states(weights, hyper, ids):
    """Final-norm hidden states ``[B, S, H]``, float32."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(weights["embed"], ids, axis=0))
        before, after = weights["mamba_layers"]
        attn = weights["attn_layers"]
        for p in range(attn["wo"].shape[0]):
            index = jnp.int32(p)
            for tree in before:
                x = _mamba_layer(x, tree, index, **hyper)
            x = _attn_layer(x, attn, index, **hyper)
            for tree in after:
                x = _mamba_layer(x, tree, index, **hyper)
        return _rms(x, _f32(weights["final_norm"]), hyper["eps"])


def logits_at(weights, hyper, ids, at):
    """Float32 logits ``[B, K, V]`` at the K positions ``at[b]`` of each
    row."""
    x = hidden_states(weights, hyper, ids)
    at = jnp.asarray(at, jnp.int32)
    embed = weights["embed"]
    with jax.default_matmul_precision("highest"):
        picked = jnp.take_along_axis(x, at[..., None], axis=1)
        # the head in blocks of the vocabulary: the embedding's float32
        # copy never exists whole
        return jnp.concatenate(
            [picked @ _f32(embed[lo:lo + HEAD_BLOCK]).T
             for lo in range(0, embed.shape[0], HEAD_BLOCK)], axis=-1)
