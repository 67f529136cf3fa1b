"""The plain reference of Phi-4-mini-flash-reasoning (``model_type``
``phi4flash``): the SambaY decoder-hybrid-decoder (arXiv:2507.06607) with
differential attention (arXiv:2410.05258), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. The selective scan is the
recurrence itself, token by token in a ``lax.scan``; attention is a masked
softmax over the whole sequence; no kernel, no cache, no window store, no
batching, and nothing imported from ``paddle_tpu``: the program hands over its
weights (``weights_of``) and its sizes (``hyper_of``) and is then judged by
this file, through the same three entry points as ``reference.py``.

``L`` layers, ``x`` the residual stream, every layer ``l``:

    x = x + Mixer_l(LN(x; ln1));  x = x + W_2 (silu(g) * u),
    [g | u] = LN(x; ln2) W_1

LN is LayerNorm with weight and bias, eps ``layer_norm_eps``. After the last
layer ``LN(x; final)`` and logits ``x E^T`` with the embedding ``E``. No
rotary embedding. Mixers: ``l`` even, ``l <= L/2``: Mamba; ``l`` odd, ``l <
L/2``: differential attention inside a window; ``l = L/2 + 1``: differential
attention, full, whose ``k``, ``v`` are the shared cache; ``l`` even, ``l >=
L/2 + 2``: GMU; ``l`` odd, ``l >= L/2 + 3``: differential cross-attention over
layer ``L/2 + 1``'s ``k``, ``v``.

- Mamba, a token ``t``, input ``h_t``: ``[a_t | z_t] = h_t W_in``; ``c_t =
  silu(b_conv + sum_{j=0..3} w_conv[j] * a_{t-3+j})`` (zeros before the
  sequence); ``[r_t | B_t | C_t] = c_t W_x``; ``delta_t = softplus(r_t W_dt +
  b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(delta_t A) * S_{t-1} + B_t (delta_t
  c_t)``, ``S_{-1} = 0``; ``y_t = C_t . S_t + D * c_t``; output ``(y_t *
  silu(z_t)) W_out``. Layer ``L/2`` also hands on ``m_t = y_t`` (before the
  gate).
- Differential attention: ``[q | k | v] = h W_qkv + b``; differential head
  ``n`` has ``q1 = q[2n]``, ``q2 = q[2n+1]``, its KV pair ``p = n // (query
  pairs / KV pairs)`` has ``k1 = k[2p]``, ``k2 = k[2p+1]``, ``V = [v[2p] |
  v[2p+1]]``; ``P_i = softmax(q_i k_i^T * head_dim^-0.5)`` over keys ``j <=
  t`` (and ``j > t - window`` in a window layer); ``lambda = exp(lq1 . lk1) -
  exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``;
  ``o_n = (1 - lambda_init) RMSNorm((P1 - lambda P2) V; subln)`` (eps as the
  LayerNorms'); output ``concat_n(o_n) W_o + b_o``.
- Cross-attention: ``q = h W_q + b``; ``k``, ``v`` layer ``L/2 + 1``'s.
- GMU: ``(m_t * silu(h_t W_1)) W_2``.

Departures from the published code, which could not be read here (no
network): every equation above is the issue's statement of the architecture
(ISSUE 37, Tentpole 1), itself the two papers'; the configuration file lists
what was ASSUMED where the published config is silent (``assumed``): the
Mamba sizes, which layers carry a bias, LayerNorm, the pairing of heads, the
order of ``W_1``'s halves. What the program stores differently, and this
file reads as it lies: ``W_1`` as two matrices ``w_gate | w_up``; ``A_log`` as
``[d_state, d_inner]``; the convolution's weight as ``[width, d_inner]``, its
last row the current token's; the four ``lambda`` vectors as one ``[4,
head_dim]`` (``lq1, lk1, lq2, lk2``).

One layer's float32 weights exist at a time (0.48 GB at the published widths)
and one sequence at a time; the head is applied to the judged positions only,
in blocks of the vocabulary (the tied embedding is 2.05 GB in float32).
"""
import functools
import math

import jax
import jax.numpy as jnp

HEAD_BLOCK = 16384


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def weights_of(model):
    """The program's own parameter tree, as it lies (bf16 on the device)."""
    return model.decode_params()[0]


def hyper_of(config):
    return {"num_heads": config.num_attention_heads,
            "num_kv_heads": config.num_key_value_heads,
            "head_dim": config.hidden_size // config.num_attention_heads,
            "window": config.sliding_window,
            "eps": float(config.layer_norm_eps)}


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _ln(x, w, b, eps):
    xc = x - jnp.mean(x, -1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps) \
        * w + b


def _mlp(x, w, eps):
    h = _ln(x, w["ln2_w"], w["ln2_b"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def mamba(h, w):
    """One sequence ``h [S, H]`` (already normalised). Returns ``(out [S,
    H], y [S, C])``: ``y`` before the gate."""
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
    delta = jax.nn.softplus(xdb[:, :rank] @ w["ssm_dt"] + w["ssm_dt_b"])
    bm, cm = xdb[:, rank:rank + n], xdb[:, rank + n:]
    a_mat = -jnp.exp(w["ssm_A_log"])                    # [N, C]

    def token(state, x):
        d, ct, bt, cmt = x
        state = jnp.exp(d[None, :] * a_mat) * state \
            + bt[:, None] * (d * ct)[None, :]
        return state, cmt @ state

    _, y = jax.lax.scan(token, jnp.zeros_like(a_mat), (delta, c, bm, cm))
    y = y + w["ssm_D"] * c
    return (y * jax.nn.silu(z)) @ w["ssm_out"], y


def diff_attention(q, k, v, w, mask, *, layer, eps):
    """q ``[S, nh, hd]``, k, v ``[S, nkv, hd]``, mask ``[S, S]`` (query,
    key). Returns ``[S, nh * hd]`` before ``W_o``."""
    s, nh, hd = q.shape
    nkv = k.shape[1]
    per = (nh // 2) // (nkv // 2)
    q = q.reshape(s, nh // 2, 2, hd)
    k = jnp.repeat(k.reshape(s, nkv // 2, 2, hd), per, axis=1)
    vv = jnp.repeat(v.reshape(s, nkv // 2, 2 * hd), per, axis=1)

    def probs(i):
        logits = jnp.einsum("qnd,knd->nqk", q[:, :, i], k[:, :, i]) \
            / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), -1)

    init = lambda_init(layer)
    lam = jnp.exp(jnp.sum(w["lam"][0] * w["lam"][1])) \
        - jnp.exp(jnp.sum(w["lam"][2] * w["lam"][3])) + init
    a = jnp.einsum("nqk,knd->qnd", probs(0) - lam * probs(1), vv)
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps) \
        * w["subln"] * (1.0 - init)
    return a.reshape(s, nh * hd)


def _pick(tree, index):
    return {n: _f32(a if index is None else a[index])
            for n, a in tree.items()}


_STATIC = ("num_heads", "num_kv_heads", "head_dim", "window", "eps", "layer")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _mamba_layer(x, tree, index, *, eps, **_):
    w = _pick(tree, index)

    def one_sequence(xs):
        out, y = mamba(_ln(xs, w["ln1_w"], w["ln1_b"], eps), w)
        return _mlp(xs + out, w, eps), y

    return jax.lax.map(one_sequence, x)


@functools.partial(jax.jit, static_argnames=_STATIC + ("windowed",))
def _attn_layer(x, tree, index, *, num_heads, num_kv_heads, head_dim, window,
                eps, layer, windowed):
    """A self layer: returns ``(x, k, v)``."""
    w = _pick(tree, index)
    s = x.shape[1]
    nq, nkv = num_heads * head_dim, num_kv_heads * head_dim
    rows = jnp.arange(s)
    mask = rows[None, :] <= rows[:, None]
    if windowed:
        mask = mask & (rows[None, :] > rows[:, None] - window)

    def one_sequence(xs):
        qkv = _ln(xs, w["ln1_w"], w["ln1_b"], eps) @ w["wqkv"] + w["bqkv"]
        q = qkv[:, :nq].reshape(s, num_heads, head_dim)
        k = qkv[:, nq:nq + nkv].reshape(s, num_kv_heads, head_dim)
        v = qkv[:, nq + nkv:].reshape(s, num_kv_heads, head_dim)
        a = diff_attention(q, k, v, w, mask, layer=layer, eps=eps)
        return _mlp(xs + a @ w["wo"] + w["bo"], w, eps), k, v

    return jax.lax.map(one_sequence, x)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _gmu_layer(x, m, tree, index, *, eps, **_):
    w = _pick(tree, index)
    h = _ln(x, w["ln1_w"], w["ln1_b"], eps)
    return _mlp(x + (m * jax.nn.silu(h @ w["gmu_in"])) @ w["gmu_out"], w, eps)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _cross_layer(x, k, v, tree, index, *, num_heads, num_kv_heads, head_dim,
                 window, eps, layer):
    w = _pick(tree, index)
    s = x.shape[1]
    rows = jnp.arange(s)
    mask = rows[None, :] <= rows[:, None]

    def one_sequence(args):
        xs, ks, vs = args
        q = (_ln(xs, w["ln1_w"], w["ln1_b"], eps) @ w["wq"] + w["bq"]
             ).reshape(s, num_heads, head_dim)
        a = diff_attention(q, ks, vs, w, mask, layer=layer, eps=eps)
        return _mlp(xs + a @ w["wo"] + w["bo"], w, eps)

    return jax.lax.map(one_sequence, (x, k, v))


def hidden_states(weights, hyper, ids):
    """Final-norm hidden states ``[B, S, H]``, float32."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(weights["embed"], ids, axis=0))
        mamba_self, attn_self = weights["self_layers"]
        pairs = attn_self["subln"].shape[0]
        for i in range(pairs):
            x, _ = _mamba_layer(x, mamba_self, jnp.int32(i), **hyper,
                                layer=2 * i)
            x, _, _ = _attn_layer(x, attn_self, jnp.int32(i), **hyper,
                                  layer=2 * i + 1, windowed=True)
        mamba_mid, attn_mid = weights["mid_layers"]
        x, m = _mamba_layer(x, mamba_mid, None, **hyper, layer=2 * pairs)
        x, k, v = _attn_layer(x, attn_mid, None, **hyper,
                              layer=2 * pairs + 1, windowed=False)
        gmus, crosses = weights["cross_layers"]
        for i in range(crosses["subln"].shape[0]):
            x = _gmu_layer(x, m, gmus, jnp.int32(i), **hyper,
                           layer=2 * pairs + 2 + 2 * i)
            x = _cross_layer(x, k, v, crosses, jnp.int32(i), **hyper,
                             layer=2 * pairs + 3 + 2 * i)
        return _ln(x, _f32(weights["final_norm"]),
                   _f32(weights["final_norm_b"]), hyper["eps"])


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
