"""The plain reference of Olmo Hybrid (``model_type`` ``olmo_hybrid``;
allenai/Olmo-Hybrid-7B): periods of Gated DeltaNet layers (arXiv:2412.06464)
and then one full-attention layer, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. The delta rule is the recurrence
itself, run token by token in a ``lax.scan``: no chunked form, no kernel, no
cache, no batching, and nothing imported from ``paddle_tpu``: the program hands
over its weights (``weights_of``) and its sizes (``hyper_of``) and is then
judged by this file, through the same three entry points as ``reference.py``.

Both kinds of layer normalise each sub-layer's OUTPUT, ``x`` the residual
stream, no bias:

    x = x + RMSNorm(Mixer(x); attn_out_ln)
    x = x + RMSNorm(W_down(silu(x W_gate) * (x W_up)); ffn_out_ln)

Full-attention mixer: ``q, k, v = x W_q, x W_k, x W_v``; ``q = RMSNorm(q;
q_norm)``, ``k = RMSNorm(k; k_norm)`` over the WHOLE projection; heads of
``head_dim``; no rotary embedding; ``score = q . k * head_dim^-0.5``, causal
softmax; ``concat_heads(softmax . v) W_o``.

Gated DeltaNet mixer, a token ``t``, a head:

- ``u_t = [x_t W_q | x_t W_k | x_t W_v]``; a channel ``c``: ``u'_t[c] =
  silu(sum_j w[j, c] * u_{t-(width-1)+j}[c])``, rows before the sequence's
  start zero, no bias; split into ``q_t, k_t`` (``dk`` a head), ``v_t``
  (``dv``);
- ``q_t = q_t / sqrt(|q_t|^2 + 1e-6) * dk^-0.5``; ``k_t = k_t / sqrt(|k_t|^2
  + 1e-6)``;
- ``beta_t = sigmoid(x_t W_b)``, times 2 with ``neg_eigval``; ``g_t =
  -exp(A_log) * softplus(x_t W_a + dt_bias)``;
- ``S`` (``dk x dv``, zero at the start): ``S = exp(g_t) S``; ``r = v_t - S^T
  k_t``; ``S = S + k_t (beta_t r)^T``; ``o_t = S^T q_t``;
- ``y_t = RMSNorm(o_t; o_norm) * silu(x_t W_z)`` a head; ``concat_heads(y_t)
  W_o``.

After the last layer ``RMSNorm(x; final_norm)`` and the untied head. One
layer's float32 weights exist at a time (0.86 GB at the published widths) and
one sequence at a time; the head is applied to the judged positions only,
in column blocks.

Departures from the published description, each an assumption the
configuration file lists (``assumed``): the norm on sub-layer outputs and the
QK-norm over the whole projection are the family's (Olmo 2 / Olmo 3);
``rope_parameters.rope_theta`` null is read as written; the linear layer is
``fla.layers.GatedDeltaNet`` with its defaults. The program stores ``W_q | W_k
| W_v`` as one matrix and ``W_a | W_b`` as one: ``weights_of`` hands them over
as they lie and this file cuts them where the equations above do.
"""
import functools

import jax
import jax.numpy as jnp

BLOCK = 512
HEAD_BLOCK = 16384
FULL = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "w_gate", "w_up",
        "w_down", "attn_out_ln", "ffn_out_ln")
LINEAR = ("gdn_wqkv", "gdn_wz", "gdn_wab", "gdn_conv", "gdn_A_log",
          "gdn_dt_bias", "gdn_o_norm", "gdn_wo", "w_gate", "w_up", "w_down",
          "attn_out_ln", "ffn_out_ln")


def weights_of(model):
    """The arrays of an ``OlmoHybridForCausalLM``, by the names used here:
    the full layers' ``[periods, ...]``, the linear layers' under ``linear``,
    one tree ``[periods, ...]`` for each place in the period."""
    w = {n: getattr(model, n).value for n in FULL}
    w["linear"] = tuple(
        {n: getattr(model, f"linear{j}_{n}").value for n in LINEAR}
        for j in range(model.config.linear_per_period))
    w["embed"] = model.embed_tokens.value
    w["final_norm"] = model.final_norm.value
    w["lm_head"] = (model.embed_tokens.value.T if model.lm_head is None
                    else model.lm_head.value)
    return w


def hyper_of(config):
    return {"num_heads": int(config.num_attention_heads),
            "head_dim": int(config.hidden_size
                            // config.num_attention_heads),
            "eps": float(config.rms_norm_eps),
            "lin_heads": int(config.linear_num_value_heads),
            "dk": int(config.linear_key_head_dim),
            "dv": int(config.linear_value_head_dim),
            "neg_eigval": bool(config.linear_allow_neg_eigval)}


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _attention(q, k, v):
    """Causal softmax attention of one sequence, q, k, v ``[S, H, D]``."""
    s, h, d = q.shape
    blk = min(BLOCK, s)
    pad = (-s) % blk
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, blk, 0)
        logits = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(float(d))
        mask = jnp.arange(s)[None, :] <= start + jnp.arange(blk)[:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, s + pad, blk))
    return out.reshape(s + pad, h, d)[:s]


def conv_silu(u, w):
    """The depthwise causal convolution and its SiLU over one sequence. u
    ``[S, C]``; w ``[width, C]``, its last row the current token's."""
    width = w.shape[0]
    ext = jnp.pad(u, ((width - 1, 0), (0, 0)))
    acc = sum(w[j] * ext[j:j + u.shape[0]] for j in range(width))
    return jax.nn.silu(acc)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule of one sequence, token by token from a zero
    state. q, k ``[S, H, dk]`` (normalised), v ``[S, H, dv]``, g, beta ``[S,
    H]``. Returns ``o [S, H, dv]``."""
    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, None, None]
        r = vt - jnp.einsum("hkv,hk->hv", s, kt)
        s = s + kt[:, :, None] * (bt[:, None] * r)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    s0 = jnp.zeros(q.shape[1:] + v.shape[-1:], jnp.float32)
    return jax.lax.scan(token, s0, (q, k, v, g, beta))[1]


def _unit(x, scale=1.0):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6) * scale


def _mlp(x, w, eps):
    m = (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
    return x + _rms(m, w["ffn_out_ln"], eps)


def _pick(stacked, index):
    return {n: _f32(a[index]) for n, a in stacked.items()}


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "head_dim", "eps", "lin_heads", "dk", "dv", "neg_eigval"))
def _linear_layer(x, stacked, index, *, num_heads, head_dim, eps, lin_heads,
                  dk, dv, neg_eigval):
    """Linear layer ``index`` (its period) of one place on x ``[B, S, H]``."""
    w = _pick(stacked, index)
    s = x.shape[1]
    nk = lin_heads * dk

    def one_sequence(xs):
        u = conv_silu(xs @ w["gdn_wqkv"], w["gdn_conv"])
        q = _unit(u[:, :nk].reshape(s, lin_heads, dk), dk ** -0.5)
        k = _unit(u[:, nk:2 * nk].reshape(s, lin_heads, dk))
        v = u[:, 2 * nk:].reshape(s, lin_heads, dv)
        ab = xs @ w["gdn_wab"]
        g = -jnp.exp(w["gdn_A_log"]) * jax.nn.softplus(
            ab[:, :lin_heads] + w["gdn_dt_bias"])
        beta = jax.nn.sigmoid(ab[:, lin_heads:]) * (2.0 if neg_eigval
                                                    else 1.0)
        o = delta_rule(q, k, v, g, beta)
        z = (xs @ w["gdn_wz"]).reshape(s, lin_heads, dv)
        y = (_rms(o, w["gdn_o_norm"], eps) * jax.nn.silu(z)).reshape(s, -1)
        xs = xs + _rms(y @ w["gdn_wo"], w["attn_out_ln"], eps)
        return _mlp(xs, w, eps)

    return jax.lax.map(one_sequence, x)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "head_dim", "eps", "lin_heads", "dk", "dv", "neg_eigval"))
def _full_layer(x, stacked, index, *, num_heads, head_dim, eps, lin_heads,
                dk, dv, neg_eigval):
    """Full-attention layer ``index`` (its period) on x ``[B, S, H]``."""
    w = _pick(stacked, index)
    s = x.shape[1]

    def one_sequence(xs):
        q = _rms(xs @ w["wq"], w["q_norm"], eps).reshape(s, num_heads,
                                                         head_dim)
        k = _rms(xs @ w["wk"], w["k_norm"], eps).reshape(s, num_heads,
                                                         head_dim)
        v = (xs @ w["wv"]).reshape(s, num_heads, head_dim)
        a = _attention(q, k, v).reshape(s, num_heads * head_dim)
        xs = xs + _rms(a @ w["wo"], w["attn_out_ln"], eps)
        return _mlp(xs, w, eps)

    return jax.lax.map(one_sequence, x)


def hidden_states(weights, hyper, ids):
    """Final-norm hidden states ``[B, S, H]``, float32."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(weights["embed"], ids, axis=0))
        full = {n: weights[n] for n in FULL}
        for p in range(full["attn_out_ln"].shape[0]):
            for place in weights["linear"]:
                x = _linear_layer(x, place, jnp.int32(p), **hyper)
            x = _full_layer(x, full, jnp.int32(p), **hyper)
        return _rms(x, _f32(weights["final_norm"]), hyper["eps"])


def logits_at(weights, hyper, ids, at):
    """Float32 logits ``[B, K, V]`` at the K positions ``at[b]`` of each
    row."""
    x = hidden_states(weights, hyper, ids)
    at = jnp.asarray(at, jnp.int32)
    head = weights["lm_head"]
    with jax.default_matmul_precision("highest"):
        picked = jnp.take_along_axis(x, at[..., None], axis=1)
        # the head in column blocks: its float32 copy never exists whole
        return jnp.concatenate(
            [picked @ _f32(head[:, lo:lo + HEAD_BLOCK])
             for lo in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)
