"""The plain reference of Nemotron-H (``model_type`` ``nemotron_h``;
NVIDIA-Nemotron-3-Nano-30B-A3B): a pre-norm decoder whose blocks are ONE mixer
each, by the letter of ``hybrid_override_pattern``; in ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``. No kernel, no
cache, no batching, no chunked (dual) form of the recurrence, no sorting of
tokens by expert, and nothing imported from ``paddle_tpu``: the program hands
over its weights (``weights_of``) and its sizes (``hyper_of``) and is then
judged by this file, through the same three entry points as ``reference.py``.

Block ``l``, ``x`` the residual stream: ``x = x + Mixer_l(RMSNorm(x; norm_l))``,
eps ``layer_norm_epsilon``. After the last block ``RMSNorm(x; norm_f)`` and the
untied head.

- ``M``, Mamba-2 (arXiv:2405.21060), input ``u_t``: ``[z_t | xBC_t | dt_t] =
  u_t W_in`` (``H P | H P + 2 G N | H``); ``xBC'_t = silu(b_conv + sum_{j=0..3}
  w_conv[j] * xBC_{t-3+j})`` a channel, zeros before position 0; ``[x_t | B_t |
  C_t] = xBC'_t`` (``H`` heads of ``P``; ``G`` groups of ``N``), head ``h``
  using group ``h // (H / G)``; ``Delta_t = softplus(dt_t + dt_bias)`` (no
  clamp: the published default limit is ``(0, inf)``); ``A = -exp(A_log)`` a
  scalar a head; the state ``S^h`` in ``R^{P x N}``, zero before position 0:
  ``S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t (x) B_t``, ``y_t = S_t C_t + D
  x_t``, TOKEN BY TOKEN; then the gate BEFORE the norm, the norm by group:
  ``y_t = RMSNorm_grouped(y_t * silu(z_t); w_norm)`` over ``G`` groups of ``H
  P / G`` channels; output ``y_t W_out``.
- ``*``, attention: ``q, k, v = u W_q, u W_k, u W_v`` (``nh`` / ``nkv`` heads of
  ``head_dim``, query head ``h`` reading KV head ``h // (nh / nkv)``), causal,
  scores ``q . k * head_dim^-0.5``, softmax, ``concat(o) W_o``. No bias and NO
  rotary embedding.
- ``E``, routed FFN: ``s = sigmoid(u W_r)``; the token's experts are the
  ``top_k`` largest of ``s + b`` (``router_bias``); their weights are
  ``routed_scale * s_e / sum_picked s`` (the UNBIASED scores; the sum over all
  ``top_k`` picks); ``MLP(u; W_up, W_down) = relu(u W_up)^2 W_down``; output
  ``MLP(u; shared) + sum_e weight_e MLP(u; expert e)``.

**The share** and **teacher-forced routing** are ``reference_deepseek_v2``'s,
word for word: the sum over ``e`` runs over the token's experts that this chip
HOLDS (``first_held ..``), and where the program says which experts its
serving programs used, the routed sum runs over exactly those, each at THIS
router's float32 score, normalised over the ``top_k`` told. ``logits_at(...,
with_router=True)`` returns ``s + b``, the selection's own score.

One sequence at a time, a block of ``BLOCK`` queries at a time, one expert's
float32 weights at a time, the head a block of the vocabulary at a time, so
that it fits beside the engine it judges.

Departures from the published description (each also in the configuration's
``assumed``): the attention applies no rotary embedding although the row
carries ``rope_theta`` and ``partial_rotary_factor``: the published
``nemotron_h`` modelling code applies none (position comes through the
Mamba-2 layers), which is knowledge of that code and not a key of the row;
the program stores ``W_in`` as two matrices (``[z | xBC]`` and ``dt``'s
columns), joined again here, and a routed expert's ``W_up`` and the
attention's ``W_q`` by output unit (``[I, hidden]``, ``[heads x head_dim,
hidden]``), turned again here; ``time_step_*`` only initialise ``dt_bias``;
``expand``, ``chunk_size``, ``rope_theta`` are read by nothing.
"""
import functools
import re

import jax
import jax.numpy as jnp

BLOCK = 256
VOCAB_BLOCK = 8192
SSD = ("ln", "ssd_in", "ssd_dt", "ssd_conv", "ssd_conv_b", "ssd_A_log",
       "ssd_D", "ssd_dt_b", "ssd_norm", "ssd_out")
ATTN = ("ln", "wq", "wk", "wv", "wo")
MOE = ("moe_ln", "router", "router_bias", "w_up", "w_down", "ws_up",
       "ws_down")
EXPERTS = ("w_up", "w_down")


def weights_of(model):
    """The arrays of a ``NemotronHForCausalLM`` by the names used here, one
    stack a kind of block."""
    def stack(prefix, names):
        return {n: getattr(model, f"{prefix}_{n}").value for n in names}

    return {"M": stack("ssd", SSD), "*": stack("attn", ATTN),
            "E": stack("moe", MOE), "embed": model.embed_tokens.value,
            "final_norm": model.final_norm.value,
            "lm_head": model.lm_head.value,
            # ids [B, S] -> the experts the serving programs used, [L_expert,
            # B, S, top_k], -1 where they did not run; or None
            "served_picks": getattr(model, "served_router_picks", None)}


def hyper_of(config):
    return {"pattern": str(config.hybrid_override_pattern),
            "num_heads": int(config.num_attention_heads),
            "num_kv_heads": int(config.num_key_value_heads),
            "head_dim": int(config.head_dim),
            "ssd_heads": int(config.mamba_num_heads),
            "ssd_head_dim": int(config.mamba_head_dim),
            "ssd_groups": int(config.n_groups),
            "ssd_state": int(config.ssm_state_size),
            "eps": float(config.layer_norm_epsilon),
            "top_k": int(config.num_experts_per_tok),
            "norm_topk_prob": bool(config.norm_topk_prob),
            "first_held": int(config.first_held_expert),
            "routed_scale": float(config.routed_scaling_factor)}


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mamba2(u, w, hy):
    """The Mamba-2 mixer on one sequence ``u [S, hidden]`` (already
    normalised), the recurrence token by token from a zero state."""
    H, P, G, N = (hy[k] for k in ("ssd_heads", "ssd_head_dim", "ssd_groups",
                                  "ssd_state"))
    S, C, GN = u.shape[0], H * P, G * N
    proj = u @ jnp.concatenate([w["ssd_in"], w["ssd_dt"]], axis=1)
    z, xbc, dt = proj[:, :C], proj[:, C:2 * C + 2 * GN], proj[:, 2 * C + 2 * GN:]
    taps = w["ssd_conv"].shape[0]
    ext = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["ssd_conv_b"] + sum(
        w["ssd_conv"][j] * ext[j:j + S] for j in range(taps)))
    x = xbc[:, :C].reshape(S, H, P)
    b = jnp.repeat(xbc[:, C:C + GN].reshape(S, G, N), H // G, axis=1)
    c = jnp.repeat(xbc[:, C + GN:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["ssd_dt_b"])                    # [S, H]
    a = -jnp.exp(w["ssd_A_log"])                                # [H]

    def token(s, t):
        xt, bt, ct, dtt = t
        s = jnp.exp(dtt * a)[:, None, None] * s \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, ct)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (x, b, c, dt))
    y = y + w["ssd_D"][:, None] * x
    y = (y.reshape(S, C) * jax.nn.silu(z)).reshape(S, G, C // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + hy["eps"])
    return (y.reshape(S, C) * w["ssd_norm"]) @ w["ssd_out"]


def attention(u, w, hy):
    """Causal grouped-query attention on one sequence, a block of queries at
    a time; no rotary embedding."""
    nh, nkv, hd = hy["num_heads"], hy["num_kv_heads"], hy["head_dim"]
    S = u.shape[0]
    q = (u @ w["wq"].T).reshape(S, nh, hd)     # (stored by output feature)
    k = jnp.repeat((u @ w["wk"]).reshape(S, nkv, hd), nh // nkv, axis=1)
    v = jnp.repeat((u @ w["wv"]).reshape(S, nkv, hd), nh // nkv, axis=1)
    pad = -S % BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    cols = jnp.arange(S)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, BLOCK)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        seen = cols[None, :] <= (start + jnp.arange(BLOCK))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    o = jax.lax.map(one, jnp.arange(0, S + pad, BLOCK))
    return o.reshape(S + pad, nh * hd)[:S] @ w["wo"]


def _mlp(u, w_up, w_down):
    return jnp.square(jax.nn.relu(u @ w_up)) @ w_down


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
    """(shared expert + the HELD experts' part of the routed sum, the
    selection's scores ``s + b``) of one sequence."""
    scores = jax.nn.sigmoid(u @ w["router"])
    top_e, top_s = route(scores, w["router_bias"], forced, hy)

    def one_expert(acc, j):
        weight = jnp.sum(
            jnp.where(top_e == hy["first_held"] + j, top_s, 0.0), -1)
        w_up, w_down = (_f32(jax.lax.dynamic_index_in_dim(w[n], j, 0, False))
                        for n in EXPERTS)
        y = _mlp(u, w_up.T, w_down)     # (stored by output unit)
        return acc + weight[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          jnp.arange(w["w_up"].shape[0]))
    return out + _mlp(u, w["ws_up"], w["ws_down"]), \
        scores + w["router_bias"]


@functools.partial(jax.jit, static_argnames=("hyper", "kind"))
def _block(x, stacked, i, forced, *, hyper, kind):
    """Block ``i`` of the stack of its ``kind`` on hidden states x [B, S, H]
    (float32): (x', the router's ``s + b`` [B, S, E], or 0)."""
    hy = dict(hyper)
    w = {n: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
         for n, a in stacked.items()}
    w = {n: (a if n in EXPERTS else _f32(a)) for n, a in w.items()}

    def one_sequence(args):
        xs, told = args
        if kind == "E":
            out, s = routed_ffn(_rms(xs, w["moe_ln"], hy["eps"]), w, told,
                                hy)
            return xs + out, s
        mixer = mamba2 if kind == "M" else attention
        return xs + mixer(_rms(xs, w["ln"], hy["eps"]), w, hy), \
            jnp.zeros((), x.dtype)

    return jax.lax.map(one_sequence, (x, forced))


def hidden_states(weights, hyper, ids, with_router=False):
    """Final-norm hidden states [B, S, H], float32; with ``with_router``
    also the routed FFNs' ``s + b`` [L_expert, B, S, E]."""
    hyper = dict(hyper)
    pattern = hyper["pattern"]
    if not re.fullmatch(r"[ME*]+", pattern):
        raise ValueError(f"hybrid_override_pattern {pattern!r}")
    static = tuple(sorted(hyper.items()))
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(weights["embed"], ids, axis=0))
        served = weights.get("served_picks")
        served = None if served is None else served(ids)
        own = jnp.full(ids.shape + (hyper["top_k"],), -1, jnp.int32)
        seen = dict.fromkeys("ME*", 0)
        scores = []
        for kind in pattern:
            i = seen[kind]
            told = own if served is None or kind != "E" \
                else jnp.asarray(served[i], jnp.int32)
            x, s = _block(x, weights[kind], jnp.int32(i), told, hyper=static,
                          kind=kind)
            seen[kind] += 1
            if with_router and kind == "E":
                scores.append(s)
        x = _rms(x, _f32(weights["final_norm"]), hyper["eps"])
        return (x, jnp.stack(scores)) if with_router else x


def logits_at(weights, hyper, ids, at, with_router=False):
    """Float32 logits [B, K, V] at the K positions ``at[b]`` of each row;
    with ``with_router`` also the routed FFNs' float32 ``s + b`` at those
    positions, [L_expert, B, K, E]: their ``top_k`` largest are the experts
    the block used."""
    out = hidden_states(weights, hyper, ids, with_router)
    x, scores = out if with_router else (out, None)
    at = jnp.asarray(at, jnp.int32)
    with jax.default_matmul_precision("highest"):
        picked = jnp.take_along_axis(x, at[..., None], axis=1)
        head = weights["lm_head"]
        logits = jnp.concatenate(
            [picked @ _f32(head[:, v:v + VOCAB_BLOCK])
             for v in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
    if not with_router:
        return logits
    return logits, jnp.take_along_axis(scores, at[None, ..., None], axis=2)

