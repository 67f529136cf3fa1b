"""The plain reference of DeepSeek-V2 (``model_type`` ``deepseek_v2``): a
pre-norm decoder with multi-head latent attention, one or more leading dense
layers and then layers of shared plus group-limited routed experts, in
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
EXPANDED form only: no absorbed weights, no kernel, no cache, no batching, no
sorting of tokens by expert, and nothing imported from ``paddle_tpu``: the
program hands over its weights (``weights_of``) and its sizes (``hyper_of``)
and is then judged by this file, through the same three entry points as
``reference.py``.

Per layer, ``x`` the residual stream, ``h = RMSNorm(x; input_ln)``, no bias:

- ``c_q = RMSNorm(h W_qa; q_a_ln)``; ``q = c_q W_qb``, by head ``q_nope |
  q_pe``;
- ``[c_kv | k_pe] = h W_kva``; ``c_kv = RMSNorm(c_kv; kv_a_ln)``; ``k_pe`` is
  ONE vector a token, shared by all heads; ``[k_nope | v] = c_kv W_kvb`` by
  head;
- ``q_pe, k_pe = RoPE(q_pe), RoPE(k_pe)`` at the token's position with YaRN's
  frequencies: frequency ``i`` is ``theta^(-2i/d)`` where it turns more than
  ``beta_fast`` times inside ``original_max_position_embeddings``, the same
  over ``factor`` where fewer than ``beta_slow`` times, and a linear blend
  between the two correction dims; cos and sin are multiplied by
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (1 for the
  published values), ``mscale(s, m) = 0.1 m ln s + 1``;
- ``score = (q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-0.5 *
  mscale(factor, mscale_all_dim)^2``, causal softmax, ``o = softmax . v``,
  ``x = x + concat_heads(o) W_o``;
- ``g = RMSNorm(x; post_ln)``. A dense layer: ``x = x + SwiGLU(g)``. An expert
  layer: ``s = softmax(g W_r)`` over the router's whole width; the experts
  are ``n_group`` groups of consecutive ids, a group's score is the MAX of its
  experts', the ``topk_group`` best groups are kept and every other expert's
  score is set to 0; the ``top_k`` largest of what is left are the token's
  experts with weights ``s_e`` as they are (or divided by their sum,
  ``norm_topk_prob``); ``x = x + SwiGLU(g; shared) + routed_scaling_factor *
  sum_e s_e SwiGLU(g; expert e)``.

After the last layer ``RMSNorm(x; final_norm)`` and the untied head.

**The share.** The program may hold only the experts ``first_held ..
first_held + n_held`` of the router's width (one chip's part of an
expert-parallel layer) and a slice of the vocabulary. The reference is given
the same share: the sum over ``e`` runs over the token's experts that are
HELD, as a loop over the held ids with a mask (every held expert sees every
token and a token keeps only its own), what the absent experts would add is
left out, and that partial sum goes on to the next layer. With every expert
held this is the published layer.

**Teacher-forced routing.** ``routed_scaling_factor`` 16 on random weights
makes the forward ill-conditioned: a near-tie of the router that bfloat16
decides the other way moves the stream by a tenth, later routers follow, and
a sound bfloat16 system leaves a float32 reference that routes for itself by
a fifth of the logits' range (measured, PERF.md PR 31). So where the program
says which experts its SERVING programs used (``weights_of`` takes the
model's ``served_router_picks``), the routed sum here runs over exactly those
experts, each weighted by THIS router's float32 score for it, and the layer's
own rule applies only where the program says nothing (-1). The logits then
judge the values of the served computation at its published scale (a wrong
held range, expert order, weight or grouped matmul moves them by the whole
routed sum), and ``judged_scores`` judges the served picks themselves: an
expert the rule would not pick has a score short of the ``top_k``-th, whoever
forced it.

One sequence at a time, a group of ``HEADS`` heads and a block of ``BLOCK``
queries at a time, one expert's float32 weights at a time, so that it fits
beside the engine it judges.

Departures from the published description: the published checkpoint's RoPE
pairs are interleaved ``(2i, 2i + 1)``; here the pairs are ``(i, i + rope /
2)`` (half-split). With random weights either pairing is a relabelling of the
columns of ``W_qb`` and ``W_kva``, and the system uses the same one. The
balance losses (``seq_aux``) belong to training and are absent.
"""
import functools
import math

import jax
import jax.numpy as jnp

BLOCK = 256
HEADS = 16
ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "q_a_ln", "kv_a_ln",
        "input_ln", "post_ln")
FFN = ("w_gate", "w_up", "w_down")
SHARED = ("ws_gate", "ws_up", "ws_down")


def weights_of(model):
    """The arrays of a ``DeepseekV2ForCausalLM``, by the names used here:
    ``expert`` and ``dense`` are the two stacks of layers."""
    w = {"expert": {n: getattr(model, n).value
                    for n in ATTN + FFN + SHARED + ("router",)},
         "dense": None}
    if model.config.first_k_dense_replace:
        w["dense"] = {n: getattr(model, "dense_" + n).value
                      for n in ATTN + FFN}
    w["embed"] = model.embed_tokens.value
    w["final_norm"] = model.final_norm.value
    w["lm_head"] = (model.embed_tokens.value.T if model.lm_head is None
                    else model.lm_head.value)
    # ids [B, S] -> the experts the serving programs used, [L_expert, B, S,
    # top_k], -1 where they did not run; or None (module docstring)
    w["served_picks"] = getattr(model, "served_router_picks", None)
    return w


def hyper_of(config):
    rs = config.rope_scaling
    return {"num_heads": int(config.num_attention_heads),
            "rank": int(config.kv_lora_rank),
            "nope": int(config.qk_nope_head_dim),
            "rope": int(config.qk_rope_head_dim),
            "v_dim": int(config.v_head_dim),
            "eps": float(config.rms_norm_eps),
            "theta": float(config.rope_theta),
            "yarn": None if not rs else (
                float(rs["factor"]),
                int(rs["original_max_position_embeddings"]),
                float(rs["beta_fast"]), float(rs["beta_slow"]),
                float(rs["mscale"]), float(rs["mscale_all_dim"])),
            "top_k": int(config.num_experts_per_tok),
            "norm_topk_prob": bool(config.norm_topk_prob),
            "n_group": int(config.n_group),
            "topk_group": int(config.topk_group),
            "first_held": int(config.first_held_expert),
            "routed_scale": float(config.routed_scaling_factor)}


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_frequencies(dim, theta, yarn):
    """The ``dim / 2`` rotary frequencies (module docstring)."""
    plain = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if yarn is None:
        return plain
    factor, original, beta_fast, beta_slow = yarn[:4]

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out


def softmax_scale(hyper):
    scale = (hyper["nope"] + hyper["rope"]) ** -0.5
    if hyper["yarn"] is not None:
        scale *= mscale(hyper["yarn"][0], hyper["yarn"][5]) ** 2
    return scale


def _rope(x, theta, yarn):
    """x: [S, heads, D]; position s rotates pair (d, d + D/2) by s times
    frequency d."""
    s, _, d = x.shape
    inv = jnp.asarray(yarn_frequencies(d, theta, yarn), jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    m = 1.0 if yarn is None else mscale(yarn[0], yarn[4]) \
        / mscale(yarn[0], yarn[5])
    sin, cos = m * jnp.sin(ang)[:, None, :], m * jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale):
    """Causal softmax attention of one sequence. q, k: [S, H, Dk]; v:
    [S, H, Dv]."""
    s, h, _ = q.shape
    blk = min(BLOCK, s)
    pad = (-s) % blk
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, blk, 0)
        logits = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        mask = jnp.arange(s)[None, :] <= start + jnp.arange(blk)[:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(one_block, jnp.arange(0, s + pad, blk))
    return out.reshape(s + pad, h, -1)[:s]


def _mla(h, w, hy):
    """The attention block's output (before the residual) for one sequence
    ``h [S, H]``, a group of heads at a time."""
    s = h.shape[0]
    nh, nope, rope, vd = hy["num_heads"], hy["nope"], hy["rope"], hy["v_dim"]
    c_q = _rms(h @ w["wq_a"], w["q_a_ln"], hy["eps"])
    kv = h @ w["wkv_a"]
    c_kv = _rms(kv[:, :hy["rank"]], w["kv_a_ln"], hy["eps"])
    k_pe = _rope(kv[:, None, hy["rank"]:], hy["theta"], hy["yarn"])
    g = min(HEADS, nh)
    w_qb = w["wq_b"].reshape(-1, nh // g, g * (nope + rope))
    w_kvb = w["wkv_b"].reshape(-1, nh // g, g * (nope + vd))
    w_o = w["wo"].reshape(nh // g, g * vd, -1)

    def head_group(acc, i):
        q = (c_q @ w_qb[:, i]).reshape(s, g, nope + rope)
        kvh = (c_kv @ w_kvb[:, i]).reshape(s, g, nope + vd)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], hy["theta"], hy["yarn"])],
            -1)
        k = jnp.concatenate(
            [kvh[..., :nope], jnp.broadcast_to(k_pe, (s, g, rope))], -1)
        o = _attention(q, k, kvh[..., nope:], softmax_scale(hy))
        return acc + o.reshape(s, g * vd) @ w_o[i], None

    out, _ = jax.lax.scan(head_group, jnp.zeros_like(h),
                          jnp.arange(nh // g))
    return out


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def _groups(scores, n_group, topk_group):
    """(group scores [S, G], the max of each group's experts'; descending
    copy; kept [S, G]: the ``topk_group`` best)."""
    s, e = scores.shape
    best = scores.reshape(s, n_group, e // n_group).max(-1)
    ranked = jnp.sort(best, -1)[:, ::-1]
    return best, ranked, best >= ranked[:, topk_group - 1][:, None]


def _only(groups, scores):
    """scores with every expert outside ``groups [S, G]`` set to 0."""
    size = scores.shape[1] // groups.shape[1]
    return jnp.where(jnp.repeat(groups, size, axis=1), scores, 0.0)


def group_limited_scores(scores, n_group, topk_group):
    """scores [S, E]: every expert outside the token's ``topk_group`` best
    groups set to 0 (a group's score the max of its experts')."""
    return _only(_groups(scores, n_group, topk_group)[2], scores)


def judged_scores(raw, n_group, topk_group, top_k):
    """What ``logits_at(..., with_router=True)`` returns for a check of the
    system's picks: a score ``r`` over the router's whole width whose
    ``top_k`` largest are exactly the group-limited rule's experts, and for
    which ``(r_kth - r_e) / r_kth`` says by what share the router's scores
    would have to be off for any other expert ``e`` to be a rightful pick.

    The hard mask itself cannot judge: it gives every expert of a group
    that lost the last place by a hair the score 0, so a system whose
    bfloat16 hidden state flips two nearly equal GROUPS would look as wrong
    as one that picks at random (with random weights that happens in every
    run: measured, PERF.md PR 31). So beside the rule's own world, every
    world one group flip away is scored, and an expert keeps the best of its
    scores:

    - the rule's own: ``s_e`` if the expert's group is kept, else 0 (the
      layer's scores; a pick just below the ``top_k``-th, ``s_kth``, is short
      by its own share of it);
    - a world in which one group that lost takes the place of one that was
      kept: an expert is short there by the share ``1 - G_lost / G_kept``
      that the two group scores would have to move, and by its own share of
      THAT world's ``top_k``-th score: ``s_kth * (G_lost / G_kept) * min(1,
      s_e / s_kth')`` if its group is kept there, else 0.

    A system off by two group flips at once is scored as wrong."""
    e = raw.shape[1]
    best, ranked, kept = _groups(raw, n_group, topk_group)

    def world(groups):
        masked = _only(groups, raw)
        return masked, jnp.sort(masked, -1)[:, e - top_k][:, None]

    out, s_kth = world(kept)
    for i in range(topk_group):                     # a kept group leaves
        for j in range(topk_group, n_group):        # a lost one comes in
            leaves, comes = ranked[:, i][:, None], ranked[:, j][:, None]
            other, other_kth = world((kept & (best != leaves))
                                     | (best == comes))
            out = jnp.maximum(out, s_kth * (comes / leaves)
                              * jnp.minimum(1.0, other / other_kth))
    return out


def _routed(g, raw, forced, w, hy):
    """The held experts' part of the routed sum for one sequence. g: [S, H];
    raw: [S, E], the router's softmax; forced: [S, top_k] int32, the experts
    a token is to use, or -1 for the rule's own."""
    top_s, top_e = jax.lax.top_k(
        group_limited_scores(raw, hy["n_group"], hy["topk_group"]),
        hy["top_k"])
    told = forced[:, :1] >= 0
    top_e = jnp.where(told, forced, top_e)
    top_s = jnp.where(told, jnp.take_along_axis(
        raw, jnp.maximum(forced, 0), axis=-1), top_s)
    if hy["norm_topk_prob"]:
        top_s = top_s / jnp.sum(top_s, -1, keepdims=True)

    def one_expert(acc, j):
        # this token's weight for held expert j (router id first_held + j):
        # its score if that expert is among the token's top_k, else 0
        weight = jnp.sum(
            jnp.where(top_e == hy["first_held"] + j, top_s, 0.0), -1)
        y = _swiglu(g, *(_f32(jax.lax.dynamic_index_in_dim(w[n], j, 0, False))
                         for n in FFN))
        return acc + weight[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(g),
                          jnp.arange(w["w_gate"].shape[0]))
    return hy["routed_scale"] * out


@functools.partial(jax.jit, static_argnames=("hyper",))
def _layer(x, stacked, i, forced, *, hyper):
    """Layer i of a stack on hidden states x [B, S, H] (float32), its routed
    experts ``forced [B, S, top_k]`` (-1: the rule's own): (x', the router's
    ``judged_scores`` [B, S, E], or 0 for a dense stack)."""
    hy = dict(hyper)
    routed = "router" in stacked
    w = {n: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
         for n, a in stacked.items()}
    # a routed layer's experts stay in their dtype: one is upcast at a time
    w = {n: (a if routed and n in FFN else _f32(a)) for n, a in w.items()}

    def one_sequence(args):
        xs, told = args
        xs = xs + _mla(_rms(xs, w["input_ln"], hy["eps"]), w, hy)
        g = _rms(xs, w["post_ln"], hy["eps"])
        if not routed:
            return xs + _swiglu(g, *(w[n] for n in FFN)), jnp.zeros((), x.dtype)
        raw = jax.nn.softmax(g @ w["router"], -1)
        return (xs + _swiglu(g, *(w[n] for n in SHARED))
                + _routed(g, raw, told, w, hy),
                judged_scores(raw, hy["n_group"], hy["topk_group"],
                              hy["top_k"]))

    return jax.lax.map(one_sequence, (x, forced))


def hidden_states(weights, hyper, ids, with_router=False):
    """Final-norm hidden states [B, S, H], float32; with ``with_router``
    also the expert layers' ``judged_scores`` [L_expert, B, S, E]."""
    static = tuple(sorted(hyper.items()))
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(weights["embed"], ids, axis=0))
        served = weights.get("served_picks")
        served = None if served is None else served(ids)
        own = jnp.full(ids.shape + (hyper["top_k"],), -1, jnp.int32)
        scores = []
        for stack in (weights["dense"], weights["expert"]):
            if stack is None:
                continue
            for i in range(stack["input_ln"].shape[0]):
                told = own if served is None or "router" not in stack \
                    else jnp.asarray(served[i], jnp.int32)
                x, s = _layer(x, stack, jnp.int32(i), told, hyper=static)
                if with_router and "router" in stack:
                    scores.append(s)
        x = _rms(x, _f32(weights["final_norm"]), hyper["eps"])
        return (x, jnp.stack(scores)) if with_router else x


def logits_at(weights, hyper, ids, at, with_router=False):
    """Float32 logits [B, K, V] at the K positions ``at[b]`` of each row;
    with ``with_router`` also the expert layers' float32 router scores at
    those positions as ``judged_scores`` gives them, [L_expert, B, K, E]:
    their ``top_k`` largest are the experts the layer used."""
    out = hidden_states(weights, hyper, ids, with_router)
    x, scores = out if with_router else (out, None)
    at = jnp.asarray(at, jnp.int32)
    with jax.default_matmul_precision("highest"):
        picked = jnp.take_along_axis(x, at[..., None], axis=1)
        logits = picked @ _f32(weights["lm_head"])
    if not with_router:
        return logits
    return logits, jnp.take_along_axis(scores, at[None, ..., None], axis=2)
