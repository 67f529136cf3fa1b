"""The plain reference of GLM-5.2 (``model_type`` ``glm_moe_dsa``): a pre-norm
decoder with multi-head latent attention over a LEARNED SELECTION of the
earlier positions (DeepSeek Sparse Attention, the indexer in some layers
only), leading dense layers and then layers of one shared plus routed experts
under a sigmoid router with a selection bias; in ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``. EXPANDED form only: no
absorbed weights, no kernel, no cache, no batching, no sorting of tokens by
expert, and nothing imported from ``paddle_tpu``: the program hands over its
weights (``weights_of``) and its sizes (``hyper_of``) and is then judged by
this file, through the same three entry points as ``reference.py``.

Layer ``l``, ``x`` the residual stream, ``h = RMSNorm(x; input_ln)``, no bias
in attention:

- ``c_q = RMSNorm(h W_qa; q_a_ln)``; ``q = c_q W_qb``, by head ``q_nope |
  q_pe``; ``[c_kv | k_pe] = h W_kva``; ``c_kv = RMSNorm(c_kv; kv_a_ln)``;
  ``k_pe`` is ONE vector a token, shared by all heads; ``[k_nope | v] = c_kv
  W_kvb`` by head; ``q_pe`` and ``k_pe`` rotated (plain RoPE, ``theta``, no
  scaling);
- **the selected set** ``S_t``. Where ``indexer_types[l]`` is ``full``:
  ``q^I_t = c_q_t W^I_qb`` as ``index_heads`` heads of ``index_dim``, the
  first ``rope`` values of each rotated by the same RoPE; ``k^I_s =
  LayerNorm(h_s W^I_k; weight, bias, index_eps)`` (one head), its first
  ``rope`` values rotated; ``w_t = h_t W^I_w * index_heads^-0.5 *
  index_dim^-0.5``; ``I[t, s] = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)`` for
  ``s <= t``; ``S_t`` = the positions of the ``min(index_topk, t + 1)``
  largest ``I[t, :]`` (equal scores: the earlier position). Where it is
  ``shared``: the nearest ``full`` layer's ``S_t`` before it, unchanged;
- ``score = (q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-0.5`` over ``s``
  in ``S_t`` only, softmax, ``o = softmax . v``, ``x = x + concat_heads(o)
  W_o``;
- ``g = RMSNorm(x; post_ln)``. A dense layer: ``x = x + SwiGLU(g)``. An expert
  layer: ``s = sigmoid(g W_r)``; the token's experts are the ``top_k``
  largest of ``s + b`` (``router_bias``); their weights are ``scale * s_e /
  sum_picked s`` (the UNBIASED scores; the sum over all ``top_k`` picks);
  ``x = x + SwiGLU(g; shared) + sum_e weight_e SwiGLU(g; expert e)``.

After the last layer ``RMSNorm(x; final_norm)`` and the untied head.

**The share** and **teacher-forced routing** are ``reference_deepseek_v2``'s,
word for word: the sum over ``e`` runs over the token's experts that this
chip HOLDS (``first_held ..``), and where the program says which experts its
serving programs used, the routed sum runs over exactly those, each at THIS
router's float32 score, normalised over the eight told. ``logits_at(...,
with_router=True)`` returns ``s + b``, the selection's own score: its
``top_k`` largest are the rule's experts and ``(r_kth - r_e) / r_kth`` is the
share by which the scores would have to be off for ``e`` to be a rightful
pick. The SELECTION of positions is not teacher-forced: the reference selects
for itself, in float32.

One sequence at a time, a block of ``BLOCK`` queries and a group of ``HEADS``
heads at a time, one expert's float32 weights at a time, the head a block of
the vocabulary at a time, so that it fits beside the engine it judges.

Departures from the published description (each also in the configuration's
``assumed``): RoPE pairs are half-split ``(i, i + rope / 2)`` where the
published checkpoint's are interleaved ``(2i, 2i + 1)`` (with random weights a
relabelling of the columns of ``W_qb``, ``W_kva``, ``W^I_qb`` and ``W^I_k``;
LayerNorm is invariant under it; the system uses the same one); the published
inference kernel rotates ``q^I`` and ``k^I`` by a Hadamard matrix and
quantises them to FP8 before the product: the rotation is orthogonal and
leaves every ``q . k``, the quantisation is that kernel's storage choice, and
neither is here; the index key's LayerNorm ``eps`` (1e-6) is assumed; the
multi-token-prediction layer is not held; DeepSeek-V3's group rule does not
arise (``n_group`` 1).
"""
import functools

import jax
import jax.numpy as jnp

BLOCK = 256
HEADS = 16
VOCAB_BLOCK = 8192
ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "q_a_ln", "kv_a_ln",
        "input_ln", "post_ln")
FFN = ("w_gate", "w_up", "w_down")
SHARED = ("ws_gate", "ws_up", "ws_down")
INDEXER = ("idx_wq_b", "idx_wk", "idx_k_ln_w", "idx_k_ln_b", "idx_w")


def weights_of(model):
    """The arrays of a ``GlmMoeDsaForCausalLM`` by the names used here:
    ``expert`` and ``dense`` are the two stacks of layers; a stack's indexer
    weights are stacked over ITS ``full`` layers, in order."""
    def stack(prefix, names):
        w = {n: getattr(model, prefix + n).value for n in names}
        if hasattr(model, prefix + "idx_wk"):
            w.update({n: getattr(model, prefix + n).value for n in INDEXER})
        return w

    w = {"expert": stack("", ATTN + FFN + SHARED + ("router",
                                                   "router_bias")),
         "dense": None}
    if model.config.first_k_dense_replace:
        w["dense"] = stack("dense_", ATTN + FFN)
    w["embed"] = model.embed_tokens.value
    w["final_norm"] = model.final_norm.value
    w["lm_head"] = (model.embed_tokens.value.T if model.lm_head is None
                    else model.lm_head.value)
    w["served_picks"] = getattr(model, "served_router_picks", None)
    return w


def hyper_of(config):
    return {"num_heads": int(config.num_attention_heads),
            "rank": int(config.kv_lora_rank),
            "nope": int(config.qk_nope_head_dim),
            "rope": int(config.qk_rope_head_dim),
            "v_dim": int(config.v_head_dim),
            "eps": float(config.rms_norm_eps),
            "theta": float(config.rope_parameters["rope_theta"]),
            "top_k": int(config.num_experts_per_tok),
            "norm_topk_prob": bool(config.norm_topk_prob),
            "first_held": int(config.first_held_expert),
            "routed_scale": float(config.routed_scaling_factor),
            "index_heads": int(config.index_n_heads),
            "index_dim": int(config.index_head_dim),
            "index_topk": int(config.index_topk),
            "index_eps": float(config.index_layer_norm_eps),
            "n_dense": int(config.first_k_dense_replace),
            "indexer_types": tuple(config.indexer_types)}


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def _rope(x, theta):
    """x: [S, heads, D]; position s rotates pair (d, d + D/2) by s times
    ``theta^(-2d/D)``."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rope_first(x, width, theta):
    """x: [S, heads, D] with the first ``width`` values of a head rotated."""
    return jnp.concatenate([_rope(x[..., :width], theta), x[..., width:]], -1)


def _blocks(s, fn, *arrays):
    """``fn(start, *blocks of BLOCK rows)`` over the rows of ``arrays``,
    stacked back to ``s`` rows."""
    blk = min(BLOCK, s)
    pad = (-s) % blk
    padded = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
              for a in arrays]

    def one(start):
        return fn(start, *(jax.lax.dynamic_slice_in_dim(a, start, blk, 0)
                           for a in padded))

    out = jax.lax.map(one, jnp.arange(0, s + pad, blk))
    return out.reshape((s + pad,) + out.shape[2:])[:s]


def index_scores(c_q, h, w, hy):
    """``I [S, S]`` of one sequence (module docstring), ``-inf`` above the
    diagonal."""
    s = h.shape[0]
    q = _rope_first((c_q @ w["idx_wq_b"]).reshape(
        s, hy["index_heads"], hy["index_dim"]), hy["rope"], hy["theta"])
    k = _layer_norm(h @ w["idx_wk"], w["idx_k_ln_w"], w["idx_k_ln_b"],
                    hy["index_eps"])
    k = _rope_first(k[:, None, :], hy["rope"], hy["theta"])[:, 0]
    wt = (h @ w["idx_w"]) * (hy["index_heads"] ** -0.5
                             * hy["index_dim"] ** -0.5)

    def one(start, qb, wb):
        dots = jnp.einsum("qjd,sd->qjs", qb, k)
        scores = jnp.einsum("qj,qjs->qs", wb, jnp.maximum(dots, 0.0))
        seen = jnp.arange(s)[None, :] <= start + jnp.arange(qb.shape[0])[:,
                                                                        None]
        return jnp.where(seen, scores, -jnp.inf)

    return _blocks(s, one, q, wt)


def select(scores, topk):
    """mask [S, S]: row ``t`` the ``min(topk, t + 1)`` largest ``scores[t,
    :t + 1]`` (``jax.lax.top_k``: of equal scores the earlier position)."""
    s = scores.shape[0]
    k = min(int(topk), s)

    def one(start, sb):
        _, idx = jax.lax.top_k(sb, k)
        hit = jnp.zeros(sb.shape, bool).at[
            jnp.arange(sb.shape[0])[:, None], idx].set(True)
        return hit & (sb > -jnp.inf)

    return _blocks(s, one, scores)


def _attention(q, k, v, mask, scale):
    """Softmax attention of one sequence over ``mask [S, S]``. q, k: [S, H,
    Dk]; v: [S, H, Dv]."""
    def one(start, qb, mb):
        logits = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        probs = jax.nn.softmax(jnp.where(mb[None], logits, -jnp.inf), -1)
        # (a padding query's row is empty: its softmax is NaN and dropped)
        return jnp.einsum("hqk,khd->qhd", jnp.where(mb[None], probs, 0.0), v)

    return _blocks(q.shape[0], one, q, mask)


def _mla(h, w, hy, sel):
    """(the attention block's output before the residual, the selection it
    used) for one sequence ``h [S, H]``; ``sel`` is the set handed down, or
    None for a layer whose ``w`` holds an indexer."""
    s = h.shape[0]
    nh, nope, rope, vd = hy["num_heads"], hy["nope"], hy["rope"], hy["v_dim"]
    c_q = _rms(h @ w["wq_a"], w["q_a_ln"], hy["eps"])
    if sel is None:
        sel = select(index_scores(c_q, h, w, hy), hy["index_topk"])
    kv = h @ w["wkv_a"]
    c_kv = _rms(kv[:, :hy["rank"]], w["kv_a_ln"], hy["eps"])
    k_pe = _rope(kv[:, None, hy["rank"]:], hy["theta"])
    g = min(HEADS, nh)
    w_qb = w["wq_b"].reshape(-1, nh // g, g * (nope + rope))
    w_kvb = w["wkv_b"].reshape(-1, nh // g, g * (nope + vd))
    w_o = w["wo"].reshape(nh // g, g * vd, -1)

    def head_group(acc, i):
        q = (c_q @ w_qb[:, i]).reshape(s, g, nope + rope)
        kvh = (c_kv @ w_kvb[:, i]).reshape(s, g, nope + vd)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], hy["theta"])], -1)
        k = jnp.concatenate(
            [kvh[..., :nope], jnp.broadcast_to(k_pe, (s, g, rope))], -1)
        o = _attention(q, k, kvh[..., nope:], sel, (nope + rope) ** -0.5)
        return acc + o.reshape(s, g * vd) @ w_o[i], None

    out, _ = jax.lax.scan(head_group, jnp.zeros_like(h),
                          jnp.arange(nh // g))
    return out, sel


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


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


def _routed(g, top_e, top_s, w, hy):
    """The HELD experts' part of the routed sum for one sequence."""
    def one_expert(acc, j):
        weight = jnp.sum(
            jnp.where(top_e == hy["first_held"] + j, top_s, 0.0), -1)
        y = _swiglu(g, *(_f32(jax.lax.dynamic_index_in_dim(w[n], j, 0, False))
                         for n in FFN))
        return acc + weight[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(g),
                          jnp.arange(w["w_gate"].shape[0]))
    return out


@functools.partial(jax.jit, static_argnames=("hyper", "full"))
def _layer(x, sel, stacked, indexer, i, forced, *, hyper, full):
    """Layer i of a stack on hidden states x [B, S, H] (float32) with the
    selection ``sel [B, S, S]`` handed down (``full``: the layer has an
    indexer, ``indexer`` its weights, and selects for itself): (x', sel',
    the router's ``s + b`` [B, S, E], or 0 for a dense stack)."""
    hy = dict(hyper)
    routed = "router" in stacked
    w = {n: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
         for n, a in stacked.items()}
    w = {n: (a if routed and n in FFN else _f32(a)) for n, a in w.items()}
    if full:
        w.update({n: _f32(a) for n, a in indexer.items()})

    def one_sequence(args):
        xs, told, had = args
        attn, used = _mla(_rms(xs, w["input_ln"], hy["eps"]), w, hy,
                          None if full else had)
        xs = xs + attn
        g = _rms(xs, w["post_ln"], hy["eps"])
        if not routed:
            return (xs + _swiglu(g, *(w[n] for n in FFN)), used,
                    jnp.zeros((), x.dtype))
        scores = jax.nn.sigmoid(g @ w["router"])
        top_e, top_s = route(scores, w["router_bias"], told, hy)
        return (xs + _swiglu(g, *(w[n] for n in SHARED))
                + _routed(g, top_e, top_s, w, hy), used,
                scores + w["router_bias"])

    return jax.lax.map(one_sequence, (x, forced, sel))


def hidden_states(weights, hyper, ids, with_router=False, with_sets=False):
    """Final-norm hidden states [B, S, H], float32; with ``with_router``
    also the expert layers' ``s + b`` [L_expert, B, S, E]; with ``with_sets``
    also every layer's selection [L, B, S, S] (a test's window into it)."""
    static = tuple(sorted(hyper.items()))
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _f32(jnp.take(weights["embed"], ids, axis=0))
        served = weights.get("served_picks")
        served = None if served is None else served(ids)
        own = jnp.full(ids.shape + (hyper["top_k"],), -1, jnp.int32)
        sel = jnp.zeros(ids.shape + ids.shape[1:], bool)
        scores, sets, layer = [], [], 0
        for stack in (weights["dense"], weights["expert"]):
            if stack is None:
                continue
            held = 0            # this stack's indexers seen so far
            layers = {n: a for n, a in stack.items() if n not in INDEXER}
            for i in range(stack["input_ln"].shape[0]):
                full = hyper["indexer_types"][layer] == "full"
                indexer = {n: stack[n][held] for n in INDEXER} if full \
                    else None
                told = own if served is None or "router" not in stack \
                    else jnp.asarray(served[i], jnp.int32)
                x, sel, s = _layer(x, sel, layers, indexer, jnp.int32(i),
                                   told, hyper=static, full=full)
                held, layer = held + full, layer + 1
                if with_router and "router" in stack:
                    scores.append(s)
                if with_sets:
                    sets.append(sel)
        x = _rms(x, _f32(weights["final_norm"]), hyper["eps"])
        out = (x,) + ((jnp.stack(scores),) if with_router else ()) \
            + ((jnp.stack(sets),) if with_sets else ())
        return out if len(out) > 1 else x


def logits_at(weights, hyper, ids, at, with_router=False):
    """Float32 logits [B, K, V] at the K positions ``at[b]`` of each row;
    with ``with_router`` also the expert layers' float32 ``s + b`` at those
    positions, [L_expert, B, K, E]: their ``top_k`` largest are the experts
    the layer used."""
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
