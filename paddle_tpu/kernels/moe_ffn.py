"""Dropless routed (mixture-of-experts) FFN for the serving step programs:
exact top-k for every live row, whatever the skew. An expert is a SwiGLU of
three matrices, ``(silu(x W_gate) * (x W_up)) W_down``, or, where the caller
has no gate matrix (``w_gate`` None), two matrices with a squared ReLU
between them, ``relu(x W_up)^2 W_down``. The two-matrix expert's ``w_up`` is
stored by OUTPUT unit, ``[E_held, I, H]``: its minor dimension is then the
model's hidden size and not ``I``, which need be no whole number of lanes
(1,856 at Nemotron-3-Nano's widths), and a Mosaic operand whose minor
dimension is not lane-aligned is copied whole, every expert of every layer, a
step (3.5 GB there: the compile's memory report, PR 47).

Two functions with one signature. ``moe_ffn_reference`` is the oracle of the
tests: every expert over every row, masked. ``moe_ffn`` is what the step
programs run: the live (row, expert) pairs are ordered by expert, three
grouped matmuls walk the groups (gate, up, down), and the weighted sum goes
back to row order. Shapes are static (``rows x top_k`` pair slots, a
length-``E`` count vector), so one program serves every routing; cost follows
the live pairs and the experts they touch: dead rows (the padding of a packed
buffer or of a prefill bucket) make no pair, and an expert nobody picked is
not read.

Router matmul and softmax run in float32 (``Precision.HIGHEST``) so that only
error upstream of the router can change which experts a row picks. The
weights are the softmax probabilities of the picked experts as they are
(OLMoE's ``norm_topk_prob`` is false): ``renormalize=True`` divides them by
their sum for a model that wants it, and ``scale`` multiplies them (a
``routed_scaling_factor``).

What a model may add to that, all static numbers of the one function:

- **groups** (``n_group``, ``topk_group``: group-limited greedy routing): the
  router's experts are ``n_group`` runs of consecutive ids, a group's score is
  the largest probability in it, only the ``topk_group`` best groups keep
  their probabilities (every other expert's is set to 0) and the ``top_k``
  are taken from what is left. ``n_group = 1`` is plain top-k.
- **a held range** (``first_held``): the expert stacks hold the contiguous ids
  ``first_held .. first_held + E_held`` of a router that is wider than they
  are: this chip's share of an expert-parallel layer. The router keeps its
  width and its rule, pairs are made only for the picks that land on a held
  expert, and what the absent experts would add is left out (the partial sum
  an exchange between chips would complete; no code stands in for it). With
  every expert held (``E_held`` the router's width) this is the layer whole.
- **a sigmoid router with a selection bias** (``router_bias``, a ``[E]``
  vector; DeepSeek-V3's ``noaux_tc``): the scores are ``sigmoid(h W_r)``, not
  a softmax; the ``top_k`` are the largest of ``score + bias`` (under the
  group limit, where there is one), and a picked expert's weight is its
  UNBIASED score. The bias decides near-ties only: it is in no weight. With
  ``renormalize`` the weights are divided by their sum over all ``top_k``
  picks, held here or not (the rule is over the router's width).
- the **shared expert** is not here: it is a dense SwiGLU every row runs, which
  the layer body adds beside this call (``serving.decode._decoder_layer``,
  scope ``moe_shared``).

The grouped matmul is ``jax.lax.ragged_dot`` on the CPU backend and the
Pallas grouped matmul that ships with JAX (``megablox.gmm``) on a TPU, whose
grid is the list of (row tile, group) visits of the non-empty groups; what
each cost on the v5e is in PERF.md (PR 26).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas_flash import _interpret_mode

#: rows of a grouped-matmul tile: pair slots are padded to a multiple of it
PAIR_TILE = 128
#: stats vector returned beside the output, one int32 each: the live pairs
#: on held experts (what the grouped matmuls run), the held experts with at
#: least one, the fullest one's, and the picks the live rows made over the
#: router's whole width (``pairs`` again when every expert is held)
STATS = ("pairs", "experts_touched", "max_expert_pairs", "picks")


def group_limited(probs, n_group, topk_group):
    """``probs [T, E]`` with every expert outside the row's ``topk_group``
    best groups set to 0; a group is ``E / n_group`` consecutive ids and its
    score the largest probability in it."""
    if n_group <= 1:
        return probs
    best = jnp.max(probs.reshape(probs.shape[0], n_group, -1), axis=-1)
    _, top_g = jax.lax.top_k(best, topk_group)
    keep = jnp.any(top_g[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                                  # [T, n_group]
    return jnp.where(jnp.repeat(keep, probs.shape[1] // n_group, axis=1),
                     probs, 0.0)


def _route(h2, router, top_k, live, renormalize, n_held, n_group=1,
           topk_group=1, first_held=0, scale=1.0, router_bias=None):
    """Float32 router: (weights [T, K] f32, experts [T, K] i32 by the
    router's ids (its width for a dead row), held ids [T, K] i32 (position in
    the held stack; ``n_held`` for a pick no held expert takes), counts
    [n_held] i32 of live pairs per held expert, stats [4] i32)."""
    n_exp = router.shape[-1]
    logits = jnp.dot(h2.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if router_bias is None:
        probs = group_limited(jax.nn.softmax(logits, axis=-1), n_group,
                              topk_group)
        w, idx = jax.lax.top_k(probs, top_k)
    else:
        probs = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(group_limited(
            probs + router_bias.astype(jnp.float32), n_group, topk_group),
            top_k)
        w = jnp.take_along_axis(probs, idx, axis=-1)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if scale != 1.0:
        w = w * scale
    # a dead row's picks go to the sentinel expert E: no group counts them
    idx = jnp.where(live[:, None], idx, n_exp).astype(jnp.int32)
    loc = idx - first_held
    loc = jnp.where((loc >= 0) & (loc < n_held), loc, n_held)
    # (a compare-and-sum, not a scatter-add: 4 us against 38 on the v5e)
    counts = jnp.sum(loc.reshape(-1, 1) == jnp.arange(n_held)[None, :],
                     axis=0, dtype=jnp.int32)
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                       jnp.max(counts),
                       top_k * jnp.sum(live)]).astype(jnp.int32)
    return w, idx, loc, counts, stats


def _prep(h, live):
    lead, hid = h.shape[:-1], h.shape[-1]
    h2 = h.reshape(-1, hid)
    live = (jnp.ones(h2.shape[0], bool) if live is None
            else live.reshape(-1).astype(bool))
    return lead, h2, live


def relu2(x):
    """``relu(x)^2``, the two-matrix expert's activation."""
    r = jnp.maximum(x, 0)
    return r * r


def moe_ffn_reference(h, router, w_gate, w_up, w_down, *, top_k, live=None,
                      renormalize=False, **routing):
    """h [..., H]; router [H, E]; w_gate, w_up [E_held, H, I] (w_gate None:
    a two-matrix expert, whose w_up is [E_held, I, H]); w_down [E_held, I,
    H]; live [...] bool (None: every row); ``routing``: the
    module docstring's ``n_group``, ``topk_group``, ``first_held``,
    ``scale``, ``router_bias``. Returns (out [..., H], stats [4]).
    Plain ``jnp``: each held expert runs over every row and is masked by the
    row's weight for it (zero where not picked or the row is dead)."""
    lead, h2, live = _prep(h, live)
    n_exp = w_up.shape[0]
    w, _, idx, _, stats = _route(h2, router, top_k, live, renormalize,
                                 n_exp, **routing)
    # [T, E + 1] weight of every expert for every row; column E is the bin
    dense = jnp.zeros((h2.shape[0], n_exp + 1), jnp.float32).at[
        jnp.arange(h2.shape[0])[:, None], idx].set(w)[:, :n_exp]

    def one_expert(acc, xs):
        wg, wu, wd, col = xs
        y = jnp.dot(relu2(jnp.dot(h2, wu.T)) if wg is None
                    else jax.nn.silu(jnp.dot(h2, wg)) * jnp.dot(h2, wu), wd)
        return acc + col[:, None] * y.astype(jnp.float32), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros(h2.shape, jnp.float32),
                          (w_gate, w_up, w_down, dense.T))
    return out.astype(h.dtype).reshape(lead + (h2.shape[1],)), stats


def _tiling(k, n):
    """(tm, tk, tn) of the Pallas grouped matmul: the whole contraction in
    one tile where it fits (a visit is then one grid step a column tile, and
    a decode step with three rows a group is bound by the weight stream, not
    by grid steps); the weight tile is at most 4 MiB in bf16. Of the tilings
    tried on the v5e this was the fastest at 24 live rows of 536 and at a
    256-row prefill (PERF.md, PR 26); megablox's default (128, 128, 128) is
    six times slower."""
    return PAIR_TILE, min(k, 2048), min(n, 1024)


def _grouped_matmul(xs, w, counts, layer=None, transposed=False):
    """xs [P, K] rows ordered by group; w [E, K, N] (``transposed``: [E, N,
    K]); counts [E] rows a group. Rows past ``sum(counts)`` come back
    undefined (the caller masks them). With ``layer`` (a traced index), ``w``
    is the stack ``[L, E, ...]`` of a layer scan and the call reads layer
    ``layer`` of it IN PLACE:
    the stack is viewed as ``L x E`` groups of which only this layer's
    hold rows, and the kernel never visits an empty group. A Mosaic call
    needs its operands whole, so slicing the layer out first costs a copy
    of all ``E`` experts a layer call, read or not (20.7 ms of an 84 ms
    OLMoE decode step on the v5e; PERF.md, PR 26)."""
    if _interpret_mode():
        if layer is not None:
            w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
        return jax.lax.ragged_dot(
            xs, jnp.swapaxes(w, 1, 2) if transposed else w, counts)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    if layer is not None:
        n_layers, n_exp = w.shape[:2]
        counts = jax.lax.dynamic_update_slice(
            jnp.zeros(n_layers * n_exp, counts.dtype), counts,
            (layer * n_exp,))
        w = w.reshape((n_layers * n_exp,) + w.shape[2:])
    k, n = w.shape[1:][::-1] if transposed else w.shape[1:]
    return gmm(xs, w, counts, xs.dtype, _tiling(k, n),
               transpose_rhs=transposed)


def moe_ffn(h, router, w_gate, w_up, w_down, *, top_k, live=None,
            renormalize=False, return_picks=False, layer=None, **routing):
    """Same contract as :func:`moe_ffn_reference`; the path the serving
    programs run. Scopes: ``moe`` > ``moe_route`` (router, top-k, ordering,
    gather, weighted sum) and ``moe_experts`` (the grouped matmuls). With
    ``return_picks`` a third value: the experts each row picked, by the
    router's ids, ``[..., top_k]`` int32 (the router's width for a dead
    row), held or not, for a check of the routing against a reference. With
    ``layer`` (a traced index) the three expert weights are stacks over
    layers ``[L, E_held, ...]``, read in place (``_grouped_matmul``)."""
    lead, h2, live = _prep(h, live)
    rows, hid = h2.shape
    with jax.named_scope("moe"):
        with jax.named_scope("moe_route"):
            w, picks, idx, counts, stats = _route(
                h2, router, top_k, live, renormalize,
                w_up.shape[0 if layer is None else 1], **routing)
            pairs = rows * top_k
            slots = -(-pairs // PAIR_TILE) * PAIR_TILE
            # pair slots ordered by held expert; dead pairs and picks of an
            # expert held elsewhere (the sentinel id) last
            order = jnp.argsort(idx.reshape(-1), stable=True)
            order = jnp.pad(order, (0, slots - pairs))
            xs = jnp.take(h2, order // top_k, axis=0)
        with jax.named_scope("moe_experts"):
            if w_gate is None:
                mid = relu2(_grouped_matmul(xs, w_up, counts, layer,
                                            transposed=True))
            else:
                g = _grouped_matmul(xs, w_gate, counts, layer)
                u = _grouped_matmul(xs, w_up, counts, layer)
                mid = jax.nn.silu(g) * u
            y = _grouped_matmul(mid.astype(h2.dtype), w_down, counts, layer)
        with jax.named_scope("moe_route"):
            # back to (row, pick) order; slots past the live pairs hold
            # whatever the grouped matmul left there, so select, not scale
            slot_of = jnp.argsort(order[:pairs])
            y = jnp.take(y, slot_of, axis=0).reshape(rows, top_k, hid)
            y = jnp.where((idx < counts.shape[0])[:, :, None],
                          y.astype(jnp.float32), 0.0)
            out = jnp.sum(y * w[:, :, None], axis=1)
    out = out.astype(h.dtype).reshape(lead + (hid,))
    if return_picks:
        return out, stats, picks.reshape(lead + (top_k,))
    return out, stats
