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
back to row order. Shapes are static, so one program serves every routing;
cost follows the live pairs and the experts they touch: dead rows (the padding
of a packed buffer or of a prefill bucket) make no pair, and an expert nobody
picked is not read. The buffer of pair slots is ``rows x top_k`` where every
expert is held. Under a held range it is sized for the picks that land on a
held expert (``_capacity``: twice an even router's share, a function of the
shapes alone) and the sentinel pairs get no slot; a call whose held pairs
overflow it takes the buffer in as many passes as they need
(``lax.while_loop``; one pass in every step an even router makes): dropless
and exact whatever the routing, one body a program, and ``STATS``' last entry
says whether a call fitted one pass. The way back from the slots to the rows
is a float32 product over the slots while the buffer is small
(``PRODUCT_SLOTS``: its cost is rows x slots, so rows squared) and the gather
by pair above that (linear in the rows: a wide chunk step, the whole-prompt
programs). Either keeps a row's output that is not finite in that row. Which
forms of the ordering and of the way back are cheaper on the v5e, and where
they cross, is ``scripts/bench_moe_route.py``'s to say (PERF.md, PR 57).

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
#: least one, the fullest one's, the picks the live rows made over the
#: router's whole width (``pairs`` again when every expert is held), and 1
#: where the call ran ONE pass on a buffer of fewer than ``rows x top_k``
#: pair slots (``_capacity``: a held range whose pairs fit it), else 0
STATS = ("pairs", "experts_touched", "max_expert_pairs", "picks",
         "compact_calls")


#: the largest buffer of a held range (``_capacity`` slots) whose way back to
#: row order is the product over the slots; a larger one goes back by the
#: gather by pair. The product costs rows x slots and the gather rows alone:
#: on the v5e the product is ahead by 18 us a call at 384 slots, level at
#: 896-1,024, behind by 33 at 1,664 and four times the gather's whole call
#: at a whole-prompt program's 20,480 (PERF.md, PR 57)
PRODUCT_SLOTS = 1024


def _tiles(n):
    return -(-n // PAIR_TILE) * PAIR_TILE


def _capacity(pairs, n_held, n_exp):
    """Pair slots of the buffer a layer call builds for the picks that land
    on a held expert, of ``pairs`` (row, pick) pairs under ``n_held`` of the
    router's ``n_exp`` experts: twice what an even router lands there, in
    whole tiles. None where that is no fewer than a slot a pair takes (every
    expert held; a step of a few rows): there is one buffer then."""
    cap = _tiles(-(-2 * pairs * n_held // n_exp))
    return cap if cap < _tiles(pairs) else None


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
    [n_held] i32 of live pairs per held expert, stats [5] i32: ``STATS``)."""
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
    held = jnp.sum(counts)
    cap = _capacity(loc.size, n_held, n_exp)
    stats = jnp.stack([held, jnp.sum(counts > 0), jnp.max(counts),
                       top_k * jnp.sum(live),
                       0 if cap is None else held <= cap]).astype(jnp.int32)
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
    ``scale``, ``router_bias``. Returns (out [..., H], stats [5]).
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
    pairs = rows * top_k
    n_held = w_up.shape[0 if layer is None else 1]
    cap = _capacity(pairs, n_held, router.shape[-1])

    def experts(xs, counts):
        with jax.named_scope("moe_experts"):
            if w_gate is None:
                mid = relu2(_grouped_matmul(xs, w_up, counts, layer,
                                            transposed=True))
            else:
                g = _grouped_matmul(xs, w_gate, counts, layer)
                u = _grouped_matmul(xs, w_up, counts, layer)
                mid = jax.nn.silu(g) * u
            return _grouped_matmul(mid.astype(h2.dtype), w_down, counts,
                                   layer)

    def every_pick(w, idx, counts):
        """A slot for every (row, pick) pair."""
        with jax.named_scope("moe_route"):
            # pair slots ordered by held expert; dead pairs and picks of an
            # expert held elsewhere (the sentinel id) last
            order = jnp.argsort(idx.reshape(-1), stable=True)
            order = jnp.pad(order, (0, _tiles(pairs) - pairs))
            xs = jnp.take(h2, order // top_k, axis=0)
        y = experts(xs, counts)
        with jax.named_scope("moe_route"):
            # back to (row, pick) order; slots past the live pairs hold
            # whatever the grouped matmul left there, so select, not scale
            slot_of = jnp.argsort(order[:pairs])
            y = jnp.take(y, slot_of, axis=0).reshape(rows, top_k, hid)
            y = jnp.where((idx < n_held)[:, :, None],
                          y.astype(jnp.float32), 0.0)
            return jnp.sum(y * w[:, :, None], axis=1)

    def held_pairs(w, idx, counts):
        """``cap`` slots a pass over the pairs on held experts, in expert
        order: the sentinel pairs sort past them and get none. ONE pass
        wherever they fit ``cap`` (every step the benchmark serves); a call
        whose share runs hotter takes ``ceil(held / cap)``, each with the
        counts of its window of the order: dropless and exact whatever the
        routing, on one body a program. Up to ``PRODUCT_SLOTS`` a row's sum
        is a float32 product over the slots, ``[rows, cap]`` weights (a
        slot's under its row, 0 elsewhere) times ``y``: no slot is looked up
        by pair, so no second sort and no running sum (130 us a call on the
        v5e at 1,280 pairs). The weights go in at ``HIGHEST`` (three bf16
        terms that add up to the float32 exactly); a bf16 ``y`` IS one such
        term, so it goes in at the default and the product costs three
        passes, not six. A slot whose ``y`` is not finite goes in as 0 under
        a weight of NaN: 0 x inf would hand it to every row of the step, and
        so it stays in its own. Above ``PRODUCT_SLOTS`` (a wide chunk step,
        a whole-prompt program's thousands of rows) the product's rows x
        ``cap`` loses to a second sort, which inverts the first, and each
        pair fetching its slot of the pass."""
        by_product = cap <= PRODUCT_SLOTS
        with jax.named_scope("moe_route"):
            order = jnp.argsort(idx.reshape(-1), stable=True)
            slot_of = None if by_product \
                else jnp.argsort(order).reshape(rows, top_k)
            order = jnp.pad(order, (0, cap))
            ends = jnp.cumsum(counts)
            held = ends[-1]

        def product(y, lo, window, row_of):
            used = jnp.arange(cap) < held - lo
            sound = used & jnp.all(jnp.isfinite(y), axis=1)
            ws = jnp.where(used, jnp.where(
                sound, jnp.take(w.reshape(-1), window), jnp.nan), 0.0)
            by_row = jnp.where(
                row_of[None, :] == jnp.arange(rows)[:, None],
                ws[None, :], 0.0)
            exact = jax.lax.Precision.HIGHEST
            return jnp.dot(
                by_row, jnp.where(sound[:, None], y.astype(jnp.float32), 0.0),
                precision=(exact, jax.lax.Precision.DEFAULT
                           if y.dtype == jnp.bfloat16 else exact))

        def gather(y, lo):
            # pick-major: ``[top_k, rows, hid]`` is the gather's own layout,
            # where ``[rows, top_k, hid]`` pads ``top_k`` to a sublane tile
            # and is copied into it
            at = slot_of.T - lo
            here = (idx.T < n_held) & (at >= 0) & (at < cap)
            y = jnp.take(y, jnp.clip(at, 0, cap - 1).reshape(-1),
                         axis=0).reshape(top_k, rows, hid)
            y = jnp.where(here[:, :, None], y.astype(jnp.float32), 0.0)
            return jnp.sum(y * w.T[:, :, None], axis=0)

        def one_pass(carry):
            lo, out = carry
            with jax.named_scope("moe_route"):
                window = jax.lax.dynamic_slice(order, (lo,), (cap,))
                row_of = window // top_k
                xs = jnp.take(h2, row_of, axis=0)
                in_pass = jnp.clip(ends - lo, 0, cap) \
                    - jnp.clip(ends - counts - lo, 0, cap)
            y = experts(xs, in_pass)
            with jax.named_scope("moe_route"):
                return lo + cap, out + (product(y, lo, window, row_of)
                                        if by_product else gather(y, lo))

        return jax.lax.while_loop(
            lambda carry: carry[0] < held, one_pass,
            (jnp.int32(0), jnp.zeros((rows, hid), jnp.float32)))[1]

    with jax.named_scope("moe"):
        with jax.named_scope("moe_route"):
            w, picks, idx, counts, stats = _route(
                h2, router, top_k, live, renormalize, n_held, **routing)
        out = (every_pick if cap is None else held_pairs)(w, idx, counts)
    out = out.astype(h.dtype).reshape(lead + (hid,))
    if return_picks:
        return out, stats, picks.reshape(lead + (top_k,))
    return out, stats
