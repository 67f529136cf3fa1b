"""Learned sparse attention over a latent cache (DeepSeek Sparse Attention:
a lightning indexer, a top-k selection of key positions, latent attention
over the selected rows only), for the serving step programs.

Three steps a layer that HAS an indexer, the last alone a layer that borrows
the selection of the layer before it:

- **index scores** (``dsa_index_scores_pallas``, kernel ``dsa_index_scores``):
  for a packed query token ``t`` of a row, over the row's cached index keys
  ``s <= t``, ``I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])`` in float32,
  no softmax. The keys are a SECOND paged cache, ``[L_full, num_blocks, bs,
  D]``, one ``D``-wide key a token for each layer with an indexer, addressed
  by the latent pool's block tables. The kernel walks
  ``pallas_mla_ragged_attention``'s work list of (query block, row) pairs,
  ``pages`` table entries a DMA group, on wide rows of one (token, index
  head) each, on that module's pipeline (``_walk_ahead``, which the latent
  kernel walks on too since PR 45: several groups in flight, one wait a
  group, a loop without a branch that computes before it starts the next
  copies) with a slot count of its own, ``SLOTS``: a block of index keys is
  8 KB where a latent block is 40 KB, so this walk is bound by the issue of
  its copies and not by their bytes; under the two-slot walk with a
  conditional start ahead of the wait that both kernels had before, a decode
  row's compute and the next group's address arithmetic ran in turn
  (PERF.md, PR 44).

  A span of several tokens (a chunk, a verify span) scores the query
  block's ``tq`` wide rows against a group and sums the heads by one small
  matmul against a matrix that holds a token's weights on its own rows. A
  span of ONE token (a decode row) owns ``heads`` of those rows and one
  output row: where ``heads`` are whole row tiles (``_one_token_walk``, the
  latent kernel's rule; 16 or 32 heads, not 4) it scores its own rows alone
  and sums its heads on the VPU, the weights along sublanes. The block-wide
  sum is a ``Precision.HIGHEST`` matmul whose ``[tq, group]`` float32
  operand changes every group: for a decode row 7/8 of it and of the scores
  belong to other rows' tokens and are masked at the store (PERF.md,
  PR 44). The kernel chooses by what it sees, ``qlen[r] == 1``;
  ``index_grid_params`` is the tiling the call and the engine's
  ``index_one_token_rows`` share.
- **the selection** (``dsa_select``): the ``k`` largest ``I[t, :]`` a query as
  a SET, by a search for the k-th value (32 counting passes over the scores'
  bit patterns; ``jax.lax.top_k`` at k = 2048 over 20k is a sort on the TPU),
  ties at the threshold taken lowest position first, which is
  ``jax.lax.top_k``'s rule (``dsa_select_reference``).
- **attention over the selection** (``dsa_attention_pallas``, kernel
  ``dsa_attention``): ``pallas_mla_ragged_attention``'s own kernel, handed
  the selection as a mask: a pair walks the row's blocks up to the diagonal
  as before and a key outside the query's set scores ``-inf`` (*walk and
  mask*). The other way, gathering each query's selected rows through the
  tables and attending them densely, took twice the masked walk's time in
  ``jax.numpy`` on the v5e and is not kept here (``scripts/bench_dsa.py``
  holds it for the measurement; PERF.md, PR 43).

A selection travels between the two kernels in the walk's own layout,
``[query blocks, key groups, 8, group]`` float32, 0 for a selected key and
``NEG_INF`` for any other (``selection_bias``): a (query block, key group)
tile is then a leading index in the kernel, never a lane offset.

``*_reference`` are the ``jax.numpy`` oracles; ``dsa_attention_reference`` is
in the EXPANDED form over gathered rows, so a test against it is a test of
the absorption, the mask and the walk at once. Inference-only (no VJP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_flash import _cparams, _interpret_mode
from .pallas_mla_ragged_attention import (PAGES, _spans, _start_ahead,
                                          _walk_ahead,
                                          mla_ragged_attention_pallas)
from .pallas_mla_ragged_attention import grid_params as _mla_grid_params
from .pallas_ragged_attention import (NEG_INF, _one_token_walk, _query_block,
                                      _work_list)

BLOCK_Q = 256
#: rows of a selection tile: a query block's tokens, padded to whole sublanes
TILE_ROWS = 8
#: lanes of a vreg: the one-token path's weights, a token a lane
_LANES = 128
#: slots of the index kernel's walk: a pool block of index keys is 8 KB
#: where the latent pool's is 40 KB, so a group's 16 DMAs are bound by their
#: issue and not by their bytes (PERF.md, PR 44: 16 decode rows at 12.9k on
#: the v5e, this walk written out in a scratch copy: 0.159 ms at 4 slots,
#: 0.152 at 8, 0.154 at 16; 0.228 under the latent walk's two)
SLOTS = 8


def _token_meta(T, qstart, qlen, kvlen):
    """(live [T], row [T], logical position [T]) of the packed tokens."""
    t = jnp.arange(T, dtype=jnp.int32)
    in_r = (t[None, :] >= qstart[:, None]) \
        & (t[None, :] < (qstart + qlen)[:, None])             # [R, T]
    seg = jnp.argmax(in_r, axis=0).astype(jnp.int32)
    pos = jnp.take(kvlen, seg) - jnp.take(qlen, seg) + t - jnp.take(qstart,
                                                                    seg)
    return jnp.any(in_r, axis=0), seg, pos


# ------------------------------------------------------------- index scores
def dsa_index_scores_reference(q_idx, w_idx, idx_pool, tables, qstart, qlen,
                               kvlen, *, layer=0):
    """jnp oracle. q_idx ``[T, Hi, D]``; w_idx ``[T, Hi]`` float32 (the
    per-token head weights, constants folded in); idx_pool ``[L_full,
    num_blocks, bs, D]``. Returns ``I [T, s_tot]`` float32: ``sum_j w[t, j]
    relu(q[t, j] . k[s])`` for ``s`` at most the token's position in its row,
    ``NEG_INF`` elsewhere and for a token outside every span."""
    T = q_idx.shape[0]
    tables, qstart, qlen, kvlen = _spans(tables, qstart, qlen, kvlen)
    R, mb = tables.shape
    keys = jnp.asarray(idx_pool).at[layer, tables].get(mode="clip")
    keys = keys.reshape(R, mb * idx_pool.shape[2], -1)
    live, seg, pos = _token_meta(T, qstart, qlen, kvlen)
    cols = jnp.arange(keys.shape[1], dtype=jnp.int32)
    valid = live[:, None] & (cols[None, :] <= pos[:, None])

    def one_token(args):
        q, w, r, ok = args
        # rows past the token's position may hold anything (NaN): drop them
        # before they meet a weight
        k = jnp.where(ok[:, None], jnp.take(keys, r, axis=0), 0)
        s = jnp.einsum("hd,sd->hs", q, k,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("h,hs->s", w, jnp.maximum(s, 0.0),
                          precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(one_token, (q_idx, w_idx.astype(jnp.float32), seg,
                                  valid))
    return jnp.where(valid, out, NEG_INF)


def index_grid_params(heads, packed_tokens, block_q=BLOCK_Q):
    """The tiling of one ``dsa_index_scores`` call, ``{"block_q",
    "one_token"}``: the query block in (token, index head) wide rows and
    whether a span of one token computes on its own ``heads`` rows
    (``_one_token_walk``, the latent kernel's rule). The ONE derivation: the
    call tiles with it and the engine counts ``index_one_token_rows`` with
    it."""
    block_q = _query_block(block_q, heads, packed_tokens)
    return {"block_q": block_q,
            "one_token": _one_token_walk(heads, block_q)}


def _index_kernel(wq_ref, wr_ref, wf_ref, wn_ref, qs_ref, ql_ref, kl_ref,
                  tbl_ref, layer_ref, q_ref, e_ref, *refs, one_token,
                  block_k, pages, tq, gh, num_blocks, table_entries):
    # with the one-token path the heads' weights come a second time, the
    # heads along sublanes (``wt_ref``), before the pool
    wt_ref = refs[0] if one_token else None
    pool_hbm, o_ref, buf, sems = refs[one_token:]
    w = pl.program_id(0)
    qi = wq_ref[w]
    r = wr_ref[w]
    nkb = wn_ref[w]
    layer = layer_ref[0]
    qstart, qlen, kvlen = qs_ref[r], ql_ref[r], kl_ref[r]
    tpb = tq // gh
    group = pages * block_k
    n_groups = (nkb + pages - 1) // pages

    @pl.when(wf_ref[w] == 1)
    def _blank():
        o_ref[:] = jnp.full_like(o_ref, NEG_INF)

    def start(gi, slot):
        # one group of ``pages`` table entries of row ``r``, resolved from
        # SMEM at issue time, every copy on the slot's ONE semaphore;
        # entries past the table clamp to its last, sentinels into the
        # layer's own blocks (masked below either way)
        for j in range(pages):
            entry = jnp.minimum(gi * pages + j, table_entries - 1)
            phys = jnp.clip(tbl_ref[r, entry], 0, num_blocks - 1)
            pltpu.make_async_copy(
                pool_hbm.at[layer, phys],
                buf.at[slot, pl.ds(j * block_k, block_k)],
                sems.at[slot]).start()

    def wait(slot):
        # a DMA semaphore counts bytes: one wait for the slot's whole
        # buffer takes the group's ``pages`` copies together
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sems.at[slot]).wait()

    slots = buf.shape[0]
    _start_ahead(n_groups, start, slots)        # (none for a dead pair)
    walk = functools.partial(_walk_ahead, n_groups, start, wait, slots=slots)

    # a span of ONE token (a decode row) owns ``gh`` of the block's wide rows
    # and one of its output rows: it scores those rows alone, and sums its
    # heads on the VPU (the weights along sublanes, a sublane reduce). The
    # block-wide sum below is a ``HIGHEST`` matmul over ``[tq, group]`` that
    # changes every group, 7/8 of it for other rows' tokens
    alone = (qlen == 1) if one_token else False

    if one_token:
        @pl.when((nkb > 0) & alone)
        def _one_token():
            tok = qstart - qi * tpb             # the token's output row
            off = pl.multiple_of(tok * gh, gh)
            wt = wt_ref[0]                      # [gh, lanes]: lane = token
            w_col = jnp.sum(
                jnp.where(jax.lax.broadcasted_iota(jnp.int32, wt.shape, 1)
                          == tok, wt, 0.0), axis=1, keepdims=True)

            def body(gi, slot):
                s = jax.lax.dot_general(
                    q_ref[pl.ds(off, gh), :], buf[slot],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)     # [gh, group]
                red = jnp.sum(jnp.maximum(s, 0.0) * w_col, axis=0,
                              keepdims=True)                # [1, group]
                tile = o_ref[0, gi]
                rows = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
                cols = gi * group + jax.lax.broadcasted_iota(
                    jnp.int32, tile.shape, 1)
                o_ref[0, gi] = jnp.where(
                    rows == tok,
                    jnp.where(cols < kvlen, red, NEG_INF), tile)

            walk(body)

    @pl.when((nkb > 0) & jnp.logical_not(alone))
    def _span():
        def body(gi, slot):
            s = jax.lax.dot_general(
                q_ref[:], buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [tq, group]
            # the heads' weighted sum: row ``tok`` of ``e`` holds the
            # token's weights on its own ``gh`` wide rows, zeros elsewhere
            red = jax.lax.dot_general(
                e_ref[0], jnp.maximum(s, 0.0), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)        # [rows, group]
            shape = red.shape
            tok = qi * tpb + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            cols = gi * group + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            mine = (tok >= qstart) & (tok < qstart + qlen) \
                & (jax.lax.broadcasted_iota(jnp.int32, shape, 0) < tpb)
            seen = cols <= kvlen - qlen + (tok - qstart)
            o_ref[0, gi] = jnp.where(
                mine, jnp.where(seen, red, NEG_INF), o_ref[0, gi])

        walk(body)


def _tiles_to_rows(tiles, tpb, T, s_tot):
    """``[nq, n_grp, 8, group]`` tiles -> ``[T, s_tot]`` rows."""
    nq, n_grp, _, group = tiles.shape
    rows = jnp.swapaxes(tiles[:, :, :tpb], 1, 2).reshape(nq * tpb,
                                                         n_grp * group)
    return rows[:T, :s_tot]


def _rows_to_tiles(rows, tpb, nq, n_grp, group, fill):
    """``[T, s_tot]`` rows -> ``[nq, n_grp, 8, group]`` tiles, ``fill``
    in the padding."""
    T, s_tot = rows.shape
    rows = jnp.pad(rows, ((0, nq * tpb - T), (0, n_grp * group - s_tot)),
                   constant_values=fill)
    tiles = jnp.swapaxes(rows.reshape(nq, tpb, n_grp, group), 1, 2)
    return jnp.pad(tiles, ((0, 0), (0, 0), (0, _tile_rows(tpb) - tpb),
                           (0, 0)), constant_values=fill)


def _tile_rows(tpb):
    return -(-tpb // TILE_ROWS) * TILE_ROWS


def dsa_index_scores_pallas(q_idx, w_idx, idx_pool, tables, qstart, qlen,
                            kvlen, *, layer=0, block_q=BLOCK_Q, pages=PAGES):
    """Index scores of the packed spans over the index-key pool; arguments
    and result as :func:`dsa_index_scores_reference`."""
    T, H, D = q_idx.shape
    num_blocks, bs = idx_pool.shape[1], idx_pool.shape[2]
    tables, qstart, qlen, kvlen = _spans(tables, qstart, qlen, kvlen)
    R, nk = tables.shape
    tiling = index_grid_params(H, T, block_q)
    tq, one_token = tiling["block_q"], tiling["one_token"]
    tpb = tq // H
    nq = -(-(T * H) // tq)
    pages = max(1, min(int(pages), nk))
    n_grp, group = -(-nk // pages), pages * bs
    rows = _tile_rows(tpb)
    q_wide = jnp.pad(q_idx.reshape(T * H, D), ((0, nq * tq - T * H), (0, 0)))
    w_pad = jnp.pad(w_idx.astype(jnp.float32), ((0, nq * tpb - T), (0, 0)))
    # e[qi, tok, wide row]: the token's weight for that row's head
    own = (jnp.arange(tq, dtype=jnp.int32)[None, :] // H
           == jnp.arange(rows, dtype=jnp.int32)[:, None])
    e = jnp.where(own[None], w_pad.reshape(nq, 1, tq), 0.0)  # [nq, rows, tq]
    # wt[qi, head, tok]: the same weights, a token's heads along sublanes
    # (the one-token path's; whole lanes, zeros past the block's tokens)
    wt = [jnp.pad(jnp.swapaxes(w_pad.reshape(nq, tpb, H), 1, 2),
                  ((0, 0), (0, 0), (0, _LANES - tpb)))] if one_token else []
    work = _work_list(qstart, qlen, kvlen, nq=nq, tokens_per_block=tpb,
                      block_size=bs, table_entries=nk)
    kernel = functools.partial(
        _index_kernel, one_token=one_token, block_k=bs, pages=pages, tq=tq,
        gh=H, num_blocks=num_blocks, table_entries=nk)

    def _q_index(w, wq, *_):
        return (wq[w], 0)

    def _t_index(w, wq, *_):
        return (wq[w], 0, 0)

    def _o_index(w, wq, *_):
        return (wq[w], 0, 0, 0)

    tiles = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(nq + R,),
            in_specs=[pl.BlockSpec((tq, D), _q_index),
                      pl.BlockSpec((1, rows, tq), _t_index)]
            + [pl.BlockSpec((1, H, _LANES), _t_index)] * one_token
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, n_grp, rows, group), _o_index),
            scratch_shapes=[
                pltpu.VMEM((SLOTS, group, D), idx_pool.dtype),
                pltpu.SemaphoreType.DMA((SLOTS,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nq, n_grp, rows, group),
                                       jnp.float32),
        compiler_params=_cparams(("arbitrary",)),
        interpret=_interpret_mode(),
        name="dsa_index_scores",
    )(*work, qstart, qlen, kvlen, tables,
      jnp.asarray(layer, jnp.int32).reshape(1), q_wide, e, *wt, idx_pool)
    return _tiles_to_rows(tiles, tpb, T, nk * bs)


# ---------------------------------------------------------------- selection
def _sortable(x):
    """float32 -> uint32 whose order is the floats' (``-0.0 < +0.0``)."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def dsa_select(scores, k):
    """The positions of the ``min(k, live)`` largest scores of each row as a
    boolean mask ``[T, S]``; ``live`` are the entries above ``NEG_INF``
    (:func:`dsa_index_scores_reference`'s mask). Ties at the k-th value go to
    the lowest positions, as ``jax.lax.top_k``. No sort: the k-th value's bit
    pattern is found a bit a pass, by counting."""
    live = scores > 0.5 * NEG_INF
    key = jnp.where(live, _sortable(scores), jnp.uint32(0))
    kk = jnp.minimum(jnp.sum(live, axis=-1, dtype=jnp.int32), int(k))

    def one_bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        n = jnp.sum(key >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= kk, cand, thr)

    thr = jax.lax.fori_loop(0, 32, one_bit,
                            jnp.zeros(scores.shape[:1], jnp.uint32))
    above = live & (key > thr[:, None])
    tie = live & (key == thr[:, None])
    need = kk - jnp.sum(above, axis=-1, dtype=jnp.int32)

    def lowest_first(_):
        rank = jnp.cumsum(tie.astype(jnp.int32), axis=-1)
        return above | (tie & (rank <= need[:, None]))

    # (every tie is taken unless two scores at the threshold are equal to
    # the bit: then, and only then, a row pays for the running count)
    return jax.lax.cond(
        jnp.any(jnp.sum(tie, axis=-1, dtype=jnp.int32) != need),
        lowest_first, lambda _: above | tie, None)


def dsa_select_reference(scores, k):
    """jnp oracle of :func:`dsa_select`: ``jax.lax.top_k``'s set."""
    live = scores > 0.5 * NEG_INF
    k = min(int(k), scores.shape[-1])
    _, idx = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), k)
    hit = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return hit & live


def selection_bias(mask, heads, block_q=BLOCK_Q, pages=PAGES, *,
                   table_entries, block_size):
    """A selection ``mask [T, s_tot]`` in the layout ``dsa_attention_pallas``
    reads for ``heads`` attention heads: ``[nq, n_grp, 8, group]`` float32, 0
    where selected, ``NEG_INF`` elsewhere and in the padding."""
    T = mask.shape[0]
    tiling = _mla_grid_params(table_entries, heads, T, block_q, pages)
    tq, pg = tiling["block_q"], tiling["pages"]
    return _rows_to_tiles(
        jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32), tq // heads,
        -(-(T * heads) // tq), -(-table_entries // pg), pg * block_size,
        NEG_INF)


# ---------------------------------------------------------------- attention
def dsa_attention_pallas(q_lat, q_pe, pool, tables, qstart, qlen, kvlen,
                         bias, *, scale, layer=0, block_q=BLOCK_Q,
                         pages=PAGES):
    """Absorbed-form attention of packed query spans over the rows of the
    latent pool that ``bias`` (:func:`selection_bias` of the queries' sets)
    selects: ``mla_ragged_attention_pallas``'s kernel and walk, a key outside
    its query's set masked; every other argument and the result as there."""
    return mla_ragged_attention_pallas(
        q_lat, q_pe, pool, tables, qstart, qlen, kvlen, scale=scale,
        layer=layer, block_q=block_q, pages=pages, selection=bias)


def _selected_rows(mask, k):
    """(positions [T, k] of a mask's set, lowest first; live [T, k])."""
    k = min(int(k), mask.shape[-1])
    order = jnp.argsort(~mask, axis=-1, stable=True)[:, :k].astype(jnp.int32)
    return order, jnp.take_along_axis(mask, order, axis=-1)


def _gather_selected(pool, layer, tables, qstart, qlen, kvlen, mask, k):
    """(rows ``[T, k, W]``, live ``[T, k]``): each packed token's at most
    ``k`` selected pool rows, gathered through its own row of the tables,
    lowest position first; zeros where a set holds fewer."""
    tables, qstart, qlen, kvlen = _spans(tables, qstart, qlen, kvlen)
    _, seg, _ = _token_meta(mask.shape[0], qstart, qlen, kvlen)
    idx, ok = _selected_rows(mask, k)
    bs = pool.shape[2]
    phys = jnp.take_along_axis(jnp.take(tables, seg, axis=0), idx // bs,
                               axis=1)
    rows = jnp.asarray(pool).at[layer, phys, idx % bs].get(mode="clip")
    return jnp.where(ok[..., None], rows, jnp.zeros_like(rows)), ok


def dsa_attention_reference(q_nope, q_pe, w_kvb, pool, tables, qstart, qlen,
                            kvlen, mask, *, scale, k, layer=0):
    """jnp oracle, EXPANDED form over gathered rows: each query's selected
    latent rows are gathered, up-projected to per-head keys ``[k_nope |
    k_pe]`` and values with ``W_kvb`` and attended plainly. q_nope ``[T, H,
    nope]``, q_pe ``[T, H, rope]``, mask ``[T, s_tot]``; returns ``[T, H,
    v]`` (``W_UV`` applied), zeros for a token with an empty set."""
    T, H, nope = q_nope.shape
    rope, rank = q_pe.shape[-1], w_kvb.shape[0]
    rows, ok = _gather_selected(pool, layer, tables, qstart, qlen, kvlen,
                                mask, k)
    kv = jnp.einsum("tkc,cd->tkd", rows[..., :rank], w_kvb).reshape(
        T, rows.shape[1], H, -1)
    keys = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            rows[:, :, None, rank:rank + rope],
            kv.shape[:3] + (rope,))], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    s = jnp.einsum("thd,tkhd->thk", q, keys,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jnp.where(ok[:, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("thk,tkhd->thd", p.astype(q.dtype), kv[..., nope:])
