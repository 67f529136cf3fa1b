"""Pallas TPU ragged paged attention over a LATENT cache (multi-head latent
attention, DeepSeek-V2), in the absorbed form.

The cache holds one row a token a layer: the normalised latent ``c_kv``
(``rank`` values), then the rotated shared key ``k_pe`` (``rope`` values), then
zeros up to whole lanes: ``[L, num_blocks, bs, W]``, no head axis and no V
side. With ``W_kvb`` split by head into ``W_UK`` and ``W_UV`` the caller folds
``W_UK`` into the query (``q_lat = q_nope W_UK^T``) and ``W_UV`` into the
output, and this kernel computes, for every head of a token alike,

    score = (q_lat . c_kv + q_pe . k_pe) * scale      o_lat = softmax . c_kv

so a fetched block serves all heads at once: the wide query ``[q_lat | q_pe |
0]`` is one row a (token, head), the key is the stored row as it lies, and the
value is its first ``rank`` lanes. That is ``pallas_ragged_attention``'s
wide-query layout with one KV head, and the iteration space is that kernel's
own: the same work list of (query block, row) pairs (``_work_list``), the same
bound on a pair's walk (the row's length and the causal diagonal,
``_pair_kv_blocks``), the same host counter (``ragged_grid_counts``), packed
spans of length 1 (decode rows) and n (prefill chunks) alike, float32 softmax
state, rows outside every span exact zeros.

What differs: the pool is ONE buffer, left in HBM whole; a loop iteration
computes one online-softmax update over a group of ``pages`` consecutive
table entries (``pages * bs`` keys, one DMA an entry). A 32-token block alone
is 40 KB: one block an iteration is bound by the DMA's latency, not by its
bytes (PERF.md, PR 25: 1.3 us a block). Entries past the pair's last block
clamp to the table's last entry: a harmless read, masked off.

A pair walks its groups on ``_walk_ahead``'s pipeline, which
``kernels.dsa``'s index-scores kernel shares: ``SLOTS - 1`` groups in flight
ahead of the one that computes, all of a group's copies on its slot's ONE
semaphore and one byte-counting wait a group, and a steady loop without a
branch that computes BEFORE it starts the copies ``SLOTS - 1`` groups ahead,
so the scalar work of their addresses packs under the update's vector work
and HBM stays busy across the softmax between the update's two matmuls
(PERF.md, PR 44 and PR 45). The walk still drains at a pair's end: a pair's
first groups are started with nothing of the pair before it to overlap them
(``pallas_ragged_attention`` hands its first group over: ROADMAP S15 (e)).

``mla_ragged_attention_reference`` is the oracle in the EXPANDED form: it
gathers the latent rows through the tables, up-projects them to per-head
keys and values with ``W_kvb`` and attends plainly, so a test of the kernel
against it is also a test of the absorption.

Inference-only (no VJP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_flash import _cparams, _interpret_mode
from .pallas_ragged_attention import (NEG_INF, _one_token_walk, _query_block,
                                      _work_list)

#: table entries one loop iteration fetches and computes on together, and
#: the query block's wide rows
PAGES = 16
BLOCK_Q = 256
#: slots of the walk's pipeline (``_walk_ahead``): a slot is one group of
#: latent blocks, 655 KB at the published widths where the index kernel's is
#: 128 KB. The smallest within 2 % of the best (PERF.md, PR 45, ms a call in
#: a loop of one program on the v5e: 16 decode rows of 64 heads at 13.4k,
#: 0.488 at 3 slots, 0.464 at 4, 0.464 at 6, 0.467 at 8; 32 rows of 128
#: heads at 4.7k, 0.515 / 0.508 / 0.508 / 0.513; the copies alone 0.393 and
#: 0.306, the update alone 0.354 and 0.417)
SLOTS = 4


def latent_row_width(rank, rope):
    """Lanes of a stored row: ``rank + rope`` values padded to whole lanes
    (Mosaic refuses a DMA window whose minor dim is not; 576 -> 640)."""
    return -(-(int(rank) + int(rope)) // 128) * 128


def _walk_ahead(n_groups, start, wait, body, slots):
    """Run ``body(gi, slot)`` over a pair's groups, ``slots - 1`` groups in
    flight ahead of the one that computes (the pair's first ``slots - 1``
    started already: ``_start_ahead``). While a group that far ahead exists
    the loop's body has no branch and computes BEFORE it starts that group:
    the next copies' addresses (table lookups, scalar work) then pack under
    the vector work, where a conditional start ahead of the wait (this
    module's walk before PR 45) runs them in turn. The pair's last
    ``slots - 1`` groups only wait and compute."""
    ahead = slots - 1

    def _tail(gi, carry):
        wait(gi % slots)
        body(gi, gi % slots)
        return carry

    def _steady(gi, carry):
        _tail(gi, carry)
        start(gi + ahead, (gi + ahead) % slots)
        return carry

    steady = jnp.maximum(n_groups - ahead, 0)
    jax.lax.fori_loop(0, steady, _steady, 0)
    jax.lax.fori_loop(steady, n_groups, _tail, 0)


def _start_ahead(n_groups, start, slots):
    """Start a pair's first ``slots - 1`` groups, each into the slot of its
    own number (a loop, not ``slots - 1`` copies of the group's starts: the
    step programs trace and lower these kernels a dozen times, and set-up
    pays for every copy's descriptor)."""
    def _first(g, carry):
        start(g, g)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(slots - 1, n_groups), _first, 0)


def _mla_kernel(wq_ref, wr_ref, wf_ref, wn_ref, qs_ref, ql_ref, kl_ref,
                tbl_ref, layer_ref, q_ref, *refs, selected, scale, block_k,
                pages, tq, gh, num_blocks, table_entries, rank):
    # with a selection (``kernels.dsa``) its tiles come before the pool: a
    # key is then valid where the walk's own rule says so AND the query's
    # tile holds 0 for it
    b_ref = refs[0] if selected else None
    pool_hbm, o_ref, buf, sems, m_scr, l_scr, acc_scr = refs[selected:]
    w = pl.program_id(0)            # one work-list entry: (query block, row)
    qi = wq_ref[w]
    r = wr_ref[w]
    nkb = wn_ref[w]                 # pool blocks this pair walks (0 = dead)
    layer = layer_ref[0]
    qstart = qs_ref[r]
    qlen = ql_ref[r]
    kvlen = kl_ref[r]
    row0 = qi * tq
    span_lo = qstart * gh
    span_hi = (qstart + qlen) * gh
    group = pages * block_k         # keys of one iteration
    n_groups = (nkb + pages - 1) // pages
    slots = buf.shape[0]

    @pl.when(wf_ref[w] == 1)
    def _zero_out():
        o_ref[:] = jnp.zeros_like(o_ref)

    def start(gi, slot):
        # one group of ``pages`` table entries of row ``r``, resolved from
        # SMEM at issue time, every copy on the slot's ONE semaphore;
        # entries past the table clamp to its last, sentinels into the
        # layer's own blocks (masked by ``_update`` either way)
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

    _start_ahead(n_groups, start, slots)        # (none for a dead pair)

    def _walk(nr, load_q, valid_of, write, picked_of):
        # one pair's walk on ``nr`` wide rows (static), the softmax state in
        # the first ``nr`` rows of the scratch
        m_ref, l_ref, acc_ref = (r.at[pl.ds(0, nr)]
                                 for r in (m_scr, l_scr, acc_scr))
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

        def _update(gi, slot):
            q = load_q()                        # [nr, W]: q_lat | q_pe | 0
            k = buf[slot]                       # [group, W]: c_kv | k_pe | 0
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            valid = valid_of(gi * group + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1), s.shape)
            if selected:
                valid = valid & (picked_of(b_ref[0, gi]) >= 0.0)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            # the value is the latent part of the same rows. Rows past kvlen
            # may hold another sequence's (or a clamped entry's) values, and
            # 0 * NaN is NaN: zero them. In every group, not under a
            # ``lax.cond`` in the one that can have any: the select rides on
            # the operand's load, where the cond's result, ``[group, rank]``,
            # went through VMEM every group (PERF.md, PR 45: 0.37 us a group)
            v = k[:, :rank]
            v = jnp.where(gi * group + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) < kvlen, v, jnp.zeros_like(v))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:] = jnp.broadcast_to(
                alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
                l_ref.shape)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

        _walk_ahead(n_groups, start, wait, _update, slots)
        write(acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30))

    # a span of ONE token (a decode row) is ``gh`` wide rows at a multiple
    # of ``gh`` inside the query block: it computes on those rows alone,
    # not on the block's other tokens, which belong to other rows (where
    # ``gh`` rows are whole tiles; else every span takes the general walk)
    one_token_walk = _one_token_walk(gh, tq)
    alone = (qlen == 1) if one_token_walk else False

    if one_token_walk:
        @pl.when((nkb > 0) & alone)
        def _one_token():
            off = pl.multiple_of(span_lo - row0, gh)

            def picked_of(tile):
                # the token's row of the selection's tile, for all its heads
                mine = jax.lax.broadcasted_iota(
                    jnp.int32, tile.shape, 0) == off // gh
                row = jnp.sum(jnp.where(mine, tile, 0.0), axis=0,
                              keepdims=True)
                return jnp.broadcast_to(row, (gh, tile.shape[1]))

            def write(out):
                o_ref[pl.ds(off, gh), :] = out.astype(o_ref.dtype)

            _walk(gh, lambda: q_ref[pl.ds(off, gh), :],
                  lambda cols, shape: cols < kvlen, write, picked_of)

    @pl.when((nkb > 0) & jnp.logical_not(alone))
    def _span():
        def picked_of(tile):
            # token i of the block on its gh wide rows
            return jnp.concatenate(
                [jnp.broadcast_to(tile[i:i + 1], (gh, tile.shape[1]))
                 for i in range(tq // gh)], axis=0)

        def valid_of(cols, shape):
            # causal within the span: wide row w is span token (w - span_lo)
            # // gh, at logical position kvlen - qlen + that index
            wrow = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            pos = kvlen - qlen + (wrow - span_lo) // gh
            return (wrow >= span_lo) & (wrow < span_hi) & (cols <= pos)

        def write(out):
            # ONLY this row's span: the output block is shared by every
            # sequence whose span intersects it
            wrow = row0 + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
            o_ref[:] = jnp.where((wrow >= span_lo) & (wrow < span_hi),
                                 out.astype(o_ref.dtype), o_ref[:])

        _walk(tq, lambda: q_ref[:], valid_of, write, picked_of)


def _mla_call(q_wide, pool, layer, tables, qstart, qlen, kvlen, scale, gh,
              block_q, rank, pages, interpret, selection=None):
    """q_wide ``[TH_pad, W]``; pool ``[L, num_blocks, bs, W]`` left in HBM;
    ``selection`` ``[nq, n_grp, 8, group]`` tiles or None; returns ``[TH_pad,
    rank]``."""
    TH, W = q_wide.shape
    num_blocks, bs = pool.shape[1], pool.shape[2]
    R, nk = tables.shape
    nq = TH // block_q
    work = _work_list(qstart, qlen, kvlen, nq=nq,
                      tokens_per_block=block_q // gh, block_size=bs,
                      table_entries=nk)
    selected = selection is not None
    kernel = functools.partial(
        _mla_kernel, selected=selected, scale=scale, block_k=bs, pages=pages,
        tq=block_q, gh=gh, num_blocks=num_blocks, table_entries=nk, rank=rank)

    def _q_index(w, wq, *_):
        return (wq[w], 0)

    def _tile_index(w, wq, *_):
        return (wq[w], 0, 0, 0)

    tiles = [pl.BlockSpec((1,) + selection.shape[1:], _tile_index)] \
        if selected else []
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(nq + R,),
            in_specs=[pl.BlockSpec((block_q, W), _q_index)] + tiles
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_q, rank), _q_index),
            scratch_shapes=[
                pltpu.VMEM((SLOTS, pages * bs, W), pool.dtype),
                pltpu.SemaphoreType.DMA((SLOTS,)),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((TH, rank), q_wide.dtype),
        # consecutive entries revisit one output block: no reordering
        compiler_params=_cparams(("arbitrary",)),
        interpret=interpret,
        name="dsa_attention" if selected else "mla_ragged_attention",
    )(*work, qstart, qlen, kvlen, tables, layer, q_wide,
      *([selection] if selected else []), pool)


def _spans(tables, qstart, qlen, kvlen):
    qstart = jnp.asarray(qstart, jnp.int32).reshape(-1)
    return (jnp.asarray(tables, jnp.int32).reshape(qstart.shape[0], -1),
            qstart, jnp.asarray(qlen, jnp.int32).reshape(-1),
            jnp.asarray(kvlen, jnp.int32).reshape(-1))


def grid_params(table_entries, heads, packed_tokens, block_q=BLOCK_Q,
                pages=PAGES):
    """The tiling of one call, ``{"block_q", "pages", "one_token"}`` (as
    ``pallas_ragged_attention.grid_params``: the one derivation the call and
    the engine's ``ragged_grid_counts`` share)."""
    block_q = _query_block(block_q, heads, packed_tokens)
    return {"block_q": block_q,
            "pages": max(1, min(int(pages), int(table_entries))),
            "one_token": _one_token_walk(heads, block_q)}


def mla_ragged_attention_pallas(q_lat, q_pe, pool, tables, qstart, qlen,
                                kvlen, *, scale, layer=0, block_q=BLOCK_Q,
                                pages=PAGES, selection=None):
    """Absorbed-form attention of packed query spans over the latent pool.

    q_lat:  [T, H, rank]  — ``q_nope W_UK^T`` of every (token, head)
    q_pe:   [T, H, rope]  — the rotated rope part of the query
    pool:   [L, num_blocks, bs, W] — the stored latent pool (module docstring)
    tables, qstart, qlen, kvlen: as ``ragged_paged_attention_pallas``
    scale:  the softmax scale (the model's: head width and YaRN's mscale)
    layer:  the layer of the pool to read (a traced index in a layer scan)
    selection: None, or the queries' selected sets as ``kernels.dsa.
            selection_bias`` lays them out: a key outside its query's set
            scores ``-inf`` (the kernel is then named ``dsa_attention``)
    returns [T, H, rank]: ``softmax . c_kv``, to be multiplied by ``W_UV``;
    packed rows outside every span are exact zeros.
    """
    T, H, rank = q_lat.shape
    W = pool.shape[-1]
    tables, qstart, qlen, kvlen = _spans(tables, qstart, qlen, kvlen)
    q_wide = jnp.concatenate(
        [q_lat, q_pe,
         jnp.zeros((T, H, W - rank - q_pe.shape[-1]), q_lat.dtype)],
        axis=-1).reshape(T * H, W)
    tiling = grid_params(tables.shape[1], H, T, block_q, pages)
    bq = tiling["block_q"]
    th_pad = -(-(T * H) // bq) * bq
    if th_pad != T * H:
        q_wide = jnp.pad(q_wide, ((0, th_pad - T * H), (0, 0)))
    out = _mla_call(q_wide, pool, jnp.asarray(layer, jnp.int32).reshape(1),
                    tables, qstart, qlen, kvlen, float(scale), H, bq, rank,
                    tiling["pages"], _interpret_mode(), selection)
    return out[:T * H].reshape(T, H, rank)


def mla_ragged_attention_reference(q_nope, q_pe, w_kvb, pool, tables, qstart,
                                   qlen, kvlen, *, scale, layer=0):
    """jnp oracle, EXPANDED form, same span semantics.

    q_nope: [T, H, nope]; q_pe: [T, H, rope] (rotated); w_kvb: [rank, H *
    (nope + v)], a head's columns its ``k_nope`` then its ``v``; pool as the
    kernel's. Each sequence's latent rows are gathered through its table,
    up-projected to per-head keys ``[k_nope | k_pe]`` and values, and
    attended causally within the span. Returns the per-head outputs
    ``[T, H, v]`` (``W_UV`` already applied)."""
    T, H, nope = q_nope.shape
    rope = q_pe.shape[-1]
    rank = w_kvb.shape[0]
    tables, qstart, qlen, kvlen = _spans(tables, qstart, qlen, kvlen)
    R, mb = tables.shape
    bs = pool.shape[2]
    s_tot = mb * bs
    rows = jnp.asarray(pool).at[layer, tables].get(mode="clip")
    rows = rows.reshape(R, s_tot, -1)
    kv = jnp.einsum("rsc,cd->rsd", rows[..., :rank], w_kvb).reshape(
        R, s_tot, H, -1)
    k_pe = jnp.broadcast_to(rows[:, :, None, rank:rank + rope],
                            (R, s_tot, H, rope))
    k_rows = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    v_rows = kv[..., nope:]
    t_idx = jnp.arange(T, dtype=jnp.int32)
    in_r = (t_idx[None, :] >= qstart[:, None]) \
        & (t_idx[None, :] < (qstart + qlen)[:, None])     # [R, T]
    live = jnp.any(in_r, axis=0)
    seg = jnp.argmax(in_r, axis=0).astype(jnp.int32)
    k = jnp.take(k_rows, seg, axis=0)                     # [T, s_tot, H, .]
    v = jnp.take(v_rows, seg, axis=0)
    pos = (jnp.take(kvlen, seg) - jnp.take(qlen, seg)
           + (t_idx - jnp.take(qstart, seg)))
    cols = jnp.arange(s_tot, dtype=jnp.int32)
    mask = (cols[None, :] <= pos[:, None]) & live[:, None]
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    logits = jnp.einsum("qhd,qkhd->qhk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    probs = jnp.where(mask[:, None, :], jax.nn.softmax(logits, axis=-1), 0.0)
    row_valid = cols[None, :] < jnp.take(kvlen, seg)[:, None]
    v = jnp.where(row_valid[:, :, None, None], v, 0.0)
    out = jnp.einsum("qhk,qkhd->qhd", probs.astype(q.dtype), v)
    return jnp.where(live[:, None, None], out, jnp.zeros_like(out))
