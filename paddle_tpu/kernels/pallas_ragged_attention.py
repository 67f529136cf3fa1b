"""Pallas TPU ragged *prefill+decode* paged attention — one kernel that
processes a mixed batch of variable-length query spans through the
serving stack's block tables.

This is the full kernel shape of "Ragged Paged Attention: A
High-Performance and Flexible LLM Inference Kernel for TPU" (PAPERS.md):
where ``pallas_paged_decode.py`` handles exactly one query token per
sequence, this kernel takes a PACKED query buffer ``[T, H, D]`` holding
every sequence's span back to back — decode rows are spans of length 1,
chunked-prefill rows are spans of length n — plus per-sequence row
metadata ``(query_start, query_len, kv_len)`` scalar-prefetched
alongside the block tables. One invocation computes causal-within-span
attention for the whole mixed batch, which is what lets the serving
engine fuse its prefill-chunk and decode programs into a single device
call (``serving/decode.build_ragged_step_fn``). Speculative decode
rides the SAME span metadata (``serving/decode.build_spec_verify_fn``,
README "Speculative decoding"): a k-token draft verify is just a span
with ``qlen = k + 1`` — last sampled token plus the drafts — whose
per-position causal attention this kernel already prices at live spans
only; nothing kernel-side is speculation-specific.

Semantics per sequence ``r`` (dead rows carry ``query_len == 0``):

- its queries are packed rows ``query_start[r] .. query_start[r] +
  query_len[r]`` of ``q``;
- span token ``i`` sits at logical position
  ``kv_len[r] - query_len[r] + i`` of the sequence (``kv_len`` counts
  the KV valid AFTER this step's writes, so a decode row with cache
  length L passes ``kv_len = L + 1``) and attends causally over
  positions ``0 .. pos`` through ``tables[r]``;
- packed rows outside every span produce exact zeros.

Design points (the block-diagonal wide-query GQA trick, the table-indirect
fetch and the Mosaic-conservative 2D tiles are ``pallas_paged_decode.py``'s):

- **The iteration space is the step's live work**, built from the span
  metadata inside the program (``_work_list``; no host work, no extra
  argument). The grid is a *work list* of (query block, row) pairs: the
  packed wide-query array is tiled into fixed query blocks, the packed
  spans are disjoint and contiguous, so at most ``nq + R`` pairs
  intersect, ordered by query block then row. A query block that no span
  touches gets one entry that only zeroes its output; unused entries
  repeat the last block (no DMA, no compute). Nothing is visited for a
  (query block, row) pair that does not intersect, or for a dead row.
- **The KV walk ends where the pair's work ends**: inside a grid step a
  ``fori_loop`` runs over exactly the pair's KV blocks — up to the row's
  own ``kv_len`` AND the causal diagonal of the last span token inside
  the query block, so the early query blocks of a chunk never touch the
  blocks their mask would remove. The stored pool, every layer of it,
  stays in HBM where it lies (``pl.ANY``).
- **Several pool pages an online-softmax update**: a loop iteration takes a
  *group* of ``pages`` consecutive table entries (``pages_per_update``: 256
  keys' worth, fewer where a row is wide; 8 for Mistral, 4 for OLMoE). It
  resolves them from the scalar-prefetched table in SMEM and fetches each
  block, at ``(layer, table entry)``, by ``make_async_copy`` into one
  two-slot ``[2, pages * bs, KD]`` buffer a side, all copies in flight
  together and the next group streaming in while this one computes; then
  one ``dot_general`` for the scores of ``pages * bs`` keys, one mask, one
  ``m / l / acc`` update, one ``P V``. The float32 accumulator ``[block_q,
  KD]`` is rescaled and written once a group, not once a 32-row page (that
  was 1.3 us a page against 0.17 us of MXU work, PERF.md, PR 25 and 32).
  Entries past the pair's last block clamp to the table's last entry, and
  sentinel entries (``>= num_blocks``) into the layer's own blocks — a
  harmless read, masked off by ``kvlen`` and the causal rule; V rows past
  ``kvlen`` are zeroed in the one group that can hold any (a stale row may
  be NaN). So HBM traffic and MXU work scale with the live logical cache
  rounded up to a group a pair, and no layer of the pool is cut out or
  re-laid-out for the call. An int8 pool's per-row scale planes ride the
  same physical index as their data block and lie concatenated over the
  group; an fp8 pool's per-block scale becomes a factor a column.
- **A span of one token computes on its own rows**: a decode row is ``gh``
  wide rows at a multiple of ``gh`` inside the query block. Where those are
  whole tiles (``gh % 16 == 0``, fewer than the block: Mistral's 32,
  OLMoE's 16) the pair loads those rows of the query block alone, keeps its
  softmax state in the first ``gh`` rows of the scratch and writes those
  ``gh`` output rows: 1 / 16 of a 512-row block for Mistral, 1 / 16 of a
  256-row block for OLMoE. Every other span takes the general walk on the
  whole block (``pallas_mla_ragged_attention`` has the same two walks).
- **One output block, several rows**: visits to an output block are
  consecutive; the first zeroes it, each row's visit writes back only its
  own span (the one-token walk its ``gh`` rows, the general walk by a
  masked read-modify-write). MXU work on the masked remainder of an
  intersecting query block is the same idle-MXU trade the wide-query trick
  already makes. The last query block may reach past the packed buffer: the
  rows it holds there belong to no span, and the buffer is never padded.
- **2D-tile conservatism**: all blocks are 2D/leading-1 tiles whose
  last-two dims equal the full array dims; compute is plain 2D
  ``dot_general``; groups ascending, the per-row state in VMEM scratch
  exactly like the decode kernels. At ``pages=1`` (an argument of the Python
  entry, for tests) a span-1 row reproduces ``paged_decode_attention_pallas``'s
  accumulation order bit for bit; at the derived ``pages`` it is the same
  mathematics in another order, equal within float32 rounding.

Inference-only (no VJP): the serving step never backpropagates.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_flash import _cparams, _interpret_mode
from .pallas_paged_decode import _head_scale_mat

NEG_INF = -1e30


def _ragged_kernel(wq_ref, wr_ref, wf_ref, wn_ref, qs_ref, ql_ref, kl_ref,
                   tbl_ref, layer_ref, *refs, scale, block_k, pages, tq, gh,
                   num_blocks, table_entries, quantized=False, hkv=0):
    # positional ref layout follows the pallas_call spec lists: inputs
    # (q, k, v[, k_scale, v_scale]), then the output, then scratch (one
    # two-slot VMEM buffer per pool-side input, the DMA semaphores, m/l/acc)
    if quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf,
         vs_buf, sems, m_scr, l_scr, acc_scr) = refs
        streams = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                   (vs_hbm, vs_buf))
    else:
        (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr,
         acc_scr) = refs
        ks_buf = vs_buf = None
        streams = ((k_hbm, k_buf), (v_hbm, v_buf))
    w = pl.program_id(0)            # one work-list entry: (query block, row)
    qi = wq_ref[w]
    r = wr_ref[w]
    nkb = wn_ref[w]                 # pool blocks this pair walks (0 = dead)
    layer = layer_ref[0]            # which layer of the stored pool
    qstart = qs_ref[r]
    qlen = ql_ref[r]
    kvlen = kl_ref[r]
    row0 = qi * tq                  # first wide row of this query block
    span_lo = qstart * gh           # span bounds in wide-row coordinates
    span_hi = (qstart + qlen) * gh
    group = pages * block_k         # keys of one online-softmax update

    @pl.when(wf_ref[w] == 1)
    def _zero_out():
        # first visit of this output block: packed rows outside every
        # span must come back as exact zeros, not stale VMEM
        o_ref[:] = jnp.zeros_like(o_ref)

    def _copies(gi, slot):
        # table-indirect fetch of group gi, `pages` consecutive table
        # entries, into buffer `slot`: the table is resolved from SMEM at
        # DMA-issue time; entries past the table clamp to its last, and
        # sentinel entries into THIS layer's blocks before the layer is
        # applied (a harmless read, masked by kvlen and the causal rule;
        # never a block of the next layer). The scale planes, this layer's
        # already, ride the SAME block index as their data.
        out = []
        for j in range(pages):
            entry = jnp.minimum(gi * pages + j, table_entries - 1)
            phys = jnp.clip(tbl_ref[r, entry], 0, num_blocks - 1)
            rows = pl.ds(j * block_k, block_k)
            for i, (hbm, buf) in enumerate(streams):
                if hbm.ndim == 4:       # the stored pool [L, nb, bs, KD]
                    src, dst = hbm.at[layer, phys], buf.at[slot, rows]
                elif hbm.ndim == 3:     # int8 planes [nb, bs, lanes]
                    src, dst = hbm.at[phys], buf.at[slot, rows]
                else:                   # fp8 planes [nb, lanes]: one row a
                    src = hbm.at[pl.ds(phys, 1)]        # block, 2D windows
                    dst = buf.at[slot, pl.ds(j, 1)]
                out.append(pltpu.make_async_copy(src, dst,
                                                 sems.at[i, slot, j]))
        return out

    def _dequant(plane, nr):
        # [nr, group] dequant factors of one group's scale planes, applied
        # post-dot by the head one-hot trick (the rows are whole tokens, so
        # the row->head map is position-free). int8 carries a scale per
        # (pool row, head): the pages' planes lie concatenated. fp8 carries
        # one per (block, head): a factor per page, spread over its columns
        # by a second one-hot.
        f = _head_scale_mat(plane[:, :hkv], nr, gh, hkv)
        if quantized == "fp8":
            page = jax.lax.broadcasted_iota(jnp.int32, (pages, group), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (pages, group), 1)
            spread = jnp.where(col // block_k == page, 1.0, 0.0)
            f = jax.lax.dot_general(f, spread.astype(jnp.float32),
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        return f

    def _walk(nr, load_q, valid_of, write):
        # one pair's walk on ``nr`` wide rows (static), the softmax state in
        # the first ``nr`` rows of the scratch
        m_ref, l_ref, acc_ref = (ref.at[pl.ds(0, nr)]
                                 for ref in (m_scr, l_scr, acc_scr))
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        n_groups = (nkb + pages - 1) // pages
        for c in _copies(0, 0):
            c.start()

        def _group(gi, carry):
            # double buffer: group gi + 1 streams in while gi computes
            slot = gi % 2

            @pl.when(gi + 1 < n_groups)
            def _prefetch():
                for c in _copies(gi + 1, 1 - slot):
                    c.start()

            for c in _copies(gi, slot):
                c.wait()
            q = load_q()                        # [nr, KD] block-diag wide
            k = k_buf[slot]                     # [group, KD]
            v = v_buf[slot]
            if quantized:
                # quantized pool: the table-indirect DMA above moved the
                # narrow dtype (the HBM win); the upcast happens HERE,
                # right after it: values convert in VMEM on the way into
                # the MXU and the scales apply post-dot (_dequant)
                k = k.astype(jnp.float32)
                v = v.astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * _dequant(ks_buf[slot], nr)
            valid = valid_of(gi * group + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1), s.shape)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # exp hits exact 0 on masked cols only while the row has a
            # valid one; a row of the block outside the span has none
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            # pool rows past `kvlen` may hold another block's garbage (or
            # a clamped entry's), and 0 * NaN is NaN: zero them out of PV,
            # in the one group that can hold any
            v = jax.lax.cond(
                (gi + 1) * group > kvlen,
                lambda v: jnp.where(
                    gi * group + jax.lax.broadcasted_iota(
                        jnp.int32, v.shape, 0) < kvlen, v, jnp.zeros_like(v)),
                lambda v: v, v)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:] = jnp.broadcast_to(
                alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
                l_ref.shape)
            if quantized:
                # V dequant, same separability: fold the scales into P
                # (P_wj * sv[j, head(w)]) and dot with the raw values
                p = p * _dequant(vs_buf[slot], nr)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
            return carry

        # exactly this pair's blocks, ascending, `pages` an update: the
        # row's own length and the causal diagonal both already bound nkb
        # (_work_list); what the last group holds past them is masked
        jax.lax.fori_loop(0, n_groups, _group, 0)
        write(acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30))

    # a span of ONE token (a decode row) is ``gh`` wide rows at a multiple
    # of ``gh`` inside the query block: it computes on those rows alone,
    # not on the block's other tokens, which belong to other rows (where
    # ``gh`` rows are whole tiles; else every span takes the general walk)
    short_walk = _one_token_walk(gh, tq)
    alone = (qlen == 1) if short_walk else False

    if short_walk:
        @pl.when((nkb > 0) & alone)
        def _one_token():
            off = pl.multiple_of(span_lo - row0, gh)

            def write(out):
                o_ref[pl.ds(off, gh), :] = out.astype(o_ref.dtype)

            _walk(gh, lambda: q_ref[pl.ds(off, gh), :],
                  lambda cols, shape: cols < kvlen, write)

    @pl.when((nkb > 0) & jnp.logical_not(alone))
    def _span():
        def valid_of(cols, shape):
            # causal-within-span: wide row w belongs to span token
            # (w - span_lo) // gh, whose logical position is
            # kvlen - qlen + that token index
            wrow = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            pos = kvlen - qlen + (wrow - span_lo) // gh
            return (wrow >= span_lo) & (wrow < span_hi) & (cols <= pos)

        def write(out):
            # write back ONLY this row's span: the output block is shared
            # by every sequence whose span intersects it, so the write is a
            # masked read-modify-write (rows not in span keep their value)
            wrow = row0 + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
            o_ref[:] = jnp.where((wrow >= span_lo) & (wrow < span_hi),
                                 out.astype(o_ref.dtype), o_ref[:])

        _walk(tq, lambda: q_ref[:], valid_of, write)


def _one_token_walk(gh, tq):
    """Whether a span of one token computes on its own ``gh`` wide rows
    alone: where those are whole tiles and fewer than the query block."""
    return gh % 16 == 0 and gh < tq


#: what an online-softmax update takes: at most this many keys, fewer where
#: the K and V buffers of that many (two slots each) would pass these bytes.
#: Settled on the chip (PERF.md, PR 32): past 256 keys the group's masked
#: tail (half a group a pair, fetched and multiplied for nothing) costs a
#: short walk more than the fewer rescales of the accumulator save a long one
_GROUP_KEYS = 256
_GROUP_BYTES = 2 << 20


def pages_per_update(pool_dtype, block_size, kd, table_entries):
    """Table entries one online-softmax update fetches and computes on
    together, from what a call can observe: ``_GROUP_KEYS`` keys' worth of
    pool blocks, fewer where a row is wide (8 for Mistral's ``KD`` 1024 in
    bf16 at blocks of 32, 4 for OLMoE's 2048), never more than the table
    holds. A one-byte pool counts at four bytes: it is upcast to float32 in
    VMEM on its way into the MXU."""
    itemsize = jnp.dtype(pool_dtype).itemsize
    row = 4 * int(kd) * (4 if itemsize == 1 else itemsize)
    keys = min(_GROUP_KEYS, _GROUP_BYTES // row)
    return max(1, min(keys // int(block_size), int(table_entries)))


def query_block_rows(kd):
    """Wide rows of a query block (before ``_query_block`` fits it to the
    heads and the packed buffer): 512 up to Mistral's ``KD`` 1024, where a
    chunk re-reads its prefix once every 16 tokens and not every 8 and the
    kernel of a 512-token chunk 3 k into its prompt is a sixth shorter than
    at 256 (PERF.md, PR 32); fewer for wider rows (256 at OLMoE's 2048, 128
    at 4096), so that the two query and two output blocks and the float32
    accumulator keep the 6 MiB of scoped VMEM they have there."""
    return min(512, (512 << 10) // int(kd))


def _least(a, b):
    """``min(a, b)`` for plain ints and for broadcasting int arrays alike."""
    return a - (a > b) * (a - b)


def _pair_kv_blocks(qs, ql, kl, qi, *, tokens_per_block, block_size,
                    table_entries):
    """KV blocks the pair (query block ``qi``, a live row) walks: up to the
    row's own length AND the causal diagonal of the last span token inside
    the query block. Works on ints and on broadcasting int arrays alike —
    the kernel's work list and the host's counter share this one rule."""
    last = _least(qs + ql, (qi + 1) * tokens_per_block) - 1
    pos_max = kl - ql + (last - qs)     # that token's logical position
    n = _least(_least(pos_max // block_size + 1, -(-kl // block_size)),
               table_entries)
    return n * (n > 0)


def _work_list(qstart, qlen, kvlen, *, nq, tokens_per_block, block_size,
               table_entries):
    """The kernel's iteration space, from the step's span metadata (jnp,
    inside the jitted program): ``nq + R`` entries ``(query block, row,
    first visit of its output block, KV blocks to walk)`` ordered by query
    block, then row. The packed spans are disjoint and contiguous, so at
    most ``nq + R - 1`` (query block, row) pairs intersect; a query block no
    live span touches gets one dead entry (zero its output, walk nothing),
    and the tail repeats the last entry dead (same blocks: no DMA)."""
    R = qstart.shape[0]
    W = nq + R
    qi = jnp.arange(nq, dtype=jnp.int32)[:, None]
    qs, ql, kl = qstart[None, :], qlen[None, :], kvlen[None, :]
    inter = ((ql > 0) & (qs < (qi + 1) * tokens_per_block)
             & (qs + ql > qi * tokens_per_block))               # [nq, R]
    n_mat = jnp.where(inter, _pair_kv_blocks(
        qs, ql, kl, qi, tokens_per_block=tokens_per_block,
        block_size=block_size, table_entries=table_entries), 0)
    # column R: the dead visit of a query block that no span touches
    untouched = ~jnp.any(inter, axis=1, keepdims=True)
    mask = jnp.concatenate([inter, untouched], axis=1).reshape(-1)
    n_flat = jnp.concatenate(
        [n_mat, jnp.zeros((nq, 1), jnp.int32)], axis=1).reshape(-1)
    order = jnp.cumsum(mask.astype(jnp.int32))      # 1-based rank of a visit
    j = jnp.arange(W, dtype=jnp.int32)
    pad = j >= order[-1]
    # entry j is the visit of rank j + 1 (the tail: of the last rank);
    # a [W, nq * (R + 1)] one-hot select, so no gather and no sort
    pick = mask[None, :] & (order[None, :]
                            == jnp.minimum(j + 1, order[-1])[:, None])
    flat = jnp.sum(jnp.where(
        pick, jnp.arange(mask.shape[0], dtype=jnp.int32)[None, :], 0), axis=1)
    wq = flat // (R + 1)
    wr = flat % (R + 1)
    wr = jnp.where(wr == R, 0, wr)
    wn = jnp.where(pad, 0, jnp.sum(jnp.where(pick, n_flat[None, :], 0),
                                   axis=1))
    first = (j == 0) | (wq != jnp.roll(wq, 1))
    return wq, wr, first.astype(jnp.int32), wn


def _ragged_call(q_wide, pool_k, pool_v, layer, tables, qstart, qlen, kvlen,
                 scale, gh, block_q, pages, interpret, scales=None):
    """q_wide: [TH, KD] block-diagonal wide rows (gh per token);
    pool_*: the stored pool ``[L, num_blocks, bs, KD]``, left in HBM whole;
    layer: [1] int32, the layer whose blocks this call reads;
    tables: [R, max_blocks] int32;
    scales: None, or ``(k_scale, v_scale)`` fp32 planes for a
    quantized pool (upcast in-kernel, right after the table-indirect
    DMA): [L, num_blocks, bs, Hkv] per-row planes select the int8 path,
    [L, num_blocks, Hkv] per-block planes select fp8 — the plane rank IS
    the mode switch, same convention as ``pallas_paged_decode``;
    block_q, pages: the call's tiling (``grid_params``).

    The grid is the work list (``_work_list``): one step per (query block,
    row) pair, and inside it a loop over exactly the pair's KV blocks,
    fetched at ``(layer, table entry)``, ``pages`` of them an iteration."""
    TH, KD = q_wide.shape
    num_blocks, bs = pool_k.shape[1], pool_k.shape[2]
    R, nk = tables.shape
    nq = -(-TH // block_q)          # the last block may be partial
    work = _work_list(qstart, qlen, kvlen, nq=nq,
                      tokens_per_block=block_q // gh, block_size=bs,
                      table_entries=nk)
    if scales is None:
        quantized = False
    else:
        quantized = "fp8" if scales[0].ndim == 3 else "int8"
    hkv = scales[0].shape[-1] if quantized else 0
    kernel = functools.partial(_ragged_kernel, scale=scale, block_k=bs,
                               pages=pages, tq=block_q, gh=gh,
                               num_blocks=num_blocks, table_entries=nk,
                               quantized=quantized, hkv=hkv)

    def _q_index(w, wq, *_):
        return (wq[w], 0)

    in_pool = pl.BlockSpec(memory_space=pl.ANY)     # fetched by the kernel
    in_specs = [pl.BlockSpec((block_q, KD), _q_index), in_pool, in_pool]
    args = [*work, qstart, qlen, kvlen, tables, layer, q_wide, pool_k,
            pool_v]
    bufs = [pltpu.VMEM((2, pages * bs, KD), pool_k.dtype),
            pltpu.VMEM((2, pages * bs, KD), pool_v.dtype)]
    if quantized:
        # per-row int8 planes [nb, bs, hkv] move one [bs, hkv] block a page,
        # per-BLOCK fp8 planes [nb, hkv] one [1, hkv] row. A DMA window's
        # minor dim must be whole lanes, so this layer's planes are cut out
        # and padded to 128 heads here and the kernel reads the first hkv
        lanes = -(-hkv // 128) * 128
        scales = [jnp.pad(jax.lax.dynamic_index_in_dim(p, layer[0], 0, False),
                          [(0, 0)] * (p.ndim - 2) + [(0, lanes - hkv)])
                  for p in scales]
        plane = pages * (1 if quantized == "fp8" else bs)
        in_specs += [in_pool, in_pool]
        args += scales
        bufs += [pltpu.VMEM((2, plane, lanes), p.dtype) for p in scales]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(nq + R,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_q, KD), _q_index),
            scratch_shapes=bufs + [
                pltpu.SemaphoreType.DMA((len(bufs), 2, pages)),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, KD), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((TH, KD), q_wide.dtype),
        # consecutive entries revisit one output block (accumulated
        # across rows by the masked write) — no reordering allowed
        compiler_params=_cparams(("arbitrary",)),
        interpret=interpret,
        name="ragged_paged_attention",
    )(*args)
    return out


def wide_rows(heads):
    """Wide rows a token in the call: its heads, or from 8 up the next
    multiple of 8 where the count is none (30 -> 32), so that ``[T, rows,
    KD]`` and ``[T * rows, KD]`` are one layout and a query block is whole
    sublane groups; the added rows are zero queries whose outputs are
    dropped (``_ragged_padded_heads``)."""
    return heads if heads % 8 == 0 or heads < 8 else -(-heads // 8) * 8


def _query_block(block_q, heads, packed_tokens):
    """The query block in wide rows: a multiple of ``heads`` (so //gh never
    crosses a pad boundary), at most the whole packed buffer."""
    return max(heads, min(int(block_q) // heads * heads,
                          packed_tokens * heads))


def grid_params(pool_dtype, block_size, kd, table_entries, heads,
                packed_tokens, block_q=None, pages=None):
    """The tiling of one call, ``{"block_q", "pages"}``: the query block in
    wide rows as the call cuts it and the table entries one online-softmax
    update takes, from what the call observes (``block_q`` / ``pages`` given:
    fitted like the derived ones). The ONE derivation:
    ``ragged_paged_attention_pallas`` tiles with it and the engine passes it
    to ``ragged_grid_counts``, so the host's counts are the kernel's."""
    if block_q is None:
        block_q = query_block_rows(kd)
    if pages is None:
        pages = pages_per_update(pool_dtype, block_size, kd, table_entries)
    return {"block_q": _query_block(block_q, heads, packed_tokens),
            "pages": max(1, min(int(pages), int(table_entries)))}


def ragged_grid_counts(qstart, qlen, kvlen, *, heads, block_size,
                       table_entries, packed_tokens, block_q=256, pages=1):
    """What one call of the kernel is asked to do, counted on the host from
    the step's span metadata (plain integers; no jax): ``grid_steps``, the
    steps the kernel visits — the ``nq + R`` work-list entries of its grid
    plus one per KV block its in-kernel loops walk; ``live_steps``, those
    that compute (the pool blocks fetched: a work-list entry itself only
    zeroes, resets or writes back); ``update_steps``, the online-softmax
    updates that takes at ``pages`` blocks an update (``pages_per_update``;
    a pair's last group may hold fewer); ``one_token_rows``, the rows that
    compute on their own ``heads`` wide rows and not on the query block
    (spans of one token, where the kernel has that walk); ``kv_tokens``, the
    cache rows the live spans attend over; ``attn_pairs``, their causal
    (query, key) pairs. A row with ``qlen == 0`` is dead."""
    bq = _query_block(block_q, heads, packed_tokens)
    nq = -(-(packed_tokens * heads) // bq)
    tpb = bq // heads
    live = updates = alone = kv_tokens = pairs = 0
    for qs, ql, kl in zip(qstart, qlen, kvlen):
        qs, ql, kl = int(qs), int(ql), int(kl)
        if ql <= 0:
            continue
        kv_tokens += kl
        pairs += ql * (kl - ql) + ql * (ql + 1) // 2
        alone += ql == 1 and _one_token_walk(heads, bq)
        for qi in range(qs // tpb, min(nq, -(-(qs + ql) // tpb))):
            n = _pair_kv_blocks(
                qs, ql, kl, qi, tokens_per_block=tpb,
                block_size=block_size, table_entries=int(table_entries))
            live += n
            updates += -(-n // int(pages))
    return {"grid_steps": nq + len(qstart) + live, "live_steps": live,
            "update_steps": updates, "one_token_rows": alone,
            "kv_tokens": kv_tokens, "attn_pairs": pairs}


# Inference-only custom_vjp, same rationale as pallas_paged_decode: the
# eager dispatch linearizes through every op and scalar-prefetch
# pallas_calls don't linearize in interpret mode. ``scales`` is ``()`` or
# the ``(k_scale, v_scale)`` planes of a quantized pool.
@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12))
def _ragged(q_wide, pool_k, pool_v, scales, layer, tables, qstart, qlen,
            kvlen, scale, gh, block_q, pages):
    return _ragged_call(q_wide, pool_k, pool_v, layer, tables, qstart, qlen,
                        kvlen, scale, gh, block_q, pages, _interpret_mode(),
                        scales=scales or None)


def _ragged_fwd_rule(q_wide, pool_k, pool_v, scales, layer, tables, qstart,
                     qlen, kvlen, scale, gh, block_q, pages):
    return _ragged(q_wide, pool_k, pool_v, scales, layer, tables, qstart,
                   qlen, kvlen, scale, gh, block_q, pages), None


def _ragged_bwd_rule(scale, gh, block_q, pages, res, g):
    raise NotImplementedError(
        "ragged_paged_attention_pallas is inference-only (the serving "
        "step never backpropagates)")


_ragged.defvjp(_ragged_fwd_rule, _ragged_bwd_rule)


def _stored_pool(pool_k, pool_v, k_scale, v_scale, layer):
    """Both call forms as ``(pool_k, pool_v, scales, layer [1] int32)`` over
    a pool ``[L, num_blocks, bs, Hkv * D]``: with ``layer`` the arrays are
    the stored pool already (``serving.block_manager.BlockManager``); without
    it they are one layer ``[num_blocks, bs, Hkv, D]`` and become the
    ``L = 1`` pool (merging the two minor dims is a copy on the chip, which
    only tests and microbenchmarks pay)."""
    if layer is None:
        pool_k, pool_v = (jnp.reshape(p, (1,) + p.shape[:2] + (-1,))
                          for p in (pool_k, pool_v))
        if k_scale is not None:
            k_scale, v_scale = (jnp.asarray(p)[None]
                                for p in (k_scale, v_scale))
        layer = 0
    scales = () if k_scale is None else (k_scale, v_scale)
    return pool_k, pool_v, scales, jnp.asarray(layer, jnp.int32).reshape(1)


def ragged_paged_attention_pallas(q, pool_k, pool_v, tables, qstart, qlen,
                                  kvlen, block_q=None, k_scale=None,
                                  v_scale=None, layer=None, pages=None):
    """Mixed prefill+decode attention over packed query spans through
    per-sequence block tables.

    q:        [T, H, D]              — the packed query buffer
    pool_k, pool_v, layer: the KV block pool and the layer to read
              (``_stored_pool``): the step programs pass the stored pool
              whole and a traced ``layer``, and the kernel fetches blocks
              from it where it lies
    tables:   [R, max_blocks] int32  — physical block ids per sequence
                                       (entries >= num_blocks = unmapped)
    qstart:   [R] int32 — span start (packed row) per sequence
    qlen:     [R] int32 — span length per sequence (0 = dead row)
    kvlen:    [R] int32 — valid logical KV rows per sequence AFTER this
                          step's writes (span token i attends over
                          positions 0 .. kvlen - qlen + i)
    k_scale/v_scale: None, or fp32 scale planes for a quantized pool
              (README "Quantized serving"), stacked or one layer's like
              the pool — per-row ``[.., num_blocks, bs, Hkv]`` planes for
              int8, per-block ``[.., num_blocks, Hkv]`` planes for fp8
              (plane rank = mode switch). The kernel
              DMAs the narrow blocks and upcasts in VMEM right after
              the table-indirect fetch — one upcast site, fused into
              the dot — so HBM traffic is 1-byte while the MXU math
              stays full-precision
    returns:  [T, H, D]; packed rows outside every span are exact zeros

    GQA is resolved with the block-diagonal wide-query trick (see
    ``pallas_decode.py``). The kernel iterates over a work list of the
    (query block, row) pairs that intersect, built here from ``qstart`` /
    ``qlen``, and for each pair over the KV blocks up to the row's
    ``kvlen`` and the causal diagonal: blocks past either are never
    fetched, dead rows and non-intersecting pairs are never visited;
    sentinel table entries clamp harmlessly. ``block_q`` (the query block's
    wide rows; None: ``query_block_rows``) and ``pages`` (table entries an
    online-softmax update takes; None: ``pages_per_update``) are for tests:
    the step programs pass neither. At ``pages=1`` a span of length 1
    reproduces ``paged_decode_attention_pallas`` for that row exactly (same
    block walk, same online-softmax accumulation order).
    """
    T, H, D = q.shape
    pool_k, pool_v, scales, layer = _stored_pool(pool_k, pool_v, k_scale,
                                                 v_scale, layer)
    KD = pool_k.shape[-1]
    Hkv = KD // D
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    scale = 1.0 / math.sqrt(D)
    qstart = jnp.asarray(qstart, jnp.int32).reshape(-1)
    qlen = jnp.asarray(qlen, jnp.int32).reshape(-1)
    kvlen = jnp.asarray(kvlen, jnp.int32).reshape(-1)
    tables = jnp.asarray(tables, jnp.int32).reshape(qstart.shape[0], -1)
    # block-diagonal wide query: head h's D values at its kv group's
    # lanes, one wide row per (token, head)
    rows = wide_rows(H)
    if rows != H:
        return _ragged_padded_heads(
            q, pool_k, pool_v, scales, layer, tables, qstart, qlen, kvlen,
            scale, rows, block_q, pages)
    eye = jnp.eye(Hkv, dtype=q.dtype)
    q_wide = jnp.einsum("bkgd,kj->bkgjd", q.reshape(T, Hkv, G, D), eye)
    q_wide = q_wide.reshape(T * H, KD)
    # the query block is a multiple of H, so //gh never crosses a token; the
    # last one may reach past the packed buffer (no pad, no copy: the rows
    # it holds past the end belong to no span and are neither computed on
    # nor written back)
    tiling = grid_params(pool_k.dtype, pool_k.shape[2], KD, tables.shape[1],
                         H, T, block_q, pages)
    out_wide = _ragged(q_wide, pool_k, pool_v, scales, layer, tables, qstart,
                       qlen, kvlen, scale, H, tiling["block_q"],
                       tiling["pages"])
    # extract each head's own kv-group block from the wide accumulator
    out = jnp.einsum("bkgjd,kj->bkgd",
                     out_wide.reshape(T, Hkv, G, Hkv, D), eye)
    return out.reshape(T, H, D)


def _ragged_padded_heads(q, pool_k, pool_v, scales, layer, tables, qstart,
                         qlen, kvlen, scale, rows, block_q, pages):
    """``ragged_paged_attention_pallas`` for a head count that is no whole
    sublane group (``wide_rows``: 30 -> ``rows`` 32). The block-diagonal
    wide query is made, and the heads' own blocks taken back out of the wide
    output, WITHOUT an array whose minor dims are ``(Hkv, D)``: at 30 KV
    heads those tile to 32 x 128 and every reshape between them and the
    ``KD`` lanes the kernel reads moves the whole 130 MB (a fifth of the
    attention's time: PERF.md, PR 33). Instead the query, its heads padded
    (4 MB), is spread over the lanes by a 0 / 1 selection matmul (``D`` ->
    ``KD``, exact in any dtype: one non-zero term a sum) and masked to its
    own kv group's lanes; the output is masked and folded back by the
    transposed selection. The added rows are zero queries whose outputs are
    dropped."""
    T, H, D = q.shape
    KD = pool_k.shape[-1]
    G = H // (KD // D)
    lane = jnp.arange(KD, dtype=jnp.int32)
    spread = (lane[None, :] % D == jnp.arange(D, dtype=jnp.int32)[:, None]
              ).astype(q.dtype)                                  # [D, KD]
    own = (lane[None, :] // D
           == jnp.arange(rows, dtype=jnp.int32)[:, None] // G)   # [rows, KD]
    q = jnp.pad(q, ((0, 0), (0, rows - H), (0, 0))).reshape(T * rows, D)
    q_wide = jnp.where(own, jnp.dot(q, spread).reshape(T, rows, KD), 0)
    tiling = grid_params(pool_k.dtype, pool_k.shape[2], KD, tables.shape[1],
                         rows, T, block_q, pages)
    out_wide = _ragged(q_wide.reshape(T * rows, KD), pool_k, pool_v, scales,
                       layer, tables, qstart, qlen, kvlen, scale, rows,
                       tiling["block_q"], tiling["pages"])
    out_wide = jnp.where(own, out_wide.reshape(T, rows, KD), 0)
    out = jnp.dot(out_wide.reshape(T * rows, KD), spread.T)
    # (the barrier keeps XLA from moving the cut of the padded heads above
    # the fold, where it would copy the wide output to take it)
    return jax.lax.optimization_barrier(out).reshape(T, rows, D)[:, :H]


def ragged_attention_reference(q, pool_k, pool_v, tables, qstart, qlen,
                               kvlen, k_scale=None, v_scale=None, layer=None):
    """jnp oracle with identical semantics and operands (``_stored_pool``:
    with ``layer`` the tables gather straight from the stored pool, no
    layer of it is cut out) — and, deliberately, the exact op sequence of
    the two programs it unifies: a span-1 row
    reproduces ``paged_decode_attention_reference`` and a span-n row
    reproduces ``_paged_suffix_prefill_impl``'s in-program attention
    (same einsums, same masking, same plain softmax), so the unified
    serving step can be pinned bitwise against the old pair. A
    quantized pool (``k_scale``/``v_scale`` given) upcasts right after
    the two-stage gather — the same fetch-then-dequantize order as the
    kernel; per-block fp8 planes (ndim 2) broadcast over the block's
    rows."""
    T, H, D = q.shape
    pool_k, pool_v, scales, layer = _stored_pool(pool_k, pool_v, k_scale,
                                                 v_scale, layer)
    bs, Hkv = pool_k.shape[2], pool_k.shape[3] // D
    G = H // Hkv
    R, mb = jnp.asarray(tables).shape
    s_tot = mb * bs
    scale = 1.0 / math.sqrt(D)
    qstart = jnp.asarray(qstart, jnp.int32).reshape(R)
    qlen = jnp.asarray(qlen, jnp.int32).reshape(R)
    kvlen = jnp.asarray(kvlen, jnp.int32).reshape(R)
    tables = jnp.asarray(tables, jnp.int32)

    def blocks(pool):               # [R, mb, ...]: this layer's, by table
        return jnp.asarray(pool).at[layer[0], tables].get(mode="clip")

    t_idx = jnp.arange(T, dtype=jnp.int32)
    # token -> sequence map (spans are disjoint; dead tokens match none)
    in_r = (t_idx[None, :] >= qstart[:, None]) \
        & (t_idx[None, :] < (qstart + qlen)[:, None])     # [R, T]
    live = jnp.any(in_r, axis=0)                          # [T]
    seg = jnp.argmax(in_r, axis=0).astype(jnp.int32)      # [T]
    # per-token logical cache, gathered in two stages: pool -> per-ROW
    # cache through each sequence's table ([R, s_tot], the same gather
    # the decode reference pays), then a contiguous per-token row pick.
    # Elementwise identical to the direct [T, mb]-indexed pool gather
    # (gathers compute nothing, so reassociation is exact) but the
    # random-access pool traffic scales with R instead of T — on the
    # CPU/jnp serving path the packed buffer's padding rows would
    # otherwise multiply the dominant gather cost ~T/R-fold.
    # (clip keeps sentinel entries harmless — masked by kvlen)
    k_rows = blocks(pool_k).reshape(R, s_tot, Hkv, D)
    v_rows = blocks(pool_v).reshape(R, s_tot, Hkv, D)
    if scales:
        # quantized pool: upcast right after the per-row gather (the
        # kernel's fetch-then-dequantize order). Per-block fp8 planes
        # ([L, num_blocks, Hkv]) broadcast over each block's rows;
        # per-row int8 planes apply per row and head.
        if scales[0].ndim == 3:
            ks_rows, vs_rows = (jnp.repeat(blocks(p), bs, axis=1)
                                for p in scales)
        else:
            ks_rows, vs_rows = (blocks(p).reshape(R, s_tot, Hkv)
                                for p in scales)
        k_rows = k_rows.astype(jnp.float32) * ks_rows[..., None]
        v_rows = v_rows.astype(jnp.float32) * vs_rows[..., None]
    k = jnp.take(k_rows, seg, axis=0)                     # [T, s_tot, ...]
    v = jnp.take(v_rows, seg, axis=0)
    kf = jnp.repeat(k, G, axis=2) if G > 1 else k
    vf = jnp.repeat(v, G, axis=2) if G > 1 else v
    pos = (jnp.take(kvlen, seg) - jnp.take(qlen, seg)
           + (t_idx - jnp.take(qstart, seg)))             # [T]
    cols = jnp.arange(s_tot, dtype=jnp.int32)
    mask = (cols[None, :] <= pos[:, None]) & live[:, None]  # [T, s_tot]
    logits = jnp.einsum("qhd,qkhd->qhk", q, kf,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # exact zeros on masked cols + zeroed garbage rows: stale pool rows
    # can be anything (0 * NaN = NaN)
    probs = jnp.where(mask[:, None, :], probs, 0.0)
    row_valid = cols[None, :] < jnp.take(kvlen, seg)[:, None]
    vf = jnp.where(row_valid[:, :, None, None], vf, 0.0)
    out = jnp.einsum("qhk,qkhd->qhd", probs.astype(q.dtype), vf)
    return jnp.where(live[:, None, None], out, jnp.zeros_like(out))
