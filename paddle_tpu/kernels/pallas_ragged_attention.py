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

Design points (the table-indirect fetch and the Mosaic-conservative tiles
are ``pallas_paged_decode.py``'s):

- **The query goes in head-major**: the entry makes ``q [T, H, D]`` into
  ``[Hkv, T * G, D]`` (row ``t * G + g`` of plane ``k`` is head ``k * G +
  g`` of token ``t``; for MHA ``[H, T, D]``) and the output comes back the
  same way: two transposes of ``T * H * D`` elements around the call, and
  no array anywhere with ``Hkv * D`` values a (token, head). The heads are
  a LEADING dimension, so a count that is no multiple of 8 (Olmo-Hybrid's
  30) needs no padding.
- **The iteration space is the step's live work**, built from the span
  metadata inside the program (``_work_list``; no host work, no extra
  argument). The grid is a *work list* of (query block, row) pairs: the
  packed tokens are tiled into fixed query blocks, the packed
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
  keys' worth, 128 where a cached row is wide: 8 blocks of 32 for Mistral, 4
  for OLMoE and Olmo-Hybrid). It
  resolves them from the scalar-prefetched table in SMEM and fetches each
  block, at ``(layer, table entry)``, by ``make_async_copy`` into one
  two-slot ``[2, pages * bs, KD]`` buffer a side, all copies in flight
  together and the next group streaming in while this one computes.
  **The pipeline does not drain at the end of a pair**: where two
  work-list entries in a row are live (``_hands_over``), the pair's LAST
  loop iteration starts the copies of the NEXT pair's first group (its row,
  its first group under a window; all in SMEM already) into the slot it is
  not computing on: K, V and a quantized pool's two scale planes, one set.
  They land behind the last update, the divide, the masked write-back, the
  grid step and the next pair's reset and query build, and that pair
  rebuilds the same descriptors and only waits. So at most two groups are in
  flight, as inside a pair: no buffer and no semaphore more. The slot of a
  pair's first group is the number of groups every earlier entry walks, mod
  2 (``_first_slots``, one more scalar-prefetched array), so whatever the
  pair before walked, its last group lies in the other slot. Nothing is
  handed across a dead entry (an untouched query block, the padded tail)
  and the last live pair hands to nobody: every copy a call starts is
  waited for inside it and none is in flight when it ends.
  Entries past the table clamp to its last and
  sentinel entries (``>= num_blocks``) into the layer's own blocks — a
  harmless read, masked off by ``kvlen`` and the causal rule; V rows past
  ``kvlen`` are zeroed, in the buffer, in the one group that can hold any
  (a stale row may be NaN). So HBM traffic scales with the live logical
  cache rounded up to a group a pair, and no layer of the pool is cut out
  or re-laid-out for the call.
- **Each KV head's keys by that head's queries only**: head ``k``'s keys
  are lanes ``k * D .. (k + 1) * D`` of the fetched group's K side and its
  values lanes ``k * Dv .. (k + 1) * Dv`` of its V side, a whole-lane-tile
  window at 128; **a key and a value need not be as wide** (``Dv`` is the V
  side's row over the KV heads: MiMo-V2-Flash's 192 | 128, where an odd
  head's key window starts at half a lane tile, which Mosaic cuts; the two
  sides are two buffers of their own row). Per head and update: ``s_k = q_k
  [rows, D] x K_k^T``, one mask (the same for every head), the ``m / l``
  update, ``acc_k [rows, Dv] += p_k x V_k``; the float32 accumulator and the
  output are ``[Hkv, rows, Dv]``. **A sink** (``sink``, a logit a query head)
  is one more column of a head's softmax with no value: the online softmax
  starts at it (``m`` the logit, ``l`` 1) instead of at nothing, in both
  walks, under a window or not; a call without one is the program it was.
  **The update is the general walk's own**
  (``_span_update``): a plane's hundreds of rows made its cost a row's and
  not a FLOP's, so a row's ``m`` lies on every lane of its tile and is never
  narrowed to one (no lane broadcast on the XLUs: they bound the schedule,
  PERF.md section 6, PR 53), and its ``l`` is summed BY LANE, column tile
  on column tile, the one reduction along the lanes made where the pair
  writes back; what is left across lanes is the row maximum, once a row
  tile an update. A decode row's walk keeps ``_softmax_update``: its ``H``
  rows are bound by their copies. No zero is multiplied (the block-diagonal
  wide query this replaced cost ``Hkv`` x the MXU work and an accumulator
  ``Hkv`` x this one: PERF.md, PR 32 and 36). An int8 pool's scale for head
  ``k`` is column ``k`` of the plane that rode the same physical index as
  its data block, applied to the head's window as it is upcast; an fp8
  pool's per-block scale is spread over the block's rows.
- **Everything the kernel cuts is whole tokens in whole 16-row tiles, at
  any group width.** The query block is sized by the state it carries
  (``query_block_rows``): as many whole row tiles of TOKENS as the float32
  accumulator of all planes (a VALUE wide) holds under 2 MiB, so ``tokens * G`` rows of a
  plane are whole row tiles whatever ``G`` is. 128 tokens at Mistral's 32 /
  8 / 128, Olmo-Hybrid's 30 / 30 / 128 and Nemotron-3-Nano's 32 / 2 / 128,
  256 at OLMoE's 16 / 16 / 128, 192 at Jamba2-3B's 20 / 1 / 128: a
  512-token chunk re-reads its prefix 3 to 5 times (a block cut to 512 rows
  of a plane held 16 tokens at a group of 20 and walked it 32 times). How
  tall a plane is decides only how the general walk takes it: **in static
  row chunks of at most 512 rows** (``_row_chunks``: one head's float32
  score tile at 256 keys stays 512 KiB), each on its own views of ``m / l /
  acc``, and **a chunk that holds no row of the pair's span, or no key at
  or under its last row's diagonal, is skipped**, so a short span in a tall
  block computes on its own chunks only. A plane of at most 512 rows is one
  chunk and the walk is unconditional.
- **A span of one token takes one product over the whole pool row**: a
  decode row is bound by its KV bytes, and ``Hkv`` small per-head products
  an update would make it slower (measured: PERF.md, PR 36). The kernel
  observes ``qlen == 1``, cuts the token's ``H`` query rows out of the
  head-major block once a pair (each plane's aligned tile of ``lcm(16, G)``
  rows, ``_token_tile``: whole tokens in whole row tiles, so no token
  straddles two; 16 rows at a group of 1 to 16 that divides 16, 80 = 4
  tokens at 20, 48 at 3; a masked sum over its rows), lays them
  block-diagonal ``[H, KD]`` in a VMEM scratch, and walks the groups with
  one ``[H, KD] x [KD, keys]`` and one ``[H, keys] x [keys, VD]`` product an
  update (``VD`` the V side's row); each head's own ``Dv`` lanes go back into
  the token's rows of its plane. The wide tile never leaves VMEM and its zeros are laid once a
  call. A quantized pool's group is upcast for it head window by head
  window, each with its own scale. Only where a query block is no whole
  number of such tiles (a test's block of 5 or 17 tokens) does every span
  take the per-head walk (``pallas_mla_ragged_attention`` has two walks
  too).
- **One output block, several rows**: visits to an output block are
  consecutive; the first zeroes it, each row's visit writes back only its
  own span, by a masked read-modify-write. The last query block may reach
  past the packed buffer: the rows it holds there belong to no span, and
  the buffer is never padded.
- **Tile conservatism**: blocks are ``[Hkv, rows, D]`` with ``rows`` whole
  16-row tiles and ``D`` the full minor dim; compute is plain 2D
  ``dot_general`` on a plane's rows; groups ascending, the per-row state in
  VMEM scratch. A span-1 row is ``paged_decode_attention_pallas``'s row
  within float32 rounding: the same mathematics in another order.

Inference-only (no VJP): the serving step never backpropagates.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_flash import _interpret_mode

NEG_INF = -1e30


def _ragged_kernel(wq_ref, wr_ref, wf_ref, wn_ref, wlo_ref, ws_ref, qs_ref,
                   ql_ref, kl_ref, tbl_ref, layer_ref, *refs, scale, block_k,
                   pages, tq, g, num_blocks, table_entries, quantized=False,
                   window=None, sink=False):
    # positional ref layout follows the pallas_call spec lists: inputs
    # (q, k, v[, k_scale, v_scale][, the sink by plane row, by head]), then
    # the output, then scratch (one
    # two-slot VMEM buffer per pool-side input, the DMA semaphores, m/l/acc
    # and, where the call has the one-token walk, its wide query and state)
    sink_rows = sink_heads = None
    if sink:
        # the sink's logit, a row of a plane and a head of the one-token
        # walk's wide query, on every lane (``_sink_planes``)
        n = 5 if quantized else 3
        sink_rows, sink_heads = refs[n:n + 2]
        refs = refs[:n] + refs[n + 2:]
    if quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf,
         vs_buf, sems, m_scr, l_scr, acc_scr, *one_token) = refs
        streams = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                   (vs_hbm, vs_buf))
    else:
        (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr,
         acc_scr, *one_token) = refs
        ks_buf = vs_buf = None
        streams = ((k_hbm, k_buf), (v_hbm, v_buf))
    # the one-token walk's scratch, where the call has that walk
    tile = _token_tile(q_ref.shape[1], g) if one_token else 0
    if tile:
        qw_scr, m1_scr, l1_scr, acc1_scr = one_token
    hkv, rows, d = q_ref.shape      # KV heads, a plane's rows a block, D
    dv = o_ref.shape[2]             # a value's width (``d`` is a key's)
    w = pl.program_id(0)            # one work-list entry: (query block, row)
    qi = wq_ref[w]
    r = wr_ref[w]
    nkb = wn_ref[w]                 # pool blocks this pair walks (0 = dead)

    def _first_group(e):
        # the first GROUP entry e walks: 0, or under a window the group that
        # holds the first key any of the pair's queries may see
        # (``_pair_first_block``)
        return 0 if window is None else wlo_ref[e] // pages

    glo = _first_group(w)
    # the entries before and after this one (clamped into the list), for
    # the group that crosses the pair boundary (``_groups``)
    before = jnp.maximum(w - 1, 0)
    after = jnp.minimum(w + 1, pl.num_programs(0) - 1)
    handed = (w > 0) & _hands_over(wn_ref[before], nkb)
    hands = (after > w) & _hands_over(nkb, wn_ref[after])
    layer = layer_ref[0]            # which layer of the stored pool
    qstart = qs_ref[r]
    qlen = ql_ref[r]
    kvlen = kl_ref[r]
    row0 = qi * (tq // hkv)         # first plane row of this query block
    span_lo = qstart * g            # span bounds in plane-row coordinates
    span_hi = (qstart + qlen) * g
    group = pages * block_k         # keys of one online-softmax update
    alone = (qlen == 1) if tile else False

    @pl.when(wf_ref[w] == 1)
    def _zero_out():
        # first visit of this output block: packed rows outside every
        # span must come back as exact zeros, not stale VMEM
        o_ref[:] = jnp.zeros_like(o_ref)

    if tile:
        @pl.when(w == 0)
        def _zero_wide():
            # the one-token walk's wide query: only its diagonal windows are
            # ever rewritten (every one, each pair), so the zeros between
            # them are laid once a call
            qw_scr[:] = jnp.zeros(qw_scr.shape, jnp.float32)

    def _copies(row, gi, slot):
        # table-indirect fetch of group gi of `row`, `pages` consecutive table
        # entries, into buffer `slot`: the table is resolved from SMEM at
        # DMA-issue time; entries past the table clamp to its last, and
        # sentinel entries into THIS layer's blocks before the layer is
        # applied (a harmless read, masked by kvlen and the causal rule;
        # never a block of the next layer). The scale planes, this layer's
        # already, ride the SAME block index as their data.
        out = []
        for j in range(pages):
            entry = jnp.minimum(gi * pages + j, table_entries - 1)
            phys = jnp.clip(tbl_ref[row, entry], 0, num_blocks - 1)
            keys = pl.ds(j * block_k, block_k)
            for i, (hbm, buf) in enumerate(streams):
                if hbm.ndim == 4:       # the stored pool [L, nb, bs, KD]
                    src, dst = hbm.at[layer, phys], buf.at[slot, keys]
                elif hbm.ndim == 3:     # int8 planes [nb, bs, lanes]
                    src, dst = hbm.at[phys], buf.at[slot, keys]
                else:                   # fp8 planes [nb, lanes]: one row a
                    src = hbm.at[pl.ds(phys, 1)]        # block, 2D windows
                    dst = buf.at[slot, pl.ds(j, 1)]
                out.append(pltpu.make_async_copy(src, dst,
                                                 sems.at[i, slot, j]))
        return out

    def _head_rows(buf, slot, scales, k, d):
        # head k's [group, d] window of a fetched group: its d lanes of
        # every row (a key's width on the K side, a value's on the V side). A quantized pool's values are upcast HERE, right after
        # the table-indirect DMA moved the narrow dtype (the HBM win), and
        # take the head's scale, column k of the plane: int8 carries one a
        # (pool row, head), the pages' planes lying concatenated; fp8 one a
        # (block, head), spread over the block's rows
        x = buf[slot, :, k * d:(k + 1) * d]
        if not quantized:
            return x
        f = scales[slot, :, k:k + 1]
        if quantized == "fp8":
            f = jnp.broadcast_to(f[:, None, :], (pages, block_k, 1)).reshape(
                group, 1)
        return x.astype(jnp.float32) * f

    def _groups(update):
        # this pair's groups of pool blocks, ascending, double-buffered: the
        # group after gi streams in while ``update(gi, slot)`` computes on
        # gi, and after the pair's LAST group that is the first group of the
        # next work-list entry, where both entries are live (``_hands_over``):
        # its copies overlap this pair's last update, divide and write-back,
        # the grid step, and the next pair's reset and query build; that pair
        # only waits for them. The first group's slot is the work list's
        # (``_first_slots``), so the group before it, whoever's, lies in the
        # other one. Exactly the pair's blocks, `pages` an update: the row's
        # own length and the causal diagonal both already bound nkb
        # (_work_list); what the last group holds past them is masked
        n_groups = (nkb + pages - 1) // pages
        s0 = ws_ref[w]
        r_after, glo_after = wr_ref[after], _first_group(after)

        @pl.when(jnp.logical_not(handed))
        def _first():
            for c in _copies(r, glo, s0):
                c.start()

        def _group(gi, carry):
            slot = (s0 + gi - glo) % 2
            last = gi + 1 == n_groups

            @pl.when(jnp.logical_not(last) | hands)
            def _prefetch():
                for c in _copies(jnp.where(last, r_after, r),
                                 jnp.where(last, glo_after, gi + 1),
                                 1 - slot):
                    c.start()

            for c in _copies(r, gi, slot):
                c.wait()

            # pool rows past `kvlen` may hold another block's garbage (or a
            # clamped entry's), and 0 * NaN is NaN: zero them out of PV, in
            # the one group that can hold any
            @pl.when((gi + 1) * group > kvlen)
            def _zero_stale():
                v = v_buf[slot]
                v_buf[slot] = jnp.where(
                    gi * group + jax.lax.broadcasted_iota(
                        jnp.int32, v.shape, 0) < kvlen, v, jnp.zeros_like(v))
                if quantized:
                    # (their scales too: a plane row is a key's, or for
                    # fp8 a page's, dead where its first key is)
                    f = vs_buf[slot]
                    per = block_k if quantized == "fp8" else 1
                    vs_buf[slot] = jnp.where(
                        gi * group + per * jax.lax.broadcasted_iota(
                            jnp.int32, f.shape, 0) < kvlen, f,
                        jnp.zeros_like(f))

            update(gi, slot)
            return carry

        jax.lax.fori_loop(glo, n_groups, _group, 0)

    def _softmax_update(s, valid, v, m_ref, l_ref, acc_ref):
        # one online-softmax update of the state ``m / l / acc`` (ref views)
        # with the scores ``s`` of a group of keys and their values ``v``:
        # the one-token walk's, on ``H`` rows (``m`` and ``l`` one value a
        # row, spread over its lane tile)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # exp hits exact 0 on masked cols only while the row has a valid
        # one; a row of the block outside the span has none
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    def _span_update(s, valid, v, m_ref, l_ref, acc_ref):
        # the general walk's online-softmax update. A plane's rows are many
        # (128 to 512 a chunk) and its cost was a row's, not a FLOP's: what
        # ``_softmax_update`` does to a lane tile a row (``m`` and ``l`` read
        # at ``[:, :1]`` and spread over the lanes again, three lane
        # broadcasts a row tile of eight on the XLUs, which bound the
        # schedule: PERF.md section 6, PR 53). Here a row's state is never
        # narrowed: ``m`` holds the row's maximum on every lane and is read
        # and written as whole lane tiles, so ``s - m``, ``alpha`` and
        # ``alpha * acc`` are elementwise between registers, and ``l`` holds
        # a row's sum BY LANE (lane j: the keys j, j + 128, ... of every
        # group, rescaled by the same ``alpha``), so an update adds column
        # tiles and the one reduction along the lanes is ``_write_chunk``'s.
        # What is left on the XLUs is the row maximum, one reduction a row
        # tile. The same mathematics in float32; ``l`` summed in another
        # order
        lanes = m_ref.shape[1]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # exp hits exact 0 on masked cols only while the row has a valid
        # one; a row of the block outside the span has none
        p = jnp.where(valid, jnp.exp(s - _lanes(m_new, s.shape[1])), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        pad = -s.shape[1] % lanes
        by_lane = p if not pad else jnp.concatenate(
            [p, jnp.zeros((p.shape[0], pad), p.dtype)], axis=1)
        l_ref[:] = alpha * l_ref[:] + sum(
            by_lane[:, j:j + lanes] for j in range(0, by_lane.shape[1], lanes))
        acc_ref[:] = acc_ref[:] * _lanes(alpha, acc_ref.shape[1]) \
            + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    def _reset(m_ref, l_ref, acc_ref, m0=None, by_lane=False):
        # a softmax with a sink (``m0``, the sink's logit a row on every
        # lane) starts where one update with that column alone would leave
        # it: the maximum at the logit, the sum at 1 (on lane 0 where ``l``
        # lies by lane, ``_span_update``), no value
        if m0 is None:
            m_ref[:] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
            l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        else:
            m_ref[:] = m0
            one = jnp.ones(l_ref.shape, jnp.float32)
            l_ref[:] = one if not by_lane else jnp.where(
                jax.lax.broadcasted_iota(
                    jnp.int32, l_ref.shape, len(l_ref.shape) - 1) == 0,
                one, 0.0)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((nkb > 0) & jnp.logical_not(alone))
    def _span():
        # the general walk, on every plane of the query block: head k's
        # scores and P V from its own D lanes of the group, [rows, D] x
        # [D, group] and [rows, group] x [group, D], a plane taller than
        # ``_PLANE_ROWS`` in static row chunks (``_row_chunks``) so that a
        # score tile stays at most that by a group of keys. A chunk computes
        # only where it holds a row of this pair's span and a key at or
        # under its last row's diagonal: a short span in a tall block (a
        # chunk's spill into its last block, a span of one token without a
        # tile of its own) never computes on the whole block. One chunk is
        # the whole plane, always live (the work list holds only pairs that
        # intersect, and walks them to their diagonal): it takes the whole
        # refs under no predicate and computes no chunk scalars, so a plane
        # of at most ``_PLANE_ROWS`` lowers to the walk it had before the
        # chunks, operation for operation (PERF.md section 6, PR 51).
        chunks = _row_chunks(rows)
        tall = len(chunks) > 1

        def where_live(live):
            return pl.when(live) if tall else (lambda f: f())

        def rows_live(c0, n):
            # (scalars) whether the chunk holds a row of the span, and the
            # position of the last one it holds
            hi = jnp.minimum(row0 + c0 + n, span_hi)
            live = hi > jnp.maximum(row0 + c0, span_lo)
            return live, kvlen - qlen + (hi - 1 - span_lo) // g

        def rows_mask(c0, n):
            # causal-within-span, the same for every head: plane row j
            # belongs to span token (j - span_lo) // g, whose logical
            # position is kvlen - qlen + that token index; a row of the
            # block outside the span sees no key
            prow = (row0 + c0 if c0 else row0) \
                + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
            in_span = (prow >= span_lo) & (prow < span_hi)
            tok = prow - span_lo
            if g > 1:
                tok = tok // g
            return in_span, jnp.where(in_span, kvlen - qlen + tok, -1)

        state = (m_scr, l_scr, acc_scr)
        ats = [slice(c0, c0 + n) if tall else slice(None) for c0, n in chunks]
        lives = [rows_live(*c) if tall else (None, None) for c in chunks]
        for at, (live, _) in zip(ats, lives):
            @where_live(live)
            def _reset_chunk():
                _reset(*(x.at[:, at] if tall else x for x in state),
                       **({} if sink_rows is None else {
                           "m0": sink_rows[:, at] if tall else sink_rows[:],
                           "by_lane": True}))
        parts = [(at, *rows_mask(*c), live, last)
                 for at, c, (live, last) in zip(ats, chunks, lives)]

        def update(gi, slot):
            for at, _, pos, live, last in parts:
                @where_live(tall and live & (gi * group <= last))
                def _chunk():
                    key = jax.lax.broadcasted_iota(
                        jnp.int32, (pos.shape[0], group), 1)
                    valid = key <= pos - gi * group
                    if window is not None:
                        # the window's edge, inside the first group walked
                        valid = valid & (key > pos - window - gi * group)
                    for k in range(hkv):
                        s = jax.lax.dot_general(
                            q_ref[k, at, :],
                            _head_rows(k_buf, slot, ks_buf, k, d),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
                        _span_update(
                            s, valid, _head_rows(v_buf, slot, vs_buf, k, dv),
                            m_scr.at[k, at], l_scr.at[k, at],
                            acc_scr.at[k, at])

        _groups(update)
        # write back ONLY this row's span: the output block is shared by
        # every sequence whose span intersects it, so the write is a masked
        # read-modify-write (rows not in the span keep their value)
        for at, in_span, _, live, _ in parts:
            @where_live(live)
            def _write_chunk():
                for k in range(hkv):
                    # (``l`` lies by lane: ``_span_update``)
                    out = acc_scr[k, at] / jnp.maximum(jnp.sum(
                        l_scr[k, at], axis=1, keepdims=True), 1e-30)
                    o_ref[k, at, :] = jnp.where(
                        in_span, out.astype(o_ref.dtype), o_ref[k, at, :])

    if not tile:
        return

    @pl.when((nkb > 0) & alone)
    def _one_token():
        # a span of ONE token (a decode row) is bound by its KV bytes: a
        # batched matrix-vector product whose G rows a KV head would each
        # make a product of their own, Hkv small products an update. It
        # takes ONE product over the whole pool row instead: the token's H
        # query rows, cut out of the head-major block once a pair, laid
        # block-diagonal ([H, KD], head h's D values at its KV head's lanes)
        # in VMEM, so the zeros it multiplies cost the MXU nothing it would
        # not idle through and the softmax works on H rows, not on a row
        # tile a plane.
        hp = qw_scr.shape[0]
        # the token's rows lie in the aligned row tile at ``toff`` of every
        # plane, from row ``first`` of the tile
        toff = pl.multiple_of((span_lo - row0) // tile * tile, tile)
        first = span_lo - row0 - toff
        at = pl.ds(toff, tile)
        trow = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        for k in range(hkv):
            t = q_ref[k, at, :].astype(jnp.float32)
            for j in range(g):
                # (a masked sum over the tile's rows: exact, one term)
                qw_scr[pl.ds(k * g + j, 1), k * d:(k + 1) * d] = jnp.sum(
                    jnp.where(trow == first + j, t, 0.0), axis=0,
                    keepdims=True)
        _reset(m1_scr, l1_scr, acc1_scr,
               **({} if sink_heads is None else {"m0": sink_heads[:]}))

        def pool_rows(buf, scales, slot, d):
            # the whole fetched group [group, KD]; a quantized pool's head
            # windows upcast and scaled one by one (``_head_rows``) and laid
            # side by side again
            if not quantized:
                return buf[slot]
            return jnp.concatenate(
                [_head_rows(buf, slot, scales, k, d) for k in range(hkv)],
                axis=1)

        def update(gi, slot):
            qw = qw_scr[:] if quantized else qw_scr[:].astype(q_ref.dtype)
            s = jax.lax.dot_general(
                qw, pool_rows(k_buf, ks_buf, slot, d),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            key = gi * group + jax.lax.broadcasted_iota(
                jnp.int32, (hp, group), 1)
            valid = key < kvlen
            if window is not None:
                valid = valid & (key >= kvlen - window)
            _softmax_update(s, valid, pool_rows(v_buf, vs_buf, slot, dv),
                            m1_scr, l1_scr, acc1_scr)

        _groups(update)
        acc1_scr[:] = acc1_scr[:] / jnp.maximum(l1_scr[:, :1], 1e-30)
        for k in range(hkv):
            # head k * g + j's own D lanes back into the token's rows of
            # plane k; the tile's other rows belong to other spans
            new = o_ref[k, at, :].astype(jnp.float32)
            for j in range(g):
                new = jnp.where(
                    trow == first + j,
                    acc1_scr[pl.ds(k * g + j, 1), k * dv:(k + 1) * dv],
                    new)
            o_ref[k, at, :] = new.astype(o_ref.dtype)


def _lanes(x, n):
    """``x [rows, 128]``, a row's one value on every lane, as ``[rows, n]``:
    itself at a lane tile, whole copies side by side at several, a cut of
    one under it (a test's narrow head or short group)."""
    if n != x.shape[1]:
        x = jnp.concatenate([x] * -(-n // x.shape[1]), axis=1)[:, :n]
    return x


#: rows of the smallest row tile every query dtype loads whole (bf16 packs
#: 16 rows a tile)
_ROW_TILE = 16


def _token_tile(plane_rows, g):
    """The aligned row tile of a plane that holds whole tokens' ``g`` rows in
    whole row tiles, ``lcm(16, g)`` rows (16 at a group of 1, 2, 4, 8 or 16;
    80, four tokens in five row tiles, at 20; 48 at 3), where a query block
    of ``plane_rows`` is whole such tiles, so no token straddles two; else
    0: the kernel then has no walk of its own for a span of one token."""
    tile = math.lcm(_ROW_TILE, g)
    return tile if plane_rows % tile == 0 else 0


def _row_chunks(plane_rows):
    """The static ``(first row, rows)`` chunks in which the general walk
    takes a plane of ``plane_rows``: as few as hold at most ``_PLANE_ROWS``
    rows each, of equal size in whole row tiles (the last may be shorter).
    One chunk, the whole plane, up to ``_PLANE_ROWS``; 8 of 480 rows (24
    tokens) at a group of 20 and 192 tokens, 4 of 512 at 16 and 128."""
    n = -(-plane_rows // _PLANE_ROWS)
    size = -(-plane_rows // (n * _ROW_TILE)) * _ROW_TILE
    return [(c0, min(size, plane_rows - c0))
            for c0 in range(0, plane_rows, size)]


def _one_token_walk(gh, tq):
    """Whether a span of one token computes on its own ``gh`` wide rows
    alone in a kernel that tiles WIDE rows (``pallas_mla_ragged_attention``):
    where those are whole tiles and fewer than the query block."""
    return gh % 16 == 0 and gh < tq


#: what an online-softmax update takes: at most ``_GROUP_KEYS`` keys, fewer
#: where the K and V buffers of that many (two slots each) would pass
#: ``_GROUP_BYTES``, never fewer than ``_LANE_KEYS``. Settled on the chip
#: (PERF.md, PR 32 and 36): a decode row fetches half a group past its end, so
#: past 256 keys, and at a wide row past 128, the dead tail costs a short
#: walk more than the fewer rescales save a long one; under a lane tile of
#: keys a head's score tile wastes the lanes it has (Olmo-Hybrid's chunk
#: kernel 1.84 ms at 64 keys an update, 1.45 at 128)
_GROUP_KEYS = 256
_GROUP_BYTES = 2 << 20
_LANE_KEYS = 128

#: what a query block holds: as many whole row tiles of tokens as keep the
#: float32 accumulator of all planes, ``[Hkv, tokens * G, D]``, under
#: ``_ACC_BYTES``; the general walk takes a plane of it in row chunks of at
#: most ``_PLANE_ROWS`` (one head's float32 score tile is that by a group of
#: keys: 512 KiB at 256)
_PLANE_ROWS = 512
_ACC_BYTES = 2 << 20

#: the scoped VMEM the call asks for: the accumulator, the softmax state at
#: a lane tile a row, two query and two output blocks, the K and V buffers
#: and a few score tiles (14 MiB at Mistral's geometry, 16 at Olmo-Hybrid's;
#: at the two geometries whose block the accumulator alone sizes, Jamba2-3B's
#: 20 / 1 / 128 at 192 tokens: accumulator, ``m`` and ``l`` 1.9 MiB each,
#: four blocks of 0.94 MiB, one score tile of 480 KiB and its temporaries,
#: about 12 MiB; Nemotron-3-Nano's 32 / 2 / 128 at 128 tokens: three times
#: 2 MiB, four blocks of 1 MiB, about 13 MiB)
_VMEM_BYTES = 48 << 20


def pages_per_update(pool_dtype, block_size, kd, table_entries, vd=None):
    """Table entries one online-softmax update fetches and computes on
    together, from what a call can observe: ``_GROUP_KEYS`` keys' worth of
    pool blocks, fewer where a row is wide but no fewer than a lane tile of
    keys (8 blocks of 32 for Mistral's ``KD`` 1024 in bf16, 4 for OLMoE's
    2048 and Olmo-Hybrid's 3840), never more than the table holds. A row is
    its K side and its V side (``vd``; None: as wide as ``kd``), two slots
    each. A one-byte pool counts at four bytes: a head's window is upcast to
    float32 in VMEM on its way into the MXU."""
    itemsize = jnp.dtype(pool_dtype).itemsize
    row = 2 * (int(kd) + int(kd if vd is None else vd)) \
        * (4 if itemsize == 1 else itemsize)
    keys = max(_LANE_KEYS, min(_GROUP_KEYS, _GROUP_BYTES // row))
    return max(1, min(keys // int(block_size), int(table_entries)))


def query_block_rows(heads, head_dim):
    """(Token, head) rows of a query block (before ``_query_block`` fits it
    to the packed buffer), from the state the block carries: as many tokens
    as the float32 accumulator ``[Hkv, tokens * G, D]`` holds under
    ``_ACC_BYTES`` (``head_dim`` is a VALUE's width where keys and values
    differ; a row of it is no narrower than a lane tile), in whole
    row tiles of tokens, so in whole row tiles of whole tokens at any group.
    128 tokens at Mistral's 32 / 8 / 128 (a 512-token chunk walks its prefix
    4 or 5 times) and Olmo-Hybrid's 30 / 30 / 128, 256 at OLMoE's 16 / 16 /
    128, 192 at Phi-4-mini-flash's 20 / 10 / 128 and at Jamba2-3B's 20 / 1 /
    128 (3,840 rows of its one plane, 3 walks), 128 at Nemotron-3-Nano's 32
    / 2 / 128 (2,048 rows a plane). How tall a plane is decides how the walk
    chunks it (``_row_chunks``), not how much a block holds. The lane tile
    is what ``m`` and ``l`` cost a row whatever ``D`` is, so heads narrower
    than 128 that the kernel sees unpaired get another block than one cut to
    512 rows of a plane held (16 / 16 / 64: 256 tokens for 512); no cell has
    them."""
    tokens = _ACC_BYTES // (4 * int(heads) * max(int(head_dim), 128))
    return max(_ROW_TILE, tokens // _ROW_TILE * _ROW_TILE) * int(heads)


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


def _pair_first_block(qs, ql, kl, qi, *, tokens_per_block, block_size,
                      window, pages):
    """The first KV block the pair (query block ``qi``, a live row) walks:
    0 with no window; else the block of the first key the window of the
    FIRST span token inside the query block holds, moved down to a whole
    group of ``pages`` blocks (the kernel's walk starts at a group). Ints
    and broadcasting int arrays alike, as ``_pair_kv_blocks``."""
    if window is None:
        return 0 * (qs + qi)
    first = qi * tokens_per_block
    first = first + (qs > first) * (qs - first)
    lo = kl - ql + (first - qs) - (int(window) - 1)
    lo = lo * (lo > 0)
    return lo // block_size // pages * pages


def _hands_over(n_before, n):
    """Whether a work-list entry's first group of pool pages is started by
    the entry BEFORE it, from the KV blocks the two walk: both live (two
    pairs in a row; nothing crosses a dead entry, so every copy a call
    starts is waited for inside it). Ints, traced scalars and arrays alike:
    the kernel, the work list's slots and the host's counter share this one
    rule."""
    return (n_before > 0) & (n > 0)


def _first_slots(wn, wlo, pages):
    """The buffer slot of each work-list entry's FIRST group, from the
    list's own arrays: the groups every earlier entry walks, ``ceil(wn /
    pages) - wlo // pages`` each (``wlo`` is whole groups), mod 2. The walk
    alternates slots from there, so the last group of the pair before lies
    in the other slot whatever its count, and the group that crosses the
    pair boundary lands where the next pair looks for it. One more
    scalar-prefetched array and not a word of SMEM the kernel keeps: a pure
    function of the list, which a test can hold against an enumeration, and
    ``_work_list`` itself stays what ``pallas_mla_ragged_attention``, whose
    walk still drains at a pair's end, unpacks."""
    walked = jnp.where(wn > 0, -(-wn // pages) - wlo // pages, 0)
    return (jnp.cumsum(walked) - walked) % 2


def _work_list(qstart, qlen, kvlen, *, nq, tokens_per_block, block_size,
               table_entries, window=None, pages=1):
    """The kernel's iteration space, from the step's span metadata (jnp,
    inside the jitted program): ``nq + R`` entries ``(query block, row,
    first visit of its output block, KV blocks to walk)`` and, under a
    ``window``, a fifth array, the first KV block to walk, ordered by query
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
    if window is None:
        return wq, wr, first.astype(jnp.int32), wn
    lo_flat = jnp.concatenate([jnp.where(inter, _pair_first_block(
        qs, ql, kl, qi, tokens_per_block=tokens_per_block,
        block_size=block_size, window=window, pages=pages), 0),
        jnp.zeros((nq, 1), jnp.int32)], axis=1).reshape(-1)
    wlo = jnp.where(pad, 0, jnp.sum(
        jnp.where(pick, lo_flat[None, :], 0), axis=1))
    return wq, wr, first.astype(jnp.int32), wn, wlo


def _ragged_call(q_hm, pool_k, pool_v, layer, tables, qstart, qlen, kvlen,
                 scale, g, block_q, pages, interpret, scales=None,
                 window=None, sink=None):
    """q_hm: [Hkv, T * g, D] head-major planes (row ``t * g + j`` of plane
    ``k`` is head ``k * g + j`` of token ``t``);
    pool_*: the stored pool ``[L, num_blocks, bs, KD]``, left in HBM whole
    (the V side ``Hkv * Dv`` wide, a value's width its own: the accumulator
    and the output ``[Hkv, T * g, Dv]`` are then a value's);
    sink: None, or ``[Hkv * g]`` float32, a head's sink logit: one more
    column of its softmax that has no value (``_sink_planes``);
    layer: [1] int32, the layer whose blocks this call reads;
    tables: [R, max_blocks] int32;
    scales: None, or ``(k_scale, v_scale)`` fp32 planes for a
    quantized pool (upcast in-kernel, right after the table-indirect
    DMA): [L, num_blocks, bs, Hkv] per-row planes select the int8 path,
    [L, num_blocks, Hkv] per-block planes select fp8 — the plane rank IS
    the mode switch, same convention as ``pallas_paged_decode``;
    block_q, pages: the call's tiling (``grid_params``), the query block in
    (token, head) rows.

    The grid is the work list (``_work_list``): one step per (query block,
    row) pair, and inside it a loop over exactly the pair's KV blocks,
    fetched at ``(layer, table entry)``, ``pages`` of them an iteration."""
    hkv, TG, D = q_hm.shape
    KD, VD = pool_k.shape[-1], pool_v.shape[-1]
    Dv = VD // hkv
    num_blocks, bs = pool_k.shape[1], pool_k.shape[2]
    R, nk = tables.shape
    tokens = block_q // (hkv * g)   # a query block's tokens
    nq = -(-TG // (tokens * g))     # the last block may be partial
    rows = _plane_rows(block_q, hkv * g, g, TG // g)
    work = _work_list(qstart, qlen, kvlen, nq=nq, tokens_per_block=tokens,
                      block_size=bs, table_entries=nk, window=window,
                      pages=pages)
    if window is None:      # one signature: a first block of 0, never read
        work += (jnp.zeros_like(work[-1]),)
    work += (_first_slots(work[3], work[4], pages),)
    if scales is None:
        quantized = False
    else:
        quantized = "fp8" if scales[0].ndim == 3 else "int8"
    kernel = functools.partial(_ragged_kernel, scale=scale, block_k=bs,
                               pages=pages, tq=block_q, g=g,
                               num_blocks=num_blocks, table_entries=nk,
                               quantized=quantized, window=window,
                               **({} if sink is None else {"sink": True}))

    def _q_index(w, wq, *_):
        return (0, wq[w], 0)

    in_pool = pl.BlockSpec(memory_space=pl.ANY)     # fetched by the kernel
    in_specs = [pl.BlockSpec((hkv, rows, D), _q_index), in_pool, in_pool]
    args = [*work, qstart, qlen, kvlen, tables, layer, q_hm, pool_k, pool_v]
    bufs = [pltpu.VMEM((2, pages * bs, KD), pool_k.dtype),
            pltpu.VMEM((2, pages * bs, VD), pool_v.dtype)]
    if quantized:
        # per-row int8 planes [nb, bs, hkv] move one [bs, hkv] block a page,
        # per-BLOCK fp8 planes [nb, hkv] one [1, hkv] row. A DMA window's
        # minor dim must be whole lanes, so this layer's planes are cut out
        # and padded to 128 heads here and the kernel reads column k
        lanes = -(-hkv // 128) * 128
        scales = [jnp.pad(jax.lax.dynamic_index_in_dim(p, layer[0], 0, False),
                          [(0, 0)] * (p.ndim - 2) + [(0, lanes - hkv)])
                  for p in scales]
        plane = pages * (1 if quantized == "fp8" else bs)
        in_specs += [in_pool, in_pool]
        args += scales
        bufs += [pltpu.VMEM((2, plane, lanes), p.dtype) for p in scales]
    hp = -(-hkv * g // _ROW_TILE) * _ROW_TILE   # the heads, whole row tiles
    if sink is not None:
        # fetched once a call: neither block's index ever moves
        planes = _sink_planes(sink, hkv, g, rows, hp)
        in_specs += [pl.BlockSpec(p.shape, lambda *_, n=p.ndim: (0,) * n)
                     for p in planes]
        args += planes
    scratch = bufs + [
        pltpu.SemaphoreType.DMA((len(bufs), 2, pages)),
        pltpu.VMEM((hkv, rows, 128), jnp.float32),
        pltpu.VMEM((hkv, rows, 128), jnp.float32),
        pltpu.VMEM((hkv, rows, Dv), jnp.float32)]
    if _token_tile(rows, g):
        # the one-token walk's block-diagonal query [H, KD] and its softmax
        # state, the heads rounded up to whole row tiles
        scratch += [pltpu.VMEM((hp, KD), jnp.float32),
                    pltpu.VMEM((hp, 128), jnp.float32),
                    pltpu.VMEM((hp, 128), jnp.float32),
                    pltpu.VMEM((hp, VD), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=11,
            grid=(nq + R,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((hkv, rows, Dv), _q_index),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((hkv, TG, Dv), q_hm.dtype),
        # consecutive entries revisit one output block (accumulated
        # across rows by the masked write) — no reordering allowed
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="ragged_paged_attention",
    )(*args)


def _sink_planes(sink, hkv, g, rows, hp):
    """A head's sink logit as the two walks read it, float32 on every lane
    of a lane tile: ``[Hkv, rows, 128]`` by plane row (row ``j`` of plane
    ``k`` is head ``k * g + j % g``: a query block starts at a whole token)
    for the general walk, ``[hp, 128]`` by head for the one-token walk's wide
    rows (the padding heads 0)."""
    sink = jnp.asarray(sink, jnp.float32).reshape(hkv, 1, g, 1)
    by_row = jnp.broadcast_to(sink, (hkv, -(-rows // g), g, 128)).reshape(
        hkv, -1, 128)[:, :rows]
    by_head = jnp.pad(jnp.broadcast_to(sink.reshape(hkv * g, 1),
                                       (hkv * g, 128)),
                      ((0, hp - hkv * g), (0, 0)))
    return [by_row, by_head]


def _query_block(block_q, heads, packed_tokens):
    """The query block in wide rows: a multiple of ``heads`` (so //gh never
    crosses a pad boundary), at most the whole packed buffer."""
    return max(heads, min(int(block_q) // heads * heads,
                          packed_tokens * heads))


def _plane_rows(block_q, heads, g, packed_tokens):
    """Rows of one KV head's plane in a query block of ``block_q`` (token,
    head) rows: ``g`` a token; the one block of a buffer it covers is whole
    row tiles (it may reach past the buffer, like any last block)."""
    tokens = block_q // heads
    rows = tokens * g
    return -(-rows // _ROW_TILE) * _ROW_TILE if tokens >= packed_tokens \
        else rows


def grid_params(pool_dtype, block_size, kd, table_entries, heads,
                packed_tokens, block_q=None, pages=None, *, head_dim,
                value_dim=None):
    """The tiling of one call, ``{"block_q", "pages", "one_token"}``: the
    query block in (token, head) rows as the call cuts it, the table entries
    one online-softmax update takes, and whether a span of one token
    computes on its own row tile (``_token_tile``), from what the call
    observes (``block_q`` / ``pages`` given: fitted like the derived ones;
    ``head_dim`` a key's width and ``value_dim`` a value's where it is
    another: the state a block carries and the V side of a pool row are then
    that wide). The ONE derivation: ``ragged_paged_attention_pallas`` tiles with it and
    the engine passes it to ``ragged_grid_counts``, so the host's counts are
    the kernel's."""
    g = int(heads) * int(head_dim) // int(kd)
    if block_q is None:
        block_q = query_block_rows(heads, value_dim or head_dim)
    if pages is None:
        pages = pages_per_update(
            pool_dtype, block_size, kd, table_entries,
            None if value_dim is None else int(heads) // g * int(value_dim))
    block_q = _query_block(block_q, heads, packed_tokens)
    return {"block_q": block_q,
            "pages": max(1, min(int(pages), int(table_entries))),
            "one_token": bool(_token_tile(
                _plane_rows(block_q, heads, g, packed_tokens), g))}


def ragged_grid_counts(qstart, qlen, kvlen, *, heads, block_size,
                       table_entries, packed_tokens, block_q=256, pages=1,
                       one_token=False, window=None, kv_heads=None):
    """What one call of the kernel is asked to do, counted on the host from
    the step's span metadata (plain integers; no jax): ``grid_steps``, the
    steps the kernel visits — the ``nq + R`` work-list entries of its grid
    plus one per KV block its in-kernel loops walk; ``live_steps``, those
    that compute (the pool blocks fetched: a work-list entry itself only
    zeroes, resets or writes back); ``update_steps``, the online-softmax
    updates that takes at ``pages`` blocks an update (``pages_per_update``;
    a pair's last group may hold fewer); ``one_token_rows``, the rows that
    compute on their own rows and not on the query block (spans of one
    token, where the kernel has that walk: ``one_token``, as the kernel's
    ``grid_params`` gives it); ``span_row_groups``, what the GENERAL walk's
    updates work on: the sum, over its live (row chunk, update) pairs, of
    the chunk's rows in every plane (``_row_chunks`` of the block's plane and
    ``_span``'s own two predicates: the chunk holds a row of the pair's
    span, and the update's first key lies at or under the chunk's last such
    row; one chunk is always live), which needs ``kv_heads`` and is 0
    without it (the latent kernel has no such walk); ``kv_tokens``, the
    cache rows the live spans attend
    over; ``attn_pairs``, their causal (query, key) pairs;
    ``prefetched_pairs``, the (query block, row) pairs whose first group of
    pool pages the pair before them in the work list started while it still
    computed (``_hands_over``, the kernel's own rule, on the list's order:
    by query block, then row, an untouched query block one dead entry). A
    row with ``qlen == 0`` is dead. Under a ``window`` a pair's walk starts
    at its first group (``_pair_first_block``) and ``kv_tokens`` /
    ``attn_pairs`` count the keys inside the window."""
    bq = _query_block(block_q, heads, packed_tokens)
    nq = -(-(packed_tokens * heads) // bq)
    tpb = bq // heads
    live = updates = alone = kv_tokens = pairs = row_groups = 0
    walks = [[] for _ in range(nq)]     # a query block's pairs, by row
    pages, hkv = int(pages), int(kv_heads or 0)
    g = heads // hkv if hkv else 0
    chunks = _row_chunks(_plane_rows(bq, heads, g, packed_tokens)) if g \
        else []
    group = pages * int(block_size)
    for qs, ql, kl in zip(qstart, qlen, kvlen):
        qs, ql, kl = int(qs), int(ql), int(kl)
        if ql <= 0:
            continue
        if window is None:
            kv_tokens += kl
            pairs += ql * (kl - ql) + ql * (ql + 1) // 2
        else:
            kv_tokens += min(kl, ql + int(window) - 1)
            pairs += sum(min(p + 1, int(window))
                         for p in range(kl - ql, kl))
        alone += ql == 1 and one_token
        for qi in range(qs // tpb, min(nq, -(-(qs + ql) // tpb))):
            n = _pair_kv_blocks(
                qs, ql, kl, qi, tokens_per_block=tpb,
                block_size=block_size, table_entries=int(table_entries))
            walks[qi].append(n)
            first = _pair_first_block(
                qs, ql, kl, qi, tokens_per_block=tpb, block_size=block_size,
                window=window, pages=pages) * (n > 0)
            live += n - first
            updates += -(-(n - first) // pages)
            if ql == 1 and one_token:
                continue
            # the general walk: ``_span``'s ``rows_live`` and its
            # ``gi * group <= last``, in plane rows of the query block
            row0, glo, n_groups = qi * tpb * g, first // pages, -(-n // pages)
            for c0, rows in chunks:
                hi = min(row0 + c0 + rows, (qs + ql) * g)
                if len(chunks) == 1:
                    walked = n_groups - glo
                elif hi > max(row0 + c0, qs * g):
                    last = kl - ql + (hi - 1 - qs * g) // g
                    walked = min(n_groups, last // group + 1) - glo
                else:
                    continue
                row_groups += hkv * rows * max(walked, 0)
    entries = [n for ns in walks for n in (ns or [0])]
    return {"grid_steps": nq + len(qstart) + live, "live_steps": live,
            "update_steps": updates, "one_token_rows": alone,
            "span_row_groups": row_groups,
            "kv_tokens": kv_tokens, "attn_pairs": pairs,
            "prefetched_pairs": int(sum(map(_hands_over, entries,
                                                entries[1:])))}


# Inference-only custom_vjp, same rationale as pallas_paged_decode: the
# eager dispatch linearizes through every op and scalar-prefetch
# pallas_calls don't linearize in interpret mode. ``scales`` is ``()`` or
# the ``(k_scale, v_scale)`` planes of a quantized pool.
@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13, 14))
def _ragged(q_hm, pool_k, pool_v, scales, sink, layer, tables, qstart, qlen,
            kvlen, scale, g, block_q, pages, window):
    return _ragged_call(q_hm, pool_k, pool_v, layer, tables, qstart, qlen,
                        kvlen, scale, g, block_q, pages, _interpret_mode(),
                        scales=scales or None, window=window,
                        sink=sink[0] if sink else None)


def _ragged_fwd_rule(q_hm, pool_k, pool_v, scales, sink, layer, tables,
                     qstart, qlen, kvlen, scale, g, block_q, pages, window):
    return _ragged(q_hm, pool_k, pool_v, scales, sink, layer, tables, qstart,
                   qlen, kvlen, scale, g, block_q, pages, window), None


def _ragged_bwd_rule(scale, g, block_q, pages, window, res, ct):
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
                                  v_scale=None, layer=None, pages=None,
                                  window=None, sink=None):
    """Mixed prefill+decode attention over packed query spans through
    per-sequence block tables.

    q:        [T, H, D]              — the packed query buffer
    pool_k, pool_v, layer: the KV block pool and the layer to read
              (``_stored_pool``): the step programs pass the stored pool
              whole and a traced ``layer``, and the kernel fetches blocks
              from it where it lies. ``D`` is a KEY's width; the V side may
              be narrower or wider, ``Hkv * Dv`` (192 | 128: MiMo-V2-Flash),
              and the output is then ``[T, H, Dv]``
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
    window:   None, or a static int: a query at position ``p`` sees keys
              ``p - window < j <= p`` only; a pair's walk then starts at the
              group of blocks that holds its first visible key
              (``_pair_first_block``) and the edge is masked inside it. With
              None the work list and the walk are the unwindowed ones
    sink:     None, or ``[H]`` float32: head ``h``'s softmax has one more
              column, of logit ``sink[h]`` (as it is: not scaled) and no
              value, so a row's probabilities sum to less than one. The
              online softmax STARTS at it (``m = sink``, ``l = 1``), in both
              walks, with and without a window
    returns:  [T, H, Dv]; packed rows outside every span are exact zeros

    GQA is resolved by the layout: the query goes in head-major, ``[Hkv,
    T * G, D]`` (a transpose of ``T * H * D`` elements each way), and the
    kernel multiplies each KV head's keys by that head's ``G`` queries a
    token only. The kernel iterates over a work list of the
    (query block, row) pairs that intersect, built here from ``qstart`` /
    ``qlen``, and for each pair over the KV blocks up to the row's
    ``kvlen`` and the causal diagonal: blocks past either are never
    fetched, dead rows and non-intersecting pairs are never visited;
    sentinel table entries clamp harmlessly. ``block_q`` (the query block's
    (token, head) rows; None: ``query_block_rows``) and ``pages`` (table
    entries an online-softmax update takes; None: ``pages_per_update``) are
    for tests: the step programs pass neither. A span of length 1 is
    ``paged_decode_attention_pallas``'s row within float32 rounding (the
    same mathematics, a head's sums taken on their own).
    """
    T, H, D = q.shape
    pool_k, pool_v, scales, layer = _stored_pool(pool_k, pool_v, k_scale,
                                                 v_scale, layer)
    KD = pool_k.shape[-1]
    Hkv = KD // D
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    Dv = pool_v.shape[-1] // Hkv
    scale = 1.0 / math.sqrt(D)
    qstart = jnp.asarray(qstart, jnp.int32).reshape(-1)
    qlen = jnp.asarray(qlen, jnp.int32).reshape(-1)
    kvlen = jnp.asarray(kvlen, jnp.int32).reshape(-1)
    tables = jnp.asarray(tables, jnp.int32).reshape(qstart.shape[0], -1)
    # the query block is whole tokens; the last one may reach past the
    # packed buffer (no pad, no copy: the rows it holds past the end belong
    # to no span and are neither computed on nor written back)
    tiling = grid_params(pool_k.dtype, pool_k.shape[2], KD, tables.shape[1],
                         H, T, block_q, pages, head_dim=D,
                         value_dim=None if Dv == D else Dv)
    q_hm = q.reshape(T, Hkv, G, D).swapaxes(0, 1).reshape(Hkv, T * G, D)
    out = _ragged(q_hm, pool_k, pool_v, scales,
                  () if sink is None else (jnp.asarray(sink, jnp.float32),),
                  layer, tables, qstart, qlen,
                  kvlen, scale, G, tiling["block_q"], tiling["pages"],
                  None if window is None else int(window))
    return out.reshape(Hkv, T, G, Dv).swapaxes(0, 1).reshape(T, H, Dv)


def ragged_attention_reference(q, pool_k, pool_v, tables, qstart, qlen,
                               kvlen, k_scale=None, v_scale=None, layer=None,
                               window=None, sink=None):
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
    v_rows = blocks(pool_v).reshape(R, s_tot, Hkv, -1)
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
    if window is not None:
        mask = mask & (cols[None, :] > pos[:, None] - int(window))
    logits = jnp.einsum("qhd,qkhd->qhk", q, kf,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    if sink is None:
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        # the sink: one more column of a head's softmax, with no value
        col = jnp.broadcast_to(jnp.asarray(sink, jnp.float32)[None, :, None],
                               (T, H, 1))
        probs = jax.nn.softmax(jnp.concatenate([logits, col], -1),
                               axis=-1)[..., :-1]
    # exact zeros on masked cols + zeroed garbage rows: stale pool rows
    # can be anything (0 * NaN = NaN)
    probs = jnp.where(mask[:, None, :], probs, 0.0)
    row_valid = cols[None, :] < jnp.take(kvlen, seg)[:, None]
    vf = jnp.where(row_valid[:, :, None, None], vf, 0.0)
    out = jnp.einsum("qhk,qkhd->qhd", probs.astype(q.dtype), vf)
    return jnp.where(live[:, None, None], out, jnp.zeros_like(out))
