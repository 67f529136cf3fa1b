"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) over a recurrent
state that lives in a store beside the paged KV pool.

A head's state ``S`` is ``[dk, dv]`` float32, ``H`` heads a (layer, slot). A
token ``t`` with normalised query and key ``q_t, k_t`` (``dk``), value ``v_t``
(``dv``), log-decay ``g_t <= 0`` and write strength ``beta_t`` (0..2 with
negative eigenvalues allowed, arXiv:2411.12537) does

    S = exp(g_t) * S;  r = v_t - S^T k_t;  S = S + k_t (beta_t r)^T;
    o_t = S^T q_t

**Grouped key heads.** ``q`` and ``k`` may arrive at FEWER heads than ``v``
(``Hk`` dividing ``H``; Qwen3-Next: 16 under 32): value head ``h`` reads key
head ``h // (H / Hk)``. Both kernels take them as they are, once: the chunk
scan's grid step holds a whole number of key heads and their value heads, the
update reads column ``h // (H / Hk)`` of a row's keys; the two ``jnp`` forms
repeat them.

**The store's layout** (``state_shape``; ``state_to_store`` /
``state_from_store`` are its one statement): a (layer, slot) holds ``[dk, H
dv]``, the key width on the SUBLANES and every head's values side by side on
the LANES (``[96, 5760]`` at the published 30 / 96 / 192: 45 whole lane
tiles), the Mamba-2 store's form (``kernels.ssd``). The device tiles the last
two dimensions (8, 128): with ``[H, dk, dv]`` (until PR 49) a minor dimension
of 192 lay in 256 lanes, and a decode row's update, which is bound by the
copy of its state in and out, moved a third more bytes than the state holds.
Now only the last lane tile of a (layer, slot) can be partial, whatever the
widths. What varies along a state vreg's lanes is what a token brings as a
lane-dense row (``v``, and ``exp(g)`` and ``beta`` repeated over their head's
``dv`` lanes); its key and query are a column a head, and a lane tile that
two heads share (the boundary at lane 192 lies inside a tile) takes one
select between their columns.

Three implementations, one semantics:

- ``gdn_reference``: the recurrence run token by token over a packed buffer
  (the oracle; the serving programs' ``decode_attention="jnp"`` path), the
  mathematics above on ``[H, dk, dv]``, the store read and written through
  the two helpers.
- ``gdn_chunk_scan`` (Pallas, and ``gdn_chunk_scan_jnp``, the same walk in
  ``jax.numpy``): spans of a prefill chunk, from each slot's state, in
  chunks of ``CHUNK`` tokens in the WY / UT form. With ``G`` the running sum
  of ``g`` inside a chunk, ``A[i, j] = beta_i exp(G_i - G_j) (k_i . k_j)``
  for ``j < i`` and ``B_i = beta_i (v_i - exp(G_i) S0^T k_i)``, the rows
  ``W = (I + A)^-1 B`` are every token's write, and

      O = (exp(G) * Q) S0 + (M * Q K^T) W,   M[i, j] = exp(G_i - G_j), j <= i
      S1 = exp(G_end) S0 + (exp(G_end - G) * K)^T W

  Decays appear only as differences ``exp(G_i - G_j)`` with ``i >= j``: at
  ``g`` = -1.6 a token ``exp(-G)`` leaves float32 inside one chunk. The unit
  lower-triangular solve runs on the MXU: the diagonal blocks of 16 are
  inverted by doubling (``(I - D)(I + D^2)(I + D^4)(I + D^8)``, exact since
  ``D^16 = 0``), the four blocks of a chunk joined by the finite series of
  the block-strictly-lower remainder.
- ``gdn_recurrent_update`` (Pallas): every decode row of a step in one
  call, the state aliased in and out, on the VPU (a state is a fresh
  operand every row: the MXU would reload its weights 30 times a row), one
  lane tile of the state at a time: the sums over ``dk`` are vreg adds down
  the sublanes, and no lane is reduced or moved.

**Work follows live spans.** Both kernels walk a list built on the device
from the spans: the chunk scan one entry a (span, 64-token block of the
packed buffer it touches), the update one entry a live row. Entries past the
list's end repeat the last live entry's block indices (no DMA) and skip the
body, so a decode-only step runs no scan over the dead chunk rows. A span
and its neighbour may share a block of the packed buffer: each entry masks
the rows that are not its span's (``beta`` 0, ``g`` 0: the state passes them
unchanged). A span marked ``fresh`` (its first position is 0) starts from a
zero state whatever its slot held.

Inference-only (no VJP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_flash import _interpret_mode

#: tokens of one chunk of the scan, and the diagonal blocks its solve inverts
CHUNK = 64
SOLVE_BLOCK = 16
#: heads one grid step of the chunk scan holds (a divisor of the head count
#: is found from here down)
SCAN_HEADS = 6
_HI = jax.lax.Precision.HIGHEST


def l2norm(x, scale=1.0, eps=1e-6):
    """``x / sqrt(|x|^2 + eps) * scale`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)
                * scale)


# ------------------------------------------------------- the store's layout
def state_shape(heads, dk, dv):
    """What a (layer, slot) of the store holds: ``[dk, H dv]``."""
    return (dk, heads * dv)


def state_to_store(s):
    """``[..., H, dk, dv] -> [..., dk, H dv]``."""
    *lead, H, dk, dv = s.shape
    return jnp.moveaxis(s, -3, -2).reshape(*lead, dk, H * dv)


def state_from_store(st, heads):
    """``[..., dk, H dv] -> [..., H, dk, dv]``."""
    *lead, dk, _ = st.shape
    return jnp.moveaxis(st.reshape(*lead, dk, heads, -1), -2, -3)


def _to_value_heads(x, heads):
    """``[T, Hk, d] -> [T, H, d]``: value head ``h`` reads key head ``h //
    (H / Hk)`` (the ``jnp`` forms; the kernels index instead)."""
    rep = heads // x.shape[1]
    return x if rep == 1 else jnp.repeat(x, rep, axis=1)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    return _dot(a, b, (((1,), (1,)), ((), ())))


def _dot_tn(a, b):
    return _dot(a, b, (((0,), (0,)), ((), ())))


def _chunk_math(q, k, v, g, beta, s0):
    """One chunk of one head in the WY / UT form (module docstring).
    q, k ``[C, K]`` normalised float32, v ``[C, dv]``, g, beta ``[C, 1]``
    (a masked row carries ``beta`` 0, ``g`` 0 and zero q, k), s0 ``[K,
    dv]``. Returns ``(o [C, dv], s1 [K, dv])``."""
    C = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    incl, strict = row >= col, row > col
    ones = jnp.ones((C, C), jnp.float32)
    gb = g * ones                                    # [C, C], row i = g_i
    gi = _dot(incl.astype(jnp.float32), gb)          # gi[i, j] = G_i
    gj = _dot(ones, jnp.where(row <= col, gb, 0.0))  # gj[i, j] = G_j
    m = jnp.where(incl, jnp.exp(jnp.where(incl, gi - gj, 0.0)), 0.0)
    g_col = gi[:, :1]                                # [C, 1] G_i
    g_end = gi[C - 1:, :1]                           # [1, 1]
    e_g = jnp.exp(g_col)
    a = jnp.where(strict, beta * m * _dot_nt(k, k), 0.0)
    b = beta * (v - e_g * _dot(k, s0))
    # (I + A)^-1 b: diagonal blocks by doubling, the rest by the finite
    # series of the block-strictly-lower remainder
    eye = (row == col).astype(jnp.float32)
    same = (row // SOLVE_BLOCK) == (col // SOLVE_BLOCK)
    d = jnp.where(same, a, 0.0)
    low = a - d
    td, p = eye - d, _dot(d, d)
    for _ in range(SOLVE_BLOCK.bit_length() - 2):    # D^2, D^4, D^8
        td = _dot(td, eye + p)
        p = _dot(p, p)
    n = _dot(td, low)
    w = y = _dot(td, b)
    for i in range(1, C // SOLVE_BLOCK):
        y = _dot(n, y)
        w = w - y if i % 2 else w + y
    o = e_g * _dot(q, s0) + _dot(m * _dot_nt(q, k), w)
    # (Mosaic broadcasts a [1, 1] along one axis at a time)
    e_end = jnp.exp(jnp.broadcast_to(g_end, (1, s0.shape[1])))
    s1 = e_end * s0 + _dot_tn(k * jnp.exp(g_end - g_col), w)
    return o, s1


def _token_step(s, q, k, v, g, beta):
    """The recurrence of one token on ``s [..., dk, dv]`` (module
    docstring's four assignments). q, k ``[..., dk]``, v ``[..., dv]``, g,
    beta ``[...]``. Returns ``(s', o)``."""
    s = s * jnp.exp(g)[..., None, None]
    r = v - jnp.einsum("...kv,...k->...v", s, k, precision=_HI)
    s = s + k[..., :, None] * (beta[..., None] * r)[..., None, :]
    return s, jnp.einsum("...kv,...k->...v", s, q, precision=_HI)


def gdn_recurrence(q, k, v, g, beta, s0=None):
    """One sequence, token by token: q, k ``[S, H, dk]`` normalised, v
    ``[S, H, dv]``, g, beta ``[S, H]``, s0 ``[H, dk, dv]`` or None (zero).
    Returns ``(o [S, H, dv], s [H, dk, dv])``, float32."""
    f32 = jnp.float32
    if s0 is None:
        s0 = jnp.zeros(q.shape[1:] + v.shape[-1:], f32)

    def step(s, x):
        return _token_step(s, *x)

    s, o = jax.lax.scan(step, s0.astype(f32), tuple(
        x.astype(f32) for x in (q, k, v, g, beta)))
    return o, s


def gdn_reference(q, k, v, g, beta, state, *, layer, seg, first):
    """The oracle over a packed buffer: token ``t`` belongs to slot
    ``seg[t]`` (``R`` = a dead row: nothing is read or written) and
    ``first[t]`` says it is its sequence's position 0 (the slot's state is
    zeroed before it). Decode rows and chunk rows alike, in buffer order. q,
    k ``[T, Hk, dk]`` normalised, v ``[T, H, dv]``, g, beta ``[T, H]``, state
    the store ``[Ll, R, dk, H dv]``. Returns ``(o [T, H, dv] float32,
    state')``."""
    f32 = jnp.float32
    R = state.shape[1]
    seg = jnp.asarray(seg, jnp.int32)
    q, k = (_to_value_heads(x, v.shape[1]) for x in (q, k))

    def step(st, x):
        qt, kt, vt, gt, bt, sg, fr = x
        s = jnp.where(fr, 0.0, st[jnp.minimum(sg, R - 1)])
        s, o = _token_step(s, qt, kt, vt, gt, bt)
        return st.at[sg].set(s, mode="drop"), o

    st, o = jax.lax.scan(step, state_from_store(state[layer], q.shape[1]),
                         tuple(x.astype(f32) for x in (q, k, v, g, beta)) + (
                             seg, jnp.asarray(first, bool)))
    return o, state.at[layer].set(state_to_store(st))


# ------------------------------------------------------------ the chunk scan
def scan_work_items(packed_tokens, num_spans):
    """Entries of the chunk scan's work list (static): every block of the
    packed buffer once, and once more for every span that may start inside
    a block another span ends in."""
    return -(-int(packed_tokens) // CHUNK) + int(num_spans)


def _scan_work(start, length, fresh, n_items):
    """The chunk scan's work list, on the device: one entry a (span, block
    of ``CHUNK`` packed rows it touches), spans in buffer order. Returns
    int32 ``[n_items]`` arrays ``(block, slot, lo, hi, flags)``: rows ``lo
    .. hi`` of the block are the span's; flags bit 0 live, 1 the span's
    first entry, 2 the span is fresh, 3 the first entry on this block.
    Entries past the live ones repeat the last live entry's indices."""
    i32 = jnp.int32
    R = start.shape[0]
    has = length > 0
    order = jnp.argsort(jnp.where(has, start, jnp.iinfo(i32).max))
    s_o, l_o = start[order], length[order]
    has_o = l_o > 0
    fb = s_o // CHUNK
    nb = jnp.where(has_o, (s_o + l_o - 1) // CHUNK - fb + 1, 0)
    cum = jnp.cumsum(nb)
    n_live = cum[-1]
    w = jnp.arange(n_items, dtype=i32)
    we = jnp.clip(jnp.minimum(w, n_live - 1), 0, None)
    si = jnp.minimum(jnp.searchsorted(cum, we, side="right"), R - 1)
    local = we - (cum[si] - nb[si])
    blk = fb[si] + local
    lo = jnp.clip(s_o[si] - blk * CHUNK, 0, CHUNK)
    hi = jnp.clip(s_o[si] + l_o[si] - blk * CHUNK, 0, CHUNK)
    live = w < n_live
    newblk = jnp.concatenate([jnp.ones((1,), bool), blk[1:] != blk[:-1]])
    flags = (live.astype(i32) + 2 * (live & (local == 0))
             + 4 * fresh[order][si].astype(i32) + 8 * newblk)
    return (blk.astype(i32), order[si].astype(i32), lo.astype(i32),
            hi.astype(i32), flags.astype(i32))


def _head_major(x, t_pad, width):
    """``[T, H, d] -> [H, t_pad, width]`` (zero-padded)."""
    T, _, d = x.shape
    return jnp.pad(jnp.swapaxes(x, 0, 1),
                   ((0, 0), (0, t_pad - T), (0, width - d)))


def _scan_heads(H, dv, rep=1):
    """Value heads one grid step of the chunk scan holds: a divisor of the
    head count whose values are whole lane tiles of the store (a block of it
    must be) and whose key heads are whole (``rep`` value heads a key head),
    or every head."""
    return max((h for h in range(1, min(SCAN_HEADS, H) + 1)
                if H % h == 0 and h * dv % 128 == 0 and h % rep == 0),
               default=H)


def _scan_kernel(blk_ref, slot_ref, lo_ref, hi_ref, flag_ref, layer_ref,
                 q_ref, k_ref, v_ref, gb_ref, s_in, o_ref, s_out, *, hb, dk,
                 rep):
    w = pl.program_id(1)
    flags = flag_ref[w]
    live, first = (flags & 1) > 0, (flags & 2) > 0
    fresh, newblk = (flags & 4) > 0, (flags & 8) > 0

    @pl.when(first | (w == 0))
    def _load():
        # the span's state at its start (zero for a fresh span); with no
        # live entry at all, entry 0 hands the block it maps back unchanged
        s_out[...] = jnp.where(live & fresh, 0.0, s_in[...])

    @pl.when(live)
    def _compute():
        rows = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0)
        mine = (rows >= lo_ref[w]) & (rows < hi_ref[w])
        kp, dv = q_ref.shape[-1], v_ref.shape[-1]
        for i in range(hb):
            q = jnp.where(mine, q_ref[i // rep], 0.0)
            k = jnp.where(mine, k_ref[i // rep], 0.0)
            g = jnp.where(mine, gb_ref[0, :, i:i + 1], 0.0)
            beta = jnp.where(mine, gb_ref[0, :, hb + i:hb + i + 1], 0.0)
            # the head's lanes of the block (every other head's start inside
            # a lane tile at 192: a shift a vreg, once a 64-token entry)
            at = slice(i * dv, (i + 1) * dv)
            s0 = s_out[0, 0, :, at]
            if kp > dk:     # (a key width of whole lane tiles pads nothing:
                            # Mosaic has no vector of 0 rows)
                s0 = jnp.concatenate(
                    [s0, jnp.zeros((kp - dk, dv), jnp.float32)], axis=0)
            o, s1 = _chunk_math(q, k, v_ref[i].astype(jnp.float32), g, beta,
                                s0)
            s_out[0, 0, :, at] = s1[:dk]
            o_ref[i] = jnp.where(mine, o, jnp.where(newblk, 0.0, o_ref[i]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(q, k, v, g, beta, state, layer, start, length, fresh,
               interpret):
    T, Hk, dk = q.shape
    H, dv = v.shape[1:]
    R = start.shape[0]
    rep = H // Hk
    hb = _scan_heads(H, dv, rep)
    n_items = scan_work_items(T, R)
    t_pad = -(-T // CHUNK) * CHUNK
    kp = -(-dk // 128) * 128
    work = _scan_work(start, length, fresh, n_items)
    f32 = jnp.float32
    qh = _head_major(q.astype(f32), t_pad, kp)
    kh = _head_major(k.astype(f32), t_pad, kp)
    vh = _head_major(v, t_pad, dv)
    # a head block's g then beta, tokens on sublanes: [H / hb, t_pad, 128]
    gbh = jnp.concatenate([
        jnp.swapaxes(x.astype(f32).reshape(T, H // hb, hb), 0, 1)
        for x in (g, beta)], axis=-1)
    gbh = jnp.pad(gbh, ((0, 0), (0, t_pad - T), (0, -2 * hb % 128)))

    def tok(width, heads=hb):
        return pl.BlockSpec(
            (heads, CHUNK, width), lambda h, w, blk, *_: (h, blk[w], 0))

    st = pl.BlockSpec(
        (1, 1, dk, hb * dv),
        lambda h, w, blk, slot, lo, hi, fl, layer: (layer[0], slot[w], 0, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(H // hb, n_items),
        in_specs=[tok(kp, hb // rep), tok(kp, hb // rep), tok(dv),
                  pl.BlockSpec((1, CHUNK, gbh.shape[-1]),
                               lambda h, w, blk, *_: (h, blk[w], 0)), st],
        out_specs=[tok(dv), st])
    o, state = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, dk=dk, rep=rep),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, t_pad, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 10 (after the six prefetched scalars): the state store
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="gdn_chunk_scan",
    )(*work, layer, qh, kh, vh, gbh, state)
    return jnp.swapaxes(o, 0, 1)[:T], state


def _span_args(layer, start, length, fresh):
    i32 = jnp.int32
    return (jnp.asarray(layer, i32).reshape(1),
            jnp.asarray(start, i32).reshape(-1),
            jnp.asarray(length, i32).reshape(-1),
            jnp.asarray(fresh, bool).reshape(-1))


def gdn_chunk_scan(q, k, v, g, beta, state, *, layer, start, length, fresh):
    """The chunked scan of every span with ``length > 0`` (Pallas). q, k
    ``[T, Hk, dk]`` normalised, v ``[T, H, dv]``, g, beta ``[T, H]``, state
    the store ``[Ll, R, dk, H dv]`` float32 (updated in place when donated),
    start / length / fresh ``[R]`` by slot: the span of slot ``r`` is packed
    rows ``start[r] .. start[r] + length[r]``. Returns ``(o [T, H, dv] float32,
    state')``; rows of ``o`` outside every span are unspecified."""
    return _scan_call(q, k, v, g, beta, state,
                      *_span_args(layer, start, length, fresh),
                      interpret=_interpret_mode())


def gdn_chunk_scan_jnp(q, k, v, g, beta, state, *, layer, start, length,
                       fresh):
    """``gdn_chunk_scan`` in ``jax.numpy``: the same work list, the same
    chunk math, a ``lax.scan`` over the entries."""
    layer, start, length, fresh = _span_args(layer, start, length, fresh)
    q, k = (_to_value_heads(x, v.shape[1]) for x in (q, k))
    T, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    t_pad = -(-T // CHUNK) * CHUNK
    work = _scan_work(start, length, fresh, scan_work_items(T, start.shape[0]))
    qh, kh, vh = (_head_major(x.astype(f32), t_pad, x.shape[-1])
                  for x in (q, k, v))
    gh, bh = (jnp.pad(x.astype(f32).T, ((0, 0), (0, t_pad - T)))
              for x in (g, beta))
    rows = jnp.arange(CHUNK)

    def item(carry, x):
        st, o_all = carry
        blk, slot, lo, hi, flags = x
        live, fresh_w = (flags & 1) > 0, (flags & 4) > 0
        mine = (rows >= lo) & (rows < hi) & live
        at = blk * CHUNK

        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, at, CHUNK, axis=1)

        s0 = jnp.where(fresh_w & ((flags & 2) > 0), 0.0, st[slot])
        m2, m1 = mine[None, :, None], mine[None, :]
        o, s1 = jax.vmap(_chunk_math)(
            jnp.where(m2, cut(qh), 0.0), jnp.where(m2, cut(kh), 0.0),
            cut(vh), jnp.where(m1, cut(gh), 0.0)[..., None],
            jnp.where(m1, cut(bh), 0.0)[..., None], s0)
        st = st.at[jnp.where(live, slot, st.shape[0])].set(s1, mode="drop")
        o_all = jax.lax.dynamic_update_slice_in_dim(
            o_all, jnp.where(m2, o, cut(o_all)), at, axis=1)
        return (st, o_all), None

    (st, o), _ = jax.lax.scan(
        item, (state_from_store(state[layer[0]], H),
               jnp.zeros((H, t_pad, dv), f32)), work)
    return jnp.swapaxes(o, 0, 1)[:T], state.at[layer[0]].set(
        state_to_store(st))


# ------------------------------------------------------ the decode-row update
def _update_kernel(slot_ref, flag_ref, layer_ref, qt_ref, kt_ref, v_ref,
                   a_ref, b_ref, s_in, o_ref, s_out, *, dv, rep):
    i = pl.program_id(0)
    flags = flag_ref[i]
    live, fresh = (flags & 1) > 0, (flags & 2) > 0

    @pl.when(jnp.logical_not(live) & (i == 0))
    def _through():     # no live row at all: hand the mapped block back
        s_out[...] = s_in[...]

    @pl.when(live)
    def _compute():
        r = slot_ref[i]
        dk, W = s_in.shape[2:]

        def spread(t):
            """``column(lo, width)``: lanes ``lo .. lo + width`` of the ``[dk,
            H dv]`` whose value head ``h``'s lanes all hold column ``h //
            rep``, its key head's, of ``t [dk, Hk]``: one lane broadcast a key
            head and sublane tile (shared by the tiles its value heads lie
            in), one select where two key heads meet inside the tile."""
            wide = {}

            def column(lo, width):
                out = None
                first = lo // dv // rep
                for hk in range(first, (lo + width - 1) // dv // rep + 1):
                    if (hk, width) not in wide:
                        wide[hk, width] = jnp.broadcast_to(t[:, hk:hk + 1],
                                                           (dk, width))
                    if out is None:
                        out = wide[hk, width]
                    else:
                        lane = jax.lax.broadcasted_iota(jnp.int32,
                                                        (dk, width), 1)
                        out = jnp.where(lane >= hk * rep * dv - lo,
                                        wide[hk, width], out)
                return out
            return column

        k_at, q_at = spread(kt_ref[r]), spread(qt_ref[r])
        for lo in range(0, W, 128):
            width = min(128, W - lo)
            at = pl.ds(lo, width)
            k_col = k_at(lo, width)
            # (a select, not a product: 0 * NaN of a slot's former tenant)
            s = jnp.where(fresh, 0.0, s_in[0, 0, :, at]) * a_ref[0, :, at]
            res = v_ref[0, :, at] - jnp.sum(s * k_col, axis=0, keepdims=True)
            s = s + k_col * (b_ref[0, :, at] * res)
            s_out[0, 0, :, at] = s
            o_ref[0, :, at] = jnp.sum(s * q_at(lo, width), axis=0,
                                      keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(q, k, v, g, beta, state, layer, live, fresh, interpret):
    R, Hk, dk = q.shape
    H, dv = v.shape[1:]
    f32, i32 = jnp.float32, jnp.int32
    # live rows first, in slot order; the rest repeat the last live row
    order = jnp.argsort(jnp.where(live, 0, 1), stable=True).astype(i32)
    n_live = jnp.sum(live.astype(i32))
    idx = jnp.clip(jnp.minimum(jnp.arange(R, dtype=i32), n_live - 1), 0,
                   None)
    slots = order[idx]
    flags = ((jnp.arange(R) < n_live).astype(i32)
             + 2 * fresh[slots].astype(i32))

    def lanes(x):       # [R, H, dv] as a row of its state's lanes
        return x.astype(f32).reshape(R, 1, H * dv)

    def rows(w):        # a head's scalar over its head's lanes
        return lanes(jnp.broadcast_to(w[..., None], (R, H, dv)))

    def whole(*shape):  # resident whole: a row's is picked by slot in-kernel
        return pl.BlockSpec(shape, lambda i, *_: (0,) * 3)

    # (a block a live row: held whole as [R, H dv], a row would be a dynamic
    # sublane offset, which Mosaic does not load)
    row = pl.BlockSpec((1, 1, H * dv), lambda i, slot, *_: (slot[i], 0, 0))
    st = pl.BlockSpec((1, 1, dk, H * dv),
                      lambda i, slot, fl, layer: (layer[0], slot[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(R,),
        in_specs=[whole(R, dk, Hk), whole(R, dk, Hk), row, row, row, st],
        out_specs=[row, st])
    o, state = pl.pallas_call(
        functools.partial(_update_kernel, dv=dv, rep=H // Hk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, 1, H * dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the three prefetched scalars): the state store
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="gdn_recurrent_update",
    )(slots, flags, layer, jnp.swapaxes(q.astype(f32), 1, 2),
      jnp.swapaxes(k.astype(f32), 1, 2), lanes(v),
      rows(jnp.exp(g.astype(f32))), rows(beta), state)
    return o.reshape(R, H, dv), state


def gdn_recurrent_update(q, k, v, g, beta, state, *, layer, live, fresh):
    """One token a slot (Pallas): row ``r`` of q, k ``[R, Hk, dk]``
    (normalised), v ``[R, H, dv]``, g, beta ``[R, H]`` is slot ``r``'s;
    ``live[r]`` says the slot has a row this step, ``fresh[r]`` that it is
    its sequence's position 0. state, the store ``[Ll, R, dk, H dv]``
    float32, is read and written at the live slots only (in place when
    donated). Returns ``(o [R, H, dv] float32, state')``; rows of ``o`` that
    are not live are unspecified."""
    i32 = jnp.int32
    return _update_call(q, k, v, g, beta, state,
                        jnp.asarray(layer, i32).reshape(1),
                        jnp.asarray(live, bool).reshape(-1),
                        jnp.asarray(fresh, bool).reshape(-1),
                        interpret=_interpret_mode())
