"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060) over a
recurrent state that lives in a store beside the paged KV pool.

A head's state ``S`` is ``[P, N]`` float32 (``P`` the head's channels, ``N``
the state size), ``H`` heads a (layer, slot). A token ``t`` with input
``x_t`` (``[H, P]``), step ``dt_t`` (``[H]``, after the softplus), input and
output vectors ``B_t``, ``C_t`` (``[G, N]``, one a GROUP of ``H / G``
consecutive heads) and the layer's ``A`` (``[H]``, negative: a SCALAR a head,
which is what makes the dual form below a matrix product) does, a head

    S = exp(dt_t A) S + (dt_t x_t) (x) B_t
    y_t = S C_t

(the skip ``D x_t``, the gate and the norm are the caller's).

**The store's layout** (``state_to_store`` / ``state_from_store`` are its one
statement): a (layer, slot) holds ``[G, N, (H / G) P]``, a group's ``S^T``
side by side: the state size on the SUBLANES, the group's channels, head after
head, on the LANES (``[8, 128, 512]`` at the published 64 / 64 / 8 / 128), as
the Mamba-1 store does (``kernels.selective_scan``). What varies along the
lanes of a state vreg is then what a token brings as a lane-dense ROW (``dt
x`` and the decay ``exp(dt A)``, one value a head repeated over its ``P``
lanes: a sublane broadcast, which a load does for nothing), and what varies
along its sublanes (``B``, ``C``) is a column ONE group shares: two lane
broadcasts a sublane tile serve the group's eight heads, the read-out ``sum_n
S[n, :] C[n]`` is vreg adds, and ``y`` leaves lane-dense. With ``[H, P, N]``
(``N`` on the lanes; until PR 48) every state vreg took four cross-lane
operations: the lane broadcasts of a head's decay and of ``x[p]``, a lane
reduction for ``y``, and a broadcast-and-select to park it; the update ran at
24 cycles a vreg, 63 % of what its bytes allow. Now a group's 64 state vregs
share 32 lane broadcasts.

Three implementations, one semantics:

- ``ssd_reference``: the recurrence token by token over a packed buffer (the
  oracle; the serving programs' ``decode_attention="jnp"`` path), the
  mathematics above on ``[H, P, N]``, the store read and written through the
  two helpers.
- ``ssd_chunk_scan`` (Pallas): the spans of a prefill chunk, from each slot's
  state, in the chunked (dual) form over ``gated_delta_rule``'s work list
  (one entry a (span, block of ``CHUNK`` packed rows it touches), built on
  the device by the same ``_scan_work``). With ``g_t = dt_t A`` and ``G`` its
  running sum inside a block, ``L[i, j] = exp(G_i - G_j)`` for ``j <= i``:

      Y = ((C B^T) * L) (dt * X) + exp(G) * (C S0^T)
      S1^T = exp(G_end) S0^T + B^T (exp(G_end - G) * dt * X)

  four matrix products a head on the MXU (``C B^T`` once a group), where
  Mamba-1's diagonal state (``kernels.selective_scan``) has none; the two
  with the state run once for the heads that share a lane tile (a PAIR at
  ``P`` = 64), on the store's own ``S^T``. Decays appear only as differences
  ``exp(G_i - G_j)`` with ``i >= j``. A row of the block that is another
  span's takes ``dt`` 0: the state passes it unchanged and it adds nothing.
  An entry holds ALL the heads (a state of 2 MiB at 64 x 64 x 128): the
  list's dead entries then cost one grid step each, not one a head block.
- ``ssd_recurrent_update`` (Pallas): every decode row of a step in one call,
  one grid step a live row, the state aliased in and out, on the VPU (a
  rank-one update and a read-out a group: a row reads and writes its state
  once, 2 x 2 MiB at the published sizes, and is bound by that: with the
  body taken out the call takes the same time, the copies' own 630-650 GB/s,
  PERF.md, PR 48).

Entries past the live ones repeat the last live entry's block indices (no
DMA) and skip the body. A span marked ``fresh`` (its first position is 0)
starts from a zero state whatever its slot held.

Inference-only (no VJP).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta_rule import (CHUNK, _dot, _dot_nt, _head_major, _scan_work,
                               _span_args, scan_work_items)
from .pallas_flash import _interpret_mode

_VMEM = 96 * 1024 * 1024


# ------------------------------------------------------- the store's layout
def state_shape(heads, head_dim, groups, state):
    """What a (layer, slot) of the store holds: ``[G, N, (H / G) P]``."""
    return (groups, state, heads // groups * head_dim)


def state_to_store(s, groups):
    """``[..., H, P, N] -> [..., G, N, (H / G) P]``."""
    *lead, H, P, N = s.shape
    s = s.reshape(*lead, groups, H // groups, P, N)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, groups, N, -1)


def state_from_store(st, heads):
    """``[..., G, N, (H / G) P] -> [..., H, P, N]``."""
    *lead, G, N, C = st.shape
    st = st.reshape(*lead, G, N, heads // G, -1)
    return jnp.moveaxis(st, -3, -1).reshape(*lead, heads, -1, N)


def _token_step(s, x, dt, a, b, c):
    """One token on ``s [..., H, P, N]``: x ``[..., H, P]``, dt ``[..., H]``,
    a ``[H]``, b, c ``[..., G, N]``. Returns ``(s', y [..., H, P])``."""
    rep = s.shape[-3] // b.shape[-2]
    b, c = (jnp.repeat(t, rep, axis=-2) for t in (b, c))       # [..., H, N]
    s = jnp.exp(dt * a)[..., None, None] * s \
        + (dt[..., None] * x)[..., None] * b[..., None, :]
    return s, jnp.sum(s * c[..., None, :], axis=-1)


def ssd_recurrence(x, dt, a, b, c, s0=None):
    """One sequence, token by token: x ``[S, H, P]``, dt ``[S, H]``, a
    ``[H]``, b, c ``[S, G, N]``, s0 ``[H, P, N]`` or None (zero). Returns ``(y
    [S, H, P], s [H, P, N])``, float32."""
    f32 = jnp.float32
    a = a.astype(f32)
    if s0 is None:
        s0 = jnp.zeros(x.shape[1:] + b.shape[-1:], f32)

    def step(s, t):
        xt, dtt, bt, ct = t
        return _token_step(s, xt, dtt, a, bt, ct)

    s, y = jax.lax.scan(step, s0.astype(f32),
                        tuple(t.astype(f32) for t in (x, dt, b, c)))
    return y, s


def ssd_reference(x, dt, a, b, c, state, *, layer, seg, first):
    """The oracle over a packed buffer: token ``t`` belongs to slot
    ``seg[t]`` (``R`` = a dead row: nothing is read or written) and
    ``first[t]`` says it is its sequence's position 0 (the slot's state is
    zeroed before it). Decode rows and chunk rows alike, in buffer order. x
    ``[T, H, P]``, dt ``[T, H]``, a ``[H]``, b, c ``[T, G, N]``, state the
    store ``[Ll, R, G, N, (H / G) P]``. Returns ``(y [T, H, P] float32,
    state')``."""
    f32 = jnp.float32
    R = state.shape[1]
    H, G = x.shape[1], b.shape[1]
    a = a.astype(f32)
    seg = jnp.asarray(seg, jnp.int32)

    def step(st, t):
        xt, dtt, bt, ct, sg, fr = t
        s = jnp.where(fr, 0.0, st[jnp.minimum(sg, R - 1)])
        s, y = _token_step(s, xt, dtt, a, bt, ct)
        return st.at[sg].set(s, mode="drop"), y

    st, y = jax.lax.scan(step, state_from_store(state[layer], H), tuple(
        t.astype(f32) for t in (x, dt, b, c)) + (
            seg, jnp.asarray(first, bool)))
    return y, state.at[layer].set(state_to_store(st, G))


# ------------------------------------------------------------ the chunk scan
def _chunk_math(x, dts, gs, cb, bt, c, s0t):
    """One block of the ``k`` heads of a lane tile in the dual form (module
    docstring). x ``[C, k P]`` (head after head on the lanes), dts, gs ``k``
    columns ``[C, 1]`` each (``g = dt A``; a masked row carries 0 in both),
    cb ``[C, C]`` (``C_i . B_j``, the heads' group's), bt ``[N, C]``, c ``[C,
    N]``, s0t ``[N, k P]`` (the heads' ``S^T`` side by side). Returns ``(y
    [C, k P], s1t [N, k P])``."""
    C, W = x.shape
    P = W // len(dts)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    incl = row >= col
    ones = jnp.ones((C, C), jnp.float32)
    head = jax.lax.broadcasted_iota(jnp.int32, (C, W), 1) // P

    def lanes(cols):    # a value a head -> each over its head's lanes
        n = cols[0].shape[0]
        out = jnp.broadcast_to(cols[0], (n, W))
        for i, t in enumerate(cols[1:], 1):
            out = jnp.where(head[:n] == i, t, out)
        return out

    ms, g_cols, g_ends = [], [], []
    for g in gs:
        gb = g * ones                                    # [C, C], row i = g_i
        gi = _dot(incl.astype(jnp.float32), gb)          # gi[i, j] = G_i
        gj = _dot(ones, jnp.where(row <= col, gb, 0.0))  # gj[i, j] = G_j
        ms.append(jnp.where(incl, jnp.exp(jnp.where(incl, gi - gj, 0.0)),
                            0.0))
        g_cols.append(gi[:, :1])                         # [C, 1] G_i
        g_ends.append(gi[C - 1:, :1])                    # [1, 1]
    g_col, g_end = lanes(g_cols), lanes(g_ends)          # [C, W], [1, W]
    xdt = x * lanes(dts)
    y = jnp.exp(g_col) * _dot(c, s0t)
    # a head's own lanes of its own product (the MXU's tile is as wide
    # whatever the head's share of it)
    for i, m in enumerate(ms):
        y = y + jnp.where(head == i, _dot(cb * m, xdt), 0.0)
    s1t = jnp.exp(g_end) * s0t + _dot(bt, xdt * jnp.exp(g_end - g_col))
    return y, s1t


def _scan_kernel(blk_ref, slot_ref, lo_ref, hi_ref, flag_ref, layer_ref,
                 x_ref, gd_ref, b_ref, c_ref, s_in, y_ref, s_out, *, hg, k):
    w = pl.program_id(0)
    flags = flag_ref[w]
    live, first = (flags & 1) > 0, (flags & 2) > 0
    fresh, newblk = (flags & 4) > 0, (flags & 8) > 0
    W = x_ref.shape[-1] // hg * k

    @pl.when(first | (w == 0))
    def _load():
        # the span's state at its start (zero for a fresh span); with no
        # live entry at all, entry 0 hands the block it maps back unchanged
        s_out[...] = jnp.where(live & fresh, 0.0, s_in[...])

    @pl.when(live)
    def _compute():
        rows = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0)
        mine = (rows >= lo_ref[w]) & (rows < hi_ref[w])

        def group(gr, _):
            b, c = b_ref[gr], c_ref[gr]
            cb, bt = _dot_nt(c, b), b.T
            gd = jnp.where(mine, gd_ref[gr], 0.0)
            for j in range(hg // k):
                at = slice(j * W, (j + 1) * W)
                heads = range(j * k, (j + 1) * k)
                y, s1t = _chunk_math(
                    x_ref[gr, :, at], [gd[:, i:i + 1] for i in heads],
                    [gd[:, hg + i:hg + i + 1] for i in heads], cb, bt, c,
                    s_out[0, 0, gr, :, at])
                s_out[0, 0, gr, :, at] = s1t
                y_ref[gr, :, at] = jnp.where(
                    mine, y, jnp.where(newblk, 0.0, y_ref[gr, :, at]))
            return 0

        jax.lax.fori_loop(0, b_ref.shape[0], group, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(x, dt, a, b, c, state, layer, start, length, fresh,
               interpret):
    T, H, P = x.shape
    G, N = b.shape[1:]
    hg = H // G
    R = start.shape[0]
    n_items = scan_work_items(T, min(R, max(T // 2, 1)))
    t_pad = -(-T // CHUNK) * CHUNK
    work = _scan_work(start, length, fresh, n_items)
    f32 = jnp.float32
    dt = dt.astype(f32)
    # a group's steps then log-decays, tokens on sublanes: [G, t_pad, 128]
    gd = jnp.concatenate([
        jnp.swapaxes(t.reshape(T, G, hg), 0, 1)
        for t in (dt, dt * a.astype(f32))], axis=-1)
    gd = jnp.pad(gd, ((0, 0), (0, t_pad - T),
                      (0, max(128 - 2 * hg, 0))))
    # group-major, a group's channels head after head on the lanes, as the
    # store holds them
    xg, bg, cg = (_head_major(t.astype(f32), t_pad, t.shape[-1])
                  for t in (x.reshape(T, G, hg * P), b, c))

    def tok(width):
        return pl.BlockSpec((G, CHUNK, width),
                            lambda w, blk, *_: (0, blk[w], 0))

    st = pl.BlockSpec(
        (1, 1) + state.shape[2:],
        lambda w, blk, slot, lo, hi, fl, layer: (layer[0], slot[w], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(n_items,),
        in_specs=[tok(hg * P), tok(gd.shape[-1]), tok(N), tok(N), st],
        out_specs=[tok(hg * P), st])
    y, state = pl.pallas_call(
        # the two state products take the heads that fill a lane tile
        # together (2 at ``P`` = 64), a divisor of the group's ``hg``
        functools.partial(_scan_kernel, hg=hg,
                          k=math.gcd(hg, max(128 // P, 1))),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(xg.shape, f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 10 (after the six prefetched scalars): the state store
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret, name="ssd_chunk_scan",
    )(*work, layer, xg, gd, bg, cg, state)
    return jnp.swapaxes(y, 0, 1)[:T].reshape(T, H, P), state


def ssd_chunk_scan(x, dt, a, b, c, state, *, layer, start, length, fresh):
    """The chunked scan of every span with ``length > 0`` (Pallas). x
    ``[T, H, P]``, dt ``[T, H]``, a ``[H]``, b, c ``[T, G, N]``, state the
    store ``[Ll, R, G, N, (H / G) P]`` float32 (updated in place when
    donated), start / length / fresh ``[R]`` by slot: the span of slot ``r``
    is packed rows ``start[r] .. start[r] + length[r]``. The caller's promise
    is that no more than ``T // 2`` spans are live (a step's spans of one
    token are ``ssd_recurrent_update``'s): the work list holds room for as
    many. Returns ``(y [T, H, P] float32, state')``; rows of ``y`` outside
    every span are unspecified."""
    return _scan_call(x, dt, a, b, c, state,
                      *_span_args(layer, start, length, fresh),
                      interpret=_interpret_mode())


# ------------------------------------------------------ the decode-row update
def _update_kernel(slot_ref, flag_ref, layer_ref, x_ref, d_ref, bc_ref,
                   s_in, y_ref, s_out):
    i = pl.program_id(0)
    flags = flag_ref[i]
    live, fresh = (flags & 1) > 0, (flags & 2) > 0
    G = s_in.shape[2]

    @pl.when(jnp.logical_not(live) & (i == 0))
    def _through():     # no live row at all: hand the mapped block back
        s_out[...] = s_in[...]

    @pl.when(live)
    def _compute():
        r = slot_ref[i]
        bc = bc_ref[r]                              # [N, lanes >= 2 G]
        for g in range(G):
            row = pl.ds(g, 1)
            # (a select, not a product: 0 * NaN of a slot's former tenant)
            s = jnp.where(fresh, 0.0, s_in[0, 0, g]) * d_ref[r, row, :] \
                + bc[:, g:g + 1] * x_ref[r, row, :]
            s_out[0, 0, g] = s
            y_ref[r, row, :] = jnp.sum(s * bc[:, G + g:G + g + 1], axis=0,
                                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(x, dt, a, b, c, state, layer, live, fresh, interpret):
    R, H, P = x.shape
    G, N = b.shape[1:]
    C = H // G * P
    f32, i32 = jnp.float32, jnp.int32
    # live rows first, in slot order; the rest repeat the last live row
    order = jnp.argsort(jnp.where(live, 0, 1), stable=True).astype(i32)
    n_live = jnp.sum(live.astype(i32))
    idx = jnp.clip(jnp.minimum(jnp.arange(R, dtype=i32), n_live - 1), 0,
                   None)
    slots = order[idx]
    flags = ((jnp.arange(R) < n_live).astype(i32)
             + 2 * fresh[slots].astype(i32))
    dt = dt.astype(f32)
    # a row's input and decay, lane-dense as the store's channels lie
    xt = (dt[..., None] * x.astype(f32)).reshape(R, G, C)
    decay = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None],
                             (R, H, P)).reshape(R, G, C)
    # a row's B then C, a group's vector a column: [R, N, lanes]
    bc = jnp.swapaxes(jnp.concatenate([b, c], axis=1).astype(f32), 1, 2)
    bc = jnp.pad(bc, ((0, 0), (0, 0), (0, -2 * G % 128)))

    def whole(*shape):  # resident whole: rows are picked by slot in-kernel
        return pl.BlockSpec(shape, lambda i, *_: (0,) * 3)

    st = pl.BlockSpec((1, 1, G, N, C),
                      lambda i, slot, fl, layer: (layer[0], slot[i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(R,),
        in_specs=[whole(R, G, C), whole(R, G, C), whole(*bc.shape), st],
        out_specs=[whole(R, G, C), st])
    y, state = pl.pallas_call(
        _update_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, G, C), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (after the three prefetched scalars): the state store
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret, name="ssd_recurrent_update",
    )(slots, flags, layer, xt, decay, bc, state)
    return y.reshape(R, H, P), state


def ssd_recurrent_update(x, dt, a, b, c, state, *, layer, live, fresh):
    """One token a slot (Pallas): row ``r`` of x ``[R, H, P]``, dt ``[R,
    H]``, b, c ``[R, G, N]`` is slot ``r``'s; ``live[r]`` says the slot has a
    row this step, ``fresh[r]`` that it is its sequence's position 0. state,
    the store ``[Ll, R, G, N, (H / G) P]`` float32, is read and written at
    the live slots only (in place when donated). Returns ``(y [R, H, P]
    float32, state')``; rows of ``y`` that are not live are unspecified."""
    i32 = jnp.int32
    return _update_call(x, dt, a, b, c, state,
                        jnp.asarray(layer, i32).reshape(1),
                        jnp.asarray(live, bool).reshape(-1),
                        jnp.asarray(fresh, bool).reshape(-1),
                        interpret=_interpret_mode())
