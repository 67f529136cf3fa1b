"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060) over a
recurrent state that lives in a store beside the paged KV pool.

A head's state ``S`` is ``[P, N]`` float32 (``P`` the head's channels, ``N``
the state size, on the lanes), ``H`` heads a (layer, slot). A token ``t`` with
input ``x_t`` (``[H, P]``), step ``dt_t`` (``[H]``, after the softplus), input
and output vectors ``B_t``, ``C_t`` (``[G, N]``, one a GROUP of ``H / G``
consecutive heads) and the layer's ``A`` (``[H]``, negative: a SCALAR a head,
which is what makes the dual form below a matrix product) does, a head

    S = exp(dt_t A) S + (dt_t x_t) (x) B_t
    y_t = S C_t

(the skip ``D x_t``, the gate and the norm are the caller's).

Three implementations, one semantics:

- ``ssd_reference``: the recurrence token by token over a packed buffer (the
  oracle; the serving programs' ``decode_attention="jnp"`` path).
- ``ssd_chunk_scan`` (Pallas): the spans of a prefill chunk, from each slot's
  state, in the chunked (dual) form over ``gated_delta_rule``'s work list
  (one entry a (span, block of ``CHUNK`` packed rows it touches), built on
  the device by the same ``_scan_work``). With ``g_t = dt_t A`` and ``G`` its
  running sum inside a block, ``L[i, j] = exp(G_i - G_j)`` for ``j <= i``:

      Y = ((C B^T) * L) (dt * X) + exp(G) * (C S0^T)
      S1 = exp(G_end) S0 + (exp(G_end - G) * dt * X)^T B

  four matrix products a head on the MXU (``C B^T`` once a group), where
  Mamba-1's diagonal state (``kernels.selective_scan``) has none. Decays
  appear only as differences ``exp(G_i - G_j)`` with ``i >= j``. A row of the
  block that is another span's takes ``dt`` 0: the state passes it unchanged
  and it adds nothing. An entry holds ALL the heads (a state of 2 MiB at 64 x
  64 x 128): the list's dead entries then cost one grid step each, not one a
  head block.
- ``ssd_recurrent_update`` (Pallas): every decode row of a step in one call,
  one grid step a live row, the state aliased in and out, on the VPU (a
  rank-one update and a read-out a head: a row reads and writes its state
  once, 2 x 2 MiB at the published sizes, and is bound by that).

Entries past the live ones repeat the last live entry's block indices (no
DMA) and skip the body. A span marked ``fresh`` (its first position is 0)
starts from a zero state whatever its slot held.

Inference-only (no VJP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta_rule import (CHUNK, _dot, _dot_nt, _dot_tn, _head_major,
                               _scan_work, _span_args, scan_work_items)
from .pallas_flash import _interpret_mode

_VMEM = 96 * 1024 * 1024


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
    [S, H, P], s)``, float32."""
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
    ``[T, H, P]``, dt ``[T, H]``, a ``[H]``, b, c ``[T, G, N]``, state ``[Ll,
    R, H, P, N]``. Returns ``(y [T, H, P] float32, state')``."""
    f32 = jnp.float32
    R = state.shape[1]
    a = a.astype(f32)
    seg = jnp.asarray(seg, jnp.int32)

    def step(st, t):
        xt, dtt, bt, ct, sg, fr = t
        s = jnp.where(fr, 0.0, st[jnp.minimum(sg, R - 1)])
        s, y = _token_step(s, xt, dtt, a, bt, ct)
        return st.at[sg].set(s, mode="drop"), y

    st, y = jax.lax.scan(step, state[layer], tuple(
        t.astype(f32) for t in (x, dt, b, c)) + (
            seg, jnp.asarray(first, bool)))
    return y, state.at[layer].set(st)


# ------------------------------------------------------------ the chunk scan
def _chunk_math(x, dt, g, cb, b, c, s0):
    """One block of one head in the dual form (module docstring). x ``[C,
    P]``, dt, g ``[C, 1]`` (``g = dt A``; a masked row carries 0 in both), cb
    ``[C, C]`` (``C_i . B_j``, the head's group's), b, c ``[C, N]``, s0 ``[P,
    N]``. Returns ``(y [C, P], s1 [P, N])``."""
    C = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    incl = row >= col
    ones = jnp.ones((C, C), jnp.float32)
    gb = g * ones                                    # [C, C], row i = g_i
    gi = _dot(incl.astype(jnp.float32), gb)          # gi[i, j] = G_i
    gj = _dot(ones, jnp.where(row <= col, gb, 0.0))  # gj[i, j] = G_j
    m = jnp.where(incl, jnp.exp(jnp.where(incl, gi - gj, 0.0)), 0.0)
    g_col = gi[:, :1]                                # [C, 1] G_i
    g_end = gi[C - 1:, :1]                           # [1, 1]
    xdt = x * dt
    y = _dot(cb * m, xdt) + jnp.exp(g_col) * _dot_nt(c, s0)
    # (Mosaic broadcasts a [1, 1] along one axis at a time)
    e_end = jnp.exp(jnp.broadcast_to(g_end, (1, s0.shape[1])))
    s1 = e_end * s0 + _dot_tn(xdt * jnp.exp(g_end - g_col), b)
    return y, s1


def _scan_kernel(blk_ref, slot_ref, lo_ref, hi_ref, flag_ref, layer_ref,
                 x_ref, gd_ref, b_ref, c_ref, s_in, y_ref, s_out, *, hg):
    w = pl.program_id(0)
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

        def group(gr, _):
            b, c = b_ref[gr], c_ref[gr]
            cb = _dot_nt(c, b)
            gd = jnp.where(mine, gd_ref[gr], 0.0)
            for i in range(hg):
                h = gr * hg + i
                y, s1 = _chunk_math(x_ref[h], gd[:, i:i + 1],
                                    gd[:, hg + i:hg + i + 1], cb, b, c,
                                    s_out[0, 0, h])
                s_out[0, 0, h] = s1
                y_ref[h] = jnp.where(mine, y,
                                     jnp.where(newblk, 0.0, y_ref[h]))
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

    xh, bh, ch = (_head_major(t.astype(f32), t_pad, t.shape[-1])
                  for t in (x, b, c))

    def tok(lead, width):
        return pl.BlockSpec((lead, CHUNK, width),
                            lambda w, blk, *_: (0, blk[w], 0))

    st = pl.BlockSpec(
        (1, 1, H, P, N),
        lambda w, blk, slot, lo, hi, fl, layer: (layer[0], slot[w], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(n_items,),
        in_specs=[tok(H, P), tok(G, gd.shape[-1]), tok(G, N), tok(G, N), st],
        out_specs=[tok(H, P), st])
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, hg=hg), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, t_pad, P), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 10 (after the six prefetched scalars): the state store
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret, name="ssd_chunk_scan",
    )(*work, layer, xh, gd, bh, ch, state)
    return jnp.swapaxes(y, 0, 1)[:T], state


def ssd_chunk_scan(x, dt, a, b, c, state, *, layer, start, length, fresh):
    """The chunked scan of every span with ``length > 0`` (Pallas). x
    ``[T, H, P]``, dt ``[T, H]``, a ``[H]``, b, c ``[T, G, N]``, state ``[Ll,
    R, H, P, N]`` float32 (updated in place when donated), start / length /
    fresh ``[R]`` by slot: the span of slot ``r`` is packed rows ``start[r]
    .. start[r] + length[r]``. The caller's promise is that no more than ``T
    // 2`` spans are live (a step's spans of one token are
    ``ssd_recurrent_update``'s): the work list holds room for as many.
    Returns ``(y [T, H, P] float32, state')``; rows of ``y`` outside every
    span are unspecified."""
    return _scan_call(x, dt, a, b, c, state,
                      *_span_args(layer, start, length, fresh),
                      interpret=_interpret_mode())


# ------------------------------------------------------ the decode-row update
def _update_kernel(slot_ref, flag_ref, layer_ref, xt_ref, at_ref, b_ref,
                   c_ref, s_in, y_ref, s_out, *, H, hg):
    i = pl.program_id(0)
    flags = flag_ref[i]
    live, fresh = (flags & 1) > 0, (flags & 2) > 0

    @pl.when(jnp.logical_not(live) & (i == 0))
    def _through():     # no live row at all: hand the mapped block back
        s_out[...] = s_in[...]

    @pl.when(live)
    def _compute():
        r = slot_ref[i]
        xt, at = xt_ref[r], at_ref[r]               # [P, lanes >= H]
        b, c = b_ref[r], c_ref[r]                   # [G, N]
        lane = jax.lax.broadcasted_iota(jnp.int32, xt.shape, 1)
        y = jnp.zeros(xt.shape, jnp.float32)
        for h in range(H):
            g = h // hg
            s = jnp.where(fresh, 0.0, s_in[0, 0, h]) * at[:, h:h + 1] \
                + xt[:, h:h + 1] * b[g:g + 1]
            s_out[0, 0, h] = s
            y = jnp.where(lane == h,
                          jnp.sum(s * c[g:g + 1], axis=1, keepdims=True), y)
        y_ref[0] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(x, dt, a, b, c, state, layer, live, fresh, interpret):
    R, H, P = x.shape
    G, N = b.shape[1:]
    f32, i32 = jnp.float32, jnp.int32
    # live rows first, in slot order; the rest repeat the last live row
    order = jnp.argsort(jnp.where(live, 0, 1), stable=True).astype(i32)
    n_live = jnp.sum(live.astype(i32))
    idx = jnp.clip(jnp.minimum(jnp.arange(R, dtype=i32), n_live - 1), 0,
                   None)
    slots = order[idx]
    flags = ((jnp.arange(R) < n_live).astype(i32)
             + 2 * fresh[slots].astype(i32))
    lanes = -(-H // 128) * 128
    dt = dt.astype(f32)

    def columns(t):     # [R, H, P] -> [R, P, lanes]: a head's column
        return jnp.pad(jnp.swapaxes(t, 1, 2),
                       ((0, 0), (0, 0), (0, lanes - H)))

    xt = columns(dt[..., None] * x.astype(f32))
    at = columns(jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None],
                                  (R, H, P)))

    def whole(*shape):  # resident whole: rows are picked by slot in-kernel
        return pl.BlockSpec(shape, lambda i, *_: (0,) * 3)

    st = pl.BlockSpec((1, 1, H, P, N),
                      lambda i, slot, fl, layer: (layer[0], slot[i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(R,),
        in_specs=[whole(R, P, lanes), whole(R, P, lanes), whole(R, G, N),
                  whole(R, G, N), st],
        out_specs=[pl.BlockSpec((1, P, lanes),
                                lambda i, slot, *_: (slot[i], 0, 0)), st])
    y, state = pl.pallas_call(
        functools.partial(_update_kernel, H=H, hg=H // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, P, lanes), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 7 (after the three prefetched scalars): the state store
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret, name="ssd_recurrent_update",
    )(slots, flags, layer, xt, at, b.astype(f32), c.astype(f32), state)
    return jnp.swapaxes(y[..., :H], 1, 2), state


def ssd_recurrent_update(x, dt, a, b, c, state, *, layer, live, fresh):
    """One token a slot (Pallas): row ``r`` of x ``[R, H, P]``, dt ``[R,
    H]``, b, c ``[R, G, N]`` is slot ``r``'s; ``live[r]`` says the slot has a
    row this step, ``fresh[r]`` that it is its sequence's position 0. state
    ``[Ll, R, H, P, N]`` float32 is read and written at the live slots only
    (in place when donated). Returns ``(y [R, H, P] float32, state')``; rows
    of ``y`` that are not live are unspecified."""
    i32 = jnp.int32
    return _update_call(x, dt, a, b, c, state,
                        jnp.asarray(layer, i32).reshape(1),
                        jnp.asarray(live, bool).reshape(-1),
                        jnp.asarray(fresh, bool).reshape(-1),
                        interpret=_interpret_mode())
