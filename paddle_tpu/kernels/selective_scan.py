"""The selective scan of a diagonal state-space layer (Mamba-1,
arXiv:2312.00752) over a recurrent state that lives in a store beside the
paged KV pool.

A layer's state ``S`` is ``[d_state, d_inner]`` float32, one a (layer, slot):
the ``d_inner`` channels lie on the LANES (a minor dimension of ``d_state`` =
16 would be padded to 128 on the device, 8 x the bytes). A token ``t`` with
step ``dt_t`` and input ``u_t = dt_t * c_t`` (``[d_inner]``, ``c`` the
convolved channel), input and output vectors ``B_t``, ``C_t`` (``[d_state]``)
and the layer's ``A = -exp(A_log)`` (``[d_state, d_inner]``) does

    S = exp(dt_t[None, :] * A) * S + B_t[:, None] * u_t[None, :]
    y_t = sum_n C_t[n] * S[n, :]

(the skip ``D * c_t`` and the gate are the caller's). The state is diagonal:
there is no matrix product in the recurrence, only an ``exp`` and a few
multiply-adds a state element a token, so unlike the delta rule
(``kernels.gated_delta_rule``) nothing here touches the MXU.

Three implementations, one semantics:

- ``ssm_reference``: the recurrence token by token over a packed buffer (the
  oracle; the serving programs' ``decode_attention="jnp"`` path).
- ``ssm_chunk_scan`` (Pallas): the spans of a prefill chunk, from each slot's
  state, walking ``gated_delta_rule``'s work list (one entry a (span, block
  of ``CHUNK`` packed rows it touches), built on the device by the same
  ``_scan_work``). An entry walks its own rows of the block eight tokens a
  load, the state of ``LANES`` channels held in registers; the grid's
  leading dimension is the channel block. Rows of the block that are another
  span's take ``dt`` 0 and ``u`` 0: the state passes them unchanged.
- ``ssm_recurrent_update`` (Pallas): every decode row of a step in one call,
  one grid step a live row, the state aliased in and out.

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

from .gated_delta_rule import CHUNK, _scan_work, _span_args, scan_work_items
from .pallas_flash import _interpret_mode

#: channels one grid step of the chunk scan holds: a state of ``[16, 1024]``
#: float32 is 16 vector registers
LANES = 1024
#: tokens one load of the chunk scan takes: a sublane tile
GROUP = 8


def _token_step(s, dt, u, b, c, a):
    """One token on ``s [..., N, C]``: dt, u ``[..., C]``, b, c ``[..., N]``,
    a ``[N, C]``. Returns ``(s', y [..., C])``."""
    s = jnp.exp(dt[..., None, :] * a) * s + b[..., :, None] * u[..., None, :]
    return s, jnp.sum(s * c[..., :, None], axis=-2)


def ssm_recurrence(dt, u, b, c, a, s0=None):
    """One sequence, token by token: dt, u ``[S, C]``, b, c ``[S, N]``, a
    ``[N, C]``, s0 ``[N, C]`` or None (zero). Returns ``(y [S, C], s)``,
    float32."""
    f32 = jnp.float32
    a = a.astype(f32)
    if s0 is None:
        s0 = jnp.zeros(a.shape, f32)

    def step(s, x):
        return _token_step(s, *x, a)

    s, y = jax.lax.scan(step, s0.astype(f32),
                        tuple(x.astype(f32) for x in (dt, u, b, c)))
    return y, s


def ssm_reference(dt, u, b, c, a, state, *, layer, seg, first):
    """The oracle over a packed buffer: token ``t`` belongs to slot
    ``seg[t]`` (``R`` = a dead row: nothing is read or written) and
    ``first[t]`` says it is its sequence's position 0 (the slot's state is
    zeroed before it). dt, u ``[T, C]``, b, c ``[T, N]``, a ``[N, C]``, state
    ``[Ll, R, N, C]``. Returns ``(y [T, C] float32, state')``."""
    f32 = jnp.float32
    R = state.shape[1]
    a = a.astype(f32)
    seg = jnp.asarray(seg, jnp.int32)

    def step(st, x):
        dtt, ut, bt, ct, sg, fr = x
        s = jnp.where(fr, 0.0, st[jnp.minimum(sg, R - 1)])
        s, y = _token_step(s, dtt, ut, bt, ct, a)
        return st.at[sg].set(s, mode="drop"), y

    st, y = jax.lax.scan(step, state[layer], tuple(
        x.astype(f32) for x in (dt, u, b, c)) + (
            seg, jnp.asarray(first, bool)))
    return y, state.at[layer].set(st)


def _lanes(channels):
    """The channel block: ``LANES``, or all the channels where they are fewer
    or no whole number of such blocks."""
    return LANES if channels % LANES == 0 else channels


def _columns(x, rows):
    """``[T, N] -> [rows, N, 128]`` float32: a token's ``N`` values down the
    sublanes, the same on every lane (the kernels broadcast lane 0)."""
    x = jnp.pad(x.astype(jnp.float32), ((0, rows - x.shape[0]), (0, 0)))
    return jnp.broadcast_to(x[:, :, None], x.shape + (128,))


# ------------------------------------------------------------ the chunk scan
def _scan_kernel(blk_ref, slot_ref, lo_ref, hi_ref, flag_ref, layer_ref,
                 dt_ref, u_ref, b_ref, c_ref, a_ref, s_in, y_ref, s_out):
    w = pl.program_id(1)
    flags = flag_ref[w]
    live, first = (flags & 1) > 0, (flags & 2) > 0
    fresh, newblk = (flags & 4) > 0, (flags & 8) > 0

    @pl.when(first | (w == 0))
    def _load():
        # the span's state at its start (zero for a fresh span); with no
        # live entry at all, entry 0 hands the block it maps back unchanged
        s_out[...] = jnp.where(live & fresh, 0.0, s_in[...])

    @pl.when(newblk)
    def _zero():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(live)
    def _compute():
        lo, hi = lo_ref[w], hi_ref[w]
        a = a_ref[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, (GROUP, 1), 0)

        def group(gi, s):
            t0 = pl.multiple_of(gi * GROUP, GROUP)
            at = pl.ds(t0, GROUP)
            mine = (t0 + rows >= lo) & (t0 + rows < hi)
            dt8 = jnp.where(mine, dt_ref[at, :], 0.0)
            u8 = jnp.where(mine, u_ref[at, :], 0.0)
            ys = []
            for i in range(GROUP):
                b = b_ref[t0 + i][:, :1]
                c = c_ref[t0 + i][:, :1]
                s = jnp.exp(dt8[i:i + 1] * a) * s + b * u8[i:i + 1]
                ys.append(jnp.sum(s * c, axis=0, keepdims=True))
            y_ref[at, :] = jnp.where(mine, jnp.concatenate(ys, axis=0),
                                     y_ref[at, :])
            return s

        s_out[0, 0] = jax.lax.fori_loop(
            lo // GROUP, (hi + GROUP - 1) // GROUP, group, s_out[0, 0])


@functools.partial(jax.jit, static_argnames=("min_span", "interpret"))
def _scan_call(dt, u, b, c, a, state, layer, start, length, fresh, min_span,
               interpret):
    T, C = dt.shape
    N = a.shape[0]
    R = start.shape[0]
    cb = _lanes(C)
    n_items = scan_work_items(T, min(R, T // min_span))
    t_pad = -(-T // CHUNK) * CHUNK
    work = _scan_work(start, length, fresh, n_items)
    f32 = jnp.float32

    def rows(x):
        return jnp.pad(x.astype(f32), ((0, t_pad - T), (0, 0)))

    tok = pl.BlockSpec((CHUNK, cb), lambda ch, w, blk, *_: (blk[w], ch))
    col = pl.BlockSpec((CHUNK, N, 128), lambda ch, w, blk, *_: (blk[w], 0, 0))
    st = pl.BlockSpec(
        (1, 1, N, cb),
        lambda ch, w, blk, slot, lo, hi, fl, layer: (layer[0], slot[w], 0,
                                                      ch))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(C // cb, n_items),
        in_specs=[tok, tok, col, col,
                  pl.BlockSpec((N, cb), lambda ch, w, *_: (0, ch)), st],
        out_specs=[tok, st])
    y, state = pl.pallas_call(
        _scan_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t_pad, C), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 11 (after the six prefetched scalars): the state store
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="ssm_chunk_scan",
    )(*work, layer, rows(dt), rows(u), _columns(b, t_pad), _columns(c, t_pad),
      a.astype(f32), state)
    return y[:T], state


def ssm_chunk_scan(dt, u, b, c, a, state, *, layer, start, length, fresh,
                   min_span=1):
    """The scan of every span with ``length > 0`` (Pallas). dt, u ``[T, C]``,
    b, c ``[T, N]``, a ``[N, C]``, state ``[Ll, R, N, C]`` float32 (updated
    in place when donated), start / length / fresh ``[R]`` by slot: the span
    of slot ``r`` is packed rows ``start[r] .. start[r] + length[r]``.
    ``min_span`` (static) is the caller's promise that no live span is
    shorter: the work list then holds room for ``T // min_span`` spans, not
    ``R`` (a decode-only buffer of ``R`` rows walks half the dead entries).
    Returns ``(y [T, C] float32, state')``; rows of ``y`` outside every span
    are unspecified."""
    return _scan_call(dt, u, b, c, a, state,
                      *_span_args(layer, start, length, fresh),
                      min_span=int(min_span), interpret=_interpret_mode())


# ------------------------------------------------------ the decode-row update
def _update_kernel(slot_ref, flag_ref, layer_ref, dt_ref, u_ref, b_ref,
                   c_ref, a_ref, s_in, y_ref, s_out):
    i = pl.program_id(0)
    flags = flag_ref[i]
    live, fresh = (flags & 1) > 0, (flags & 2) > 0

    @pl.when(jnp.logical_not(live) & (i == 0))
    def _through():     # no live row at all: hand the mapped block back
        s_out[...] = s_in[...]

    @pl.when(live)
    def _compute():
        r = slot_ref[i]
        at = pl.ds(r, 1)
        s = jnp.where(fresh, 0.0, s_in[0, 0])
        s = jnp.exp(dt_ref[at, :] * a_ref[...]) * s \
            + b_ref[r][:, :1] * u_ref[at, :]
        s_out[0, 0] = s
        y_ref[at, :] = jnp.sum(s * c_ref[r][:, :1], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(dt, u, b, c, a, state, layer, live, fresh, interpret):
    R, C = dt.shape
    N = a.shape[0]
    f32, i32 = jnp.float32, jnp.int32
    # live rows first, in slot order; the rest repeat the last live row
    order = jnp.argsort(jnp.where(live, 0, 1), stable=True).astype(i32)
    n_live = jnp.sum(live.astype(i32))
    idx = jnp.clip(jnp.minimum(jnp.arange(R, dtype=i32), n_live - 1), 0,
                   None)
    slots = order[idx]
    flags = ((jnp.arange(R) < n_live).astype(i32)
             + 2 * fresh[slots].astype(i32))
    r_pad = -(-R // 8) * 8

    def rows(x):
        return jnp.pad(x.astype(f32), ((0, r_pad - R), (0, 0)))

    def whole(*shape):  # resident whole: rows are picked by slot in-kernel
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    st = pl.BlockSpec((1, 1, N, C),
                      lambda i, slot, fl, layer: (layer[0], slot[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(R,),
        in_specs=[whole(r_pad, C), whole(r_pad, C), whole(r_pad, N, 128),
                  whole(r_pad, N, 128), whole(N, C), st],
        out_specs=[whole(r_pad, C), st])
    y, state = pl.pallas_call(
        _update_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r_pad, C), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the three prefetched scalars): the state store
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="ssm_recurrent_update",
    )(slots, flags, layer, rows(dt), rows(u), _columns(b, r_pad),
      _columns(c, r_pad), a.astype(f32), state)
    return y[:R], state


def ssm_recurrent_update(dt, u, b, c, a, state, *, layer, live, fresh):
    """One token a slot (Pallas): row ``r`` of dt, u ``[R, C]``, b, c ``[R,
    N]`` is slot ``r``'s; ``live[r]`` says the slot has a row this step,
    ``fresh[r]`` that it is its sequence's position 0. state ``[Ll, R, N,
    C]`` float32 is read and written at the live slots only (in place when
    donated). Returns ``(y [R, C] float32, state')``; rows of ``y`` that are
    not live are unspecified."""
    i32 = jnp.int32
    return _update_call(dt, u, b, c, a, state,
                        jnp.asarray(layer, i32).reshape(1),
                        jnp.asarray(live, bool).reshape(-1),
                        jnp.asarray(fresh, bool).reshape(-1),
                        interpret=_interpret_mode())
