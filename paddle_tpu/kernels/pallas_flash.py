"""Pallas TPU flash attention — fwd + bwd kernels with custom VJP.

The TPU rewrite of the reference's flash-attention CUDA glue
(``paddle/phi/kernels/gpu/flash_attn_kernel.cu`` + third_party/flashattn):
tiled online-softmax forward (no S×S materialization; KV streamed through
VMEM blocks) and the standard two-kernel backward (dkv with q innermost,
dq with kv innermost), causal block pruning included.

Layout: [BH, S, D] per q/k/v (heads folded into batch); f32 accumulation
scratch in VMEM; LSE residual stored [BH, S].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _cparams(dims):
    return pltpu.CompilerParams(dimension_semantics=dims)


def _row_valid(ref_block, idx, block, seq_len):
    """[block, D] mask zeroing rows whose global index >= seq_len (the
    Pallas-padded tail when seq_len % block != 0 — padded reads are
    undefined and must not reach the accumulators)."""
    rows = idx * block + jax.lax.broadcasted_iota(jnp.int32, ref_block.shape, 0)
    return jnp.where(rows < seq_len, ref_block, jnp.zeros_like(ref_block))


def _rope_block(x, sin, cos):
    """Neox rope applied to a [block, D] tile in the kernel prologue —
    fuses the reference's fused_rope_kernel.cu † into the attention reads
    (no separate HBM round-trip for rotated q/k)."""
    d = x.shape[-1]
    rot = jnp.concatenate([-x[:, d // 2:], x[:, :d // 2]], axis=-1)
    return (x * cos + rot * sin).astype(x.dtype)


def _rope_t_block(y, sin, cos):
    """Adjoint of _rope_block: rope(x) = c*x + s*R(x) with
    R([x1,x2]) = [-x2,x1], so rope^T(y) = c*y + R^T(s*y) and
    R^T([z1,z2]) = [z2,-z1]. Applied to dq/dk accumulators so the kernels
    return gradients w.r.t. the PRE-rope projections."""
    d = y.shape[-1]
    z = y * sin
    rot_t = jnp.concatenate([z[:, d // 2:], -z[:, :d // 2]], axis=-1)
    return y * cos + rot_t


# ----------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                seq_len, rope=False):
    if rope:
        sq_ref, cq_ref, sk_ref, ck_ref = rest[:4]
        rest = rest[4:]
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    tail = seq_len % block_q != 0 or seq_len % block_k != 0

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = ki * block_k <= qi * block_q + (block_q - 1)

    @pl.when(run if causal else ki >= 0)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        if rope:
            q = _rope_block(q, sq_ref[...], cq_ref[...])
            k = _rope_block(k, sk_ref[...], ck_ref[...])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or tail:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = (rows >= cols) if causal else (s == s)
            if tail:
                keep = keep & (cols < seq_len)
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if tail:  # exp underflows to exact 0 on masked cols, but padded v
            p = jnp.where(  # rows may be NaN garbage and 0*NaN = NaN
                ki * block_k
                + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) < seq_len,
                p, 0.0)
        v = v_ref[0]
        if tail:
            v = _row_valid(v, ki, block_k, seq_len)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _rope_specs(block_q, block_k, D):
    """BlockSpecs for (sin_q, cos_q, sin_k, cos_k) over [S, D] tables."""
    return [
        pl.BlockSpec((block_q, D), lambda b, qi, ki: (qi, 0)),
        pl.BlockSpec((block_q, D), lambda b, qi, ki: (qi, 0)),
        pl.BlockSpec((block_k, D), lambda b, qi, ki: (ki, 0)),
        pl.BlockSpec((block_k, D), lambda b, qi, ki: (ki, 0)),
    ]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               rope=None):
    BH, S, D = q.shape
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(S, block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, seq_len=S,
                               rope=rope is not None)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
    ]
    args = [q, k, v]
    if rope is not None:
        sin, cos = rope
        in_specs += _rope_specs(block_q, block_k, D)
        args += [sin, cos, sin, cos]
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=_cparams(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    return o, lse


# ----------------------------------------------------------------- backward
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, block_q, block_k, seq_len, rope=False):
    if rope:
        sq_ref, cq_ref, sk_ref, ck_ref = rest[:4]
        rest = rest[4:]
    dk_ref, dv_ref, dk_scr, dv_scr = rest
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    tail = seq_len % block_q != 0 or seq_len % block_k != 0

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = qi * block_q + (block_q - 1) >= ki * block_k

    @pl.when(run if causal else qi >= 0)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        if rope:
            q = _rope_block(q, sq_ref[...], cq_ref[...])
            k = _rope_block(k, sk_ref[...], ck_ref[...])
        if tail:  # padded q rows are undefined and sum into every dk/dv row
            q = _row_valid(q, qi, block_q, seq_len)
            do = _row_valid(do, qi, block_q, seq_len)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]
        if tail:  # padded-row lse/delta are garbage: zero p and ds there
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
            p = jnp.where(rows < seq_len, p, 0.0)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        if tail:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, ds.shape, 0)
            ds = jnp.where(rows < seq_len, ds, 0.0)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk = dk_scr[:]
        if rope:  # gradient w.r.t. the PRE-rope k projection
            dk = _rope_t_block(dk, sk_ref[...], ck_ref[...])
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, block_q, block_k, seq_len, rope=False):
    if rope:
        sq_ref, cq_ref, sk_ref, ck_ref = rest[:4]
        rest = rest[4:]
    dq_ref, dq_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    tail = seq_len % block_q != 0 or seq_len % block_k != 0

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = ki * block_k <= qi * block_q + (block_q - 1)

    @pl.when(run if causal else ki >= 0)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        if rope:
            q = _rope_block(q, sq_ref[...], cq_ref[...])
            k = _rope_block(k, sk_ref[...], ck_ref[...])
        if tail:  # padded k/v rows are undefined and sum into every dq row
            k = _row_valid(k, ki, block_k, seq_len)
            v = _row_valid(v, ki, block_k, seq_len)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or tail:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = (rows >= cols) if causal else (s == s)
            if tail:
                keep = keep & (cols < seq_len)
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - lse)
        if tail:
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
            p = jnp.where(cols < seq_len, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq = dq_scr[:]
        if rope:  # gradient w.r.t. the PRE-rope q projection
            dq = _rope_t_block(dq, sq_ref[...], cq_ref[...])
        dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd(res, g, scale, causal, block_q, block_k, interpret,
               rope=None):
    q, k, v, o, lse = res
    do = g
    BH, S, D = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                     axis=-1, keepdims=True)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(S, block_k)

    base_args = [q, k, v, do, lse, delta]
    rope_args = []
    if rope is not None:
        sin, cos = rope
        rope_args = [sin, cos, sin, cos]

    # NOTE the dkv grid is (b, ki, qi): its rope specs swap the index args
    def dkv_rope_specs():
        return [
            pl.BlockSpec((block_q, D), lambda b, ki, qi: (qi, 0)),
            pl.BlockSpec((block_q, D), lambda b, ki, qi: (qi, 0)),
            pl.BlockSpec((block_k, D), lambda b, ki, qi: (ki, 0)),
            pl.BlockSpec((block_k, D), lambda b, ki, qi: (ki, 0)),
        ]

    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=S,
                          rope=rope is not None),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, ki, qi: (b, qi, 0)),
        ] + (dkv_rope_specs() if rope is not None else []),
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_cparams(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*base_args, *rope_args)
    dk, dv = dkv

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=S,
                          rope=rope is not None),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ] + (_rope_specs(block_q, block_k, D) if rope is not None else []),
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_cparams(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*base_args, *rope_args)
    return dq, dk, dv


# ----------------------------------------------------------------- public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                      _interpret_mode())
    return o


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        _interpret_mode())
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, res, g):
    return _flash_bwd(res, g, scale, causal, block_q, block_k,
                      _interpret_mode())


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# rope-fused variant: q/k rotate inside the kernels (prologue on reads,
# adjoint on dq/dk) — no separate rope HBM round-trip. sin/cos cotangents
# are reported as zero: the tables are position constants, never trained.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_rope(q, k, v, sin, cos, scale, causal, block_q, block_k):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                      _interpret_mode(), rope=(sin, cos))
    return o


def _flash_rope_fwd_rule(q, k, v, sin, cos, scale, causal, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        _interpret_mode(), rope=(sin, cos))
    return o, (q, k, v, o, lse, sin, cos)


def _flash_rope_bwd_rule(scale, causal, block_q, block_k, res, g):
    q, k, v, o, lse, sin, cos = res
    dq, dk, dv = _flash_bwd((q, k, v, o, lse), g, scale, causal, block_q,
                            block_k, _interpret_mode(), rope=(sin, cos))
    return dq, dk, dv, jnp.zeros_like(sin), jnp.zeros_like(cos)


_flash_rope.defvjp(_flash_rope_fwd_rule, _flash_rope_bwd_rule)

_FORCE_INTERPRET = [False]


def _interpret_mode():
    """Interpret on the CPU backend (tests), compile with Mosaic on a TPU.
    Any other backend is an error: a kernel that silently ran interpreted
    there would report numbers for a program nobody deploys."""
    if _FORCE_INTERPRET[0]:
        return True
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas TPU kernels run compiled on 'tpu' and interpreted on "
        f"'cpu'; the default JAX backend is {backend!r}")


def flash_attention_pallas(q, k, v, causal=True, block_q=1024, block_k=1024):
    """q/k/v: [B, S, H, D] (paddle layout). GQA handled by repeating kv heads."""
    B, S, H, D = q.shape
    Hk = k.shape[2]
    if Hk != H:
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    def fold(x):
        return jnp.swapaxes(x, 1, 2).reshape(B * H, S, D)

    o = flash_attention_bhsd(fold(q), fold(k), fold(v), causal=causal,
                             block_q=block_q, block_k=block_k)
    return jnp.swapaxes(o.reshape(B, H, S, D), 1, 2)


def flash_attention_bhsd(q, k, v, causal=True, block_q=1024, block_k=1024,
                         rope=None):
    """Transpose-free entry: q/k/v are [BH, S, D] (heads folded into batch).
    Use this from models that emit head-major projections — the head
    transpose then folds into the projection matmul epilogue instead of a
    separate HBM pass.

    ``rope=(sin, cos)`` ([S, D] f32 tables) applies neox rotary embedding
    to q/k INSIDE the kernels (prologue + dq/dk adjoint) — the fusion of
    the reference's ``fused_rope_kernel.cu`` † into attention, eliminating
    the rotated q/k HBM round-trip."""
    BH, S, D = q.shape
    scale = 1.0 / math.sqrt(D)
    bq = min(block_q, S)
    bk = min(block_k, S)
    if rope is not None:
        sin, cos = rope
        sin = jnp.asarray(sin, jnp.float32)
        cos = jnp.asarray(cos, jnp.float32)
        assert sin.shape == (S, D) and cos.shape == (S, D), (sin.shape, S, D)
        return _flash_rope(q, k, v, sin, cos, scale, bool(causal), bq, bk)
    return _flash(q, k, v, scale, bool(causal), bq, bk)
