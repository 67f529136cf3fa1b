"""Pallas TPU decode attention — single-query attention against a ragged
KV cache.

The TPU rewrite of the reference's masked-multihead-attention decode
kernel inside ``paddle/phi/kernels/fusion/gpu/fused_multi_transformer_op.cu``
† (SURVEY §3.5): one query token per sequence attends to a [S_max]-long
cache of which only ``lengths[b]`` entries are valid. Design points:

- **Ragged lengths** ([B] int32, scalar-prefetched to SMEM): KV blocks
  entirely past a row's length are skipped — HBM cost scales with the
  *valid* cache, not S_max, like the ragged/paged-attention kernels this
  slot is named for in SURVEY §3.5 / PAPERS.md.
- **No transpose of the cache**: the kernel reads the paddle cache layout
  [B, S_max, Hkv, D] directly (viewed as [B, S_max, Hkv*D] — a free
  reshape, identical memory layout) via the BlockSpec index map, so no
  [B,S,H,D] -> [B,H,S,D] HBM pass precedes it.
- **Mosaic-conservative lowering** (the r4 kernel was rejected by the
  real TPU compiler: a (1, block_k, 1, D) KV block has last-two dims
  (1, D) that neither divide (8, 128) nor equal the full (Hkv, D)).
  This version uses ONLY 2D tiles whose last-two block dims equal the
  full array dims, and only plain 2D ``dot_general`` — no sublane
  slicing, no batch dims, no cross-tile reshapes. GQA head matching is
  done with a **block-diagonal wide query**: q is expanded outside the
  kernel to [H, Hkv*D] with head h's D values placed at its kv-group's
  lane offset and zeros elsewhere, so one [H,KD]x[KD,bk] matmul yields
  exactly the per-head logits (cross-head terms multiply zeros). The
  PV matmul symmetrically produces a wide [H, Hkv*D] accumulator whose
  per-head diagonal block is extracted outside the kernel. This costs
  ~Hkv x more MXU FLOPs than a sliced kernel, but decode is HBM-bound
  (cache+weight streaming) and the MXU is ~100x idle at bench shapes;
  HBM traffic — the real bottleneck — is unchanged (cache read once,
  no G x GQA repeat).

Inference-only (no VJP): decode never backpropagates.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_flash import _cparams, _interpret_mode

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale, block_k):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    length = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k < length)  # ragged skip: block fully past length
    def _compute():
        q = q_ref[0]                        # [H, Hkv*D] block-diagonal
        k = k_ref[0]                        # [block_k, Hkv*D]
        v = v_ref[0]                        # [block_k, Hkv*D]
        # one 2D matmul = all heads' logits (zeros kill cross-head terms)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # exp hits exact 0 on masked cols, but cache rows past `length`
        # may be uninitialized garbage (NaN) and 0*NaN = NaN
        p = jnp.where(cols < length, p, 0.0)
        v = jnp.where(
            ki * block_k
            + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) < length,
            v, jnp.zeros_like(v))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def _decode_call(q_wide, kv_k, kv_v, lengths, scale, block_k, interpret):
    """q_wide: [B, H, KD] block-diagonal; kv_*: [B, S_max, KD]."""
    B, H, KD = q_wide.shape
    s_max = kv_k.shape[1]
    nk = pl.cdiv(s_max, block_k)
    grid = (B, nk)
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k)

    def _kv_index(b, ki, lens):
        # ragged DMA skip: blocks fully past lens[b] re-reference the last
        # valid block instead of fetching — Pallas elides the copy when the
        # block index repeats, so HBM traffic scales with the VALID cache
        # length, not S_max (the compute for those steps is pl.when-gated
        # off anyway). This is the paged-attention fetch pattern.
        last = (jnp.maximum(lens[b], 1) - 1) // block_k
        return (b, jnp.minimum(ki, last), 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, KD), lambda b, ki, lens: (b, 0, 0)),
                pl.BlockSpec((1, block_k, KD), _kv_index),
                pl.BlockSpec((1, block_k, KD), _kv_index),
            ],
            out_specs=pl.BlockSpec((1, H, KD), lambda b, ki, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, KD), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, KD), q_wide.dtype),
        compiler_params=_cparams(("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(lengths, q_wide, kv_k, kv_v)
    return out


# Inference-only custom_vjp: the eager dispatch (ops/_op.apply) builds a
# jax.vjp around every op, and linearizing THROUGH a scalar-prefetch
# pallas_call is unsupported in interpret mode. The custom rule keeps the
# linearizer out of the kernel; actually differentiating decode raises.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _decode(q_wide, kv_k, kv_v, lengths, scale, block_k):
    return _decode_call(q_wide, kv_k, kv_v, lengths, scale, block_k,
                        _interpret_mode())


def _decode_fwd_rule(q_wide, kv_k, kv_v, lengths, scale, block_k):
    return _decode(q_wide, kv_k, kv_v, lengths, scale, block_k), None


def _decode_bwd_rule(scale, block_k, res, g):
    raise NotImplementedError(
        "decode_attention_pallas is inference-only (single-token decode "
        "never backpropagates); use the flash-attention kernel for "
        "training attention")


_decode.defvjp(_decode_fwd_rule, _decode_bwd_rule)


def decode_attention_pallas(q, k_cache, v_cache, lengths, block_k=256):
    """Single-token decode attention.

    q:        [B, H, D]       — the one query token per sequence
    k_cache:  [B, S_max, Hkv, D]  (paddle cache layout, read in place)
    v_cache:  [B, S_max, Hkv, D]
    lengths:  [B] int32       — valid cache entries per row (ragged)
    returns:  [B, H, D]

    GQA (Hkv < H) is resolved inside the kernel via the block-diagonal
    wide-query trick (see module docstring); kv blocks past
    ``lengths[b]`` are skipped per row.
    """
    B, H, D = q.shape
    Hkv = k_cache.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    s_max = k_cache.shape[1]
    KD = Hkv * D
    scale = 1.0 / math.sqrt(D)
    bk = min(block_k, s_max)
    if s_max % bk or (bk % 8 and bk != s_max):
        # Mosaic: the KV block's second-to-last dim must be a multiple of
        # 8 or equal s_max. Largest multiple-of-8 divisor of s_max wins;
        # if s_max has none (not divisible by 8), a single full-length
        # block is the only legal tiling.
        cands = [d for d in range(8, bk + 1, 8) if s_max % d == 0]
        bk = max(cands) if cands else s_max
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    # block-diagonal wide query: head h's D values at its kv group's lanes
    eye = jnp.eye(Hkv, dtype=q.dtype)
    q_wide = jnp.einsum("bkgd,kj->bkgjd", q.reshape(B, Hkv, G, D), eye)
    q_wide = q_wide.reshape(B, H, KD)
    out_wide = _decode(q_wide, k_cache.reshape(B, s_max, KD),
                       v_cache.reshape(B, s_max, KD), lengths, scale, bk)
    # extract each head's own kv-group block from the wide accumulator
    out = jnp.einsum("bkgjd,kj->bkgd",
                     out_wide.reshape(B, Hkv, G, Hkv, D), eye)
    return out.reshape(B, H, D)


def decode_attention_reference(q, k_cache, v_cache, lengths):
    """jnp oracle with identical semantics (tests + non-Pallas fallback)."""
    B, H, D = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    s_max = k_cache.shape[1]
    k = jnp.repeat(k_cache, G, axis=2) if G > 1 else k_cache
    v = jnp.repeat(v_cache, G, axis=2) if G > 1 else v_cache
    logits = jnp.einsum("bhd,bkhd->bhk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / math.sqrt(D)
    valid = jnp.arange(s_max)[None, None, :] < jnp.asarray(
        lengths, jnp.int32)[:, None, None]
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # zero masked probs/values explicitly: uninitialized cache rows can be
    # NaN and 0*NaN = NaN
    probs = jnp.where(valid, probs, 0.0)
    row_valid = (jnp.arange(s_max)[None, :, None, None]
                 < jnp.asarray(lengths, jnp.int32)[:, None, None, None])
    v = jnp.where(row_valid, v, 0.0)
    out = jnp.einsum("bhk,bkhd->bhd", probs.astype(q.dtype), v)
    return out
