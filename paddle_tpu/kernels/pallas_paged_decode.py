"""Pallas TPU ragged *paged* decode attention — single-query attention
that walks a per-sequence block table over a shared KV block pool.

This is the kernel shape of "Ragged Paged Attention: A High-Performance
and Flexible LLM Inference Kernel for TPU" (PAPERS.md) applied to the
serving stack's :class:`~paddle_tpu.serving.block_manager.BlockManager`
pool: instead of a dense per-slot cache ``[B, S_max, Hkv, D]``, the KV
lives once in a pool ``[num_blocks, block_size, Hkv, D]`` and each
sequence owns a row of a block table ``[B, max_blocks]`` naming the
physical blocks that spell its logical cache. Prefix-cache hits are
ZERO-COPY: a hit's table row simply references the published blocks, so
concurrent sequences sharing a system prompt read the same physical
block (one block, N refs) and admission never dispatches an install
copy.

Design points, inherited from ``pallas_decode.py`` (same Mosaic-
conservative lowering, same block-diagonal wide-query GQA trick):

- **Table-indirect DMA**: the KV BlockSpec index map reads the
  scalar-prefetched table — grid step ``(b, ki)`` fetches pool block
  ``tables[b, ki]``. The pool itself never moves or re-layouts; the
  indirection IS the gather, resolved at DMA-issue time.
- **Ragged skip**: blocks fully past ``lengths[b]`` clamp their table
  index to the row's last valid entry; Pallas elides the copy when the
  block index repeats, so HBM traffic scales with the VALID logical
  cache, and the compute for those steps is ``pl.when``-gated off.
- **Sentinel tables**: dead slots carry table entries ``>= num_blocks``;
  the index map clamps them into range (a harmless read of an arbitrary
  block) and the row's ``length == 0`` masks everything out.
- **2D-tile conservatism**: the KV block ``(1, block_size, Hkv*D)``
  has last-two dims equal to the pool array's trailing dims, the same
  always-legal tiling the dense decode kernel uses.

Inference-only (no VJP): decode never backpropagates.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_decode import decode_attention_reference
from .pallas_flash import _cparams, _interpret_mode

NEG_INF = -1e30


def _head_scale_mat(s, rows, gh, hkv):
    """Per-(wide-row, KV-row) dequant factors for an int8 pool block
    (README "Quantized serving"): wide row ``w`` belongs to KV head
    ``(w % gh) // (gh // hkv)`` and its dequant factor for pool row
    ``j`` is that head's scale ``s[j, h]``. Rather than interleave-
    repeating the scale plane across each head's D lanes (a lane-dim
    reshape Mosaic dislikes), build the [rows, hkv] head one-hot from
    iota and take ONE small dot with the scale plane — 2D ops only,
    the kernels' conservative-tiling discipline. ``s``: [block_k, hkv]
    fp32 → returns [rows, block_k] fp32."""
    g = gh // hkv
    w = jax.lax.broadcasted_iota(jnp.int32, (rows, hkv), 0)
    h = jax.lax.broadcasted_iota(jnp.int32, (rows, hkv), 1)
    onehot = jnp.where((w % gh) // g == h, 1.0, 0.0).astype(jnp.float32)
    return jax.lax.dot_general(onehot, s, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _block_scale_vec(s, rows, gh, hkv):
    """The fp8 sibling of :func:`_head_scale_mat`: an fp8 pool's scale
    planes are per-BLOCK (one fp32 scalar per (block, head) — README
    "Quantized serving"), so the dequant factor is constant across the
    block's pool rows and depends only on the wide row's KV head. Same
    one-hot trick, contracted with the block's ``[1, hkv]`` scale
    vector → ``[rows, 1]``, broadcast over the logits/probs columns
    post-dot. 2D ops only."""
    g = gh // hkv
    w = jax.lax.broadcasted_iota(jnp.int32, (rows, hkv), 0)
    h = jax.lax.broadcasted_iota(jnp.int32, (rows, hkv), 1)
    onehot = jnp.where((w % gh) // g == h, 1.0, 0.0).astype(jnp.float32)
    return jax.lax.dot_general(onehot, s, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _paged_kernel(len_ref, tbl_ref, *refs, scale, block_k,
                  quantized=False, hkv=0):
    # positional ref layout follows the pallas_call spec lists: inputs
    # (q, k, v[, k_scale, v_scale]), then the output, then scratch
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr,
         acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
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
        if quantized:
            # quantized pool: the DMA above moved int8/fp8 (the HBM
            # win); the upcast happens HERE, right after it — the data
            # converts in VMEM on the way into the MXU (fused into the
            # dot, never materialized back to HBM), and the scales
            # apply POST-dot: int8's per-row-per-head planes via the
            # head one-hot trick (_head_scale_mat), fp8's per-block
            # planes as a per-wide-row factor (_block_scale_vec) —
            # both separable because the block-diagonal wide rows pair
            # each output row with exactly one KV head
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quantized == "fp8":
            s = s * _block_scale_vec(ks_ref[...], s.shape[0], s.shape[0],
                                     hkv)
        elif quantized:
            s = s * _head_scale_mat(ks_ref[0], s.shape[0], s.shape[0],
                                    hkv)
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # exp hits exact 0 on masked cols, but pool rows past `length`
        # may hold another block's garbage — zero them out of PV
        p = jnp.where(cols < length, p, 0.0)
        v = jnp.where(
            ki * block_k
            + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) < length,
            v, jnp.zeros_like(v))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        if quantized == "fp8":
            p = p * _block_scale_vec(vs_ref[...], p.shape[0], p.shape[0],
                                     hkv)
        elif quantized:
            # V dequant, same separability: fold the scales into P
            # (P_wj * sv[j, head(w)]) and dot with the raw int8 values
            p = p * _head_scale_mat(vs_ref[0], p.shape[0], p.shape[0],
                                    hkv)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def _paged_call(q_wide, pool_k, pool_v, tables, lengths, scale, interpret,
                scales=None):
    """q_wide: [B, H, KD] block-diagonal; pool_*: [num_blocks, bs, KD];
    tables: [B, max_blocks] int32 physical block ids; scales: None, or
    ``(k_scale, v_scale)`` fp32 planes — [num_blocks, bs, Hkv] for an
    int8 pool (per-row), [num_blocks, Hkv] for an fp8 pool (per-block;
    the plane rank is the mode switch). Either way the dequant happens
    in-kernel, right after the table-indirect DMA."""
    B, H, KD = q_wide.shape
    num_blocks, bs = pool_k.shape[0], pool_k.shape[1]
    nk = tables.shape[1]
    grid = (B, nk)
    quantized = False if scales is None else \
        ("fp8" if scales[0].ndim == 2 else "int8")
    hkv = scales[0].shape[-1] if quantized else 0
    kernel = functools.partial(_paged_kernel, scale=scale, block_k=bs,
                               quantized=quantized, hkv=hkv)

    def _kv_index(b, ki, lens, tbl):
        # table-indirect fetch with the dense kernel's ragged-skip clamp:
        # steps past the last valid logical block re-reference it (copy
        # elided on repeat), and sentinel entries (dead slots, unmapped
        # tail) clamp into the pool — a harmless read, masked by length.
        last = (jnp.maximum(lens[b], 1) - 1) // bs
        phys = tbl[b, jnp.minimum(ki, last)]
        return (jnp.clip(phys, 0, num_blocks - 1), 0, 0)

    def _kv_index2(b, ki, lens, tbl):
        # the fp8 scale planes' 2D twin (per-block planes have no row
        # axis): same clamp, same physical block
        return _kv_index(b, ki, lens, tbl)[:2]

    in_specs = [
        pl.BlockSpec((1, H, KD), lambda b, ki, lens, tbl: (b, 0, 0)),
        pl.BlockSpec((1, bs, KD), _kv_index),
        pl.BlockSpec((1, bs, KD), _kv_index),
    ]
    args = [lengths, tables, q_wide, pool_k, pool_v]
    if quantized == "fp8":
        in_specs += [pl.BlockSpec((1, hkv), _kv_index2),
                     pl.BlockSpec((1, hkv), _kv_index2)]
        args += [scales[0], scales[1]]
    elif quantized:
        # the scale planes ride the SAME table-indirect index map as
        # the data blocks: one block's scales arrive with its values
        in_specs += [pl.BlockSpec((1, bs, hkv), _kv_index),
                     pl.BlockSpec((1, bs, hkv), _kv_index)]
        args += [scales[0], scales[1]]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, KD),
                                   lambda b, ki, lens, tbl: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, KD), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, KD), q_wide.dtype),
        compiler_params=_cparams(("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(*args)
    return out


# Inference-only custom_vjp, same rationale as pallas_decode: the eager
# dispatch linearizes through every op and scalar-prefetch pallas_calls
# don't linearize in interpret mode.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _paged(q_wide, pool_k, pool_v, tables, lengths, scale):
    return _paged_call(q_wide, pool_k, pool_v, tables, lengths, scale,
                       _interpret_mode())


def _paged_fwd_rule(q_wide, pool_k, pool_v, tables, lengths, scale):
    return _paged(q_wide, pool_k, pool_v, tables, lengths, scale), None


def _paged_bwd_rule(scale, res, g):
    raise NotImplementedError(
        "paged_decode_attention_pallas is inference-only (single-token "
        "decode never backpropagates)")


_paged.defvjp(_paged_fwd_rule, _paged_bwd_rule)


# quantized twin (the arg count differs, so it needs its own custom_vjp
# wrapper; same inference-only rationale)
@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _paged_q(q_wide, pool_k, pool_v, k_scale, v_scale, tables, lengths,
             scale):
    return _paged_call(q_wide, pool_k, pool_v, tables, lengths, scale,
                       _interpret_mode(), scales=(k_scale, v_scale))


def _paged_q_fwd_rule(q_wide, pool_k, pool_v, k_scale, v_scale, tables,
                      lengths, scale):
    return _paged_q(q_wide, pool_k, pool_v, k_scale, v_scale, tables,
                    lengths, scale), None


def _paged_q_bwd_rule(scale, res, g):
    raise NotImplementedError(
        "paged_decode_attention_pallas is inference-only (single-token "
        "decode never backpropagates)")


_paged_q.defvjp(_paged_q_fwd_rule, _paged_q_bwd_rule)


def paged_decode_attention_pallas(q, pool_k, pool_v, tables, lengths,
                                  k_scale=None, v_scale=None):
    """Single-token decode attention through a block table.

    q:        [B, H, D]              — one query token per sequence
    pool_k:   [num_blocks, bs, Hkv, D]  — the shared KV block pool
    pool_v:   [num_blocks, bs, Hkv, D]
    tables:   [B, max_blocks] int32  — physical block ids per sequence
                                       (entries >= num_blocks = unmapped)
    lengths:  [B] int32              — valid logical rows per sequence
    k_scale/v_scale: None, or fp32 scale planes — [num_blocks, bs, Hkv]
              per-row for an int8 pool, [num_blocks, Hkv] per-block
              for an fp8 pool (README "Quantized serving") — the
              kernel DMAs the quantized blocks and upcasts in VMEM
              right after the table-indirect fetch (fused into the
              dot), so HBM traffic is 1 byte/value while the MXU math
              stays full-precision
    returns:  [B, H, D]

    The logical cache of row ``b`` is ``pool[tables[b]]`` flattened to
    ``[max_blocks * bs]`` rows, of which ``lengths[b]`` are valid. GQA
    is resolved with the block-diagonal wide-query trick (see
    ``pallas_decode.py``); blocks past a row's length are never fetched.
    """
    B, H, D = q.shape
    Hkv = pool_k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    num_blocks, bs = pool_k.shape[0], pool_k.shape[1]
    KD = Hkv * D
    scale = 1.0 / math.sqrt(D)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    tables = jnp.asarray(tables, jnp.int32).reshape(B, -1)
    eye = jnp.eye(Hkv, dtype=q.dtype)
    q_wide = jnp.einsum("bkgd,kj->bkgjd", q.reshape(B, Hkv, G, D), eye)
    q_wide = q_wide.reshape(B, H, KD)
    if k_scale is not None:
        out_wide = _paged_q(q_wide, pool_k.reshape(num_blocks, bs, KD),
                            pool_v.reshape(num_blocks, bs, KD),
                            k_scale, v_scale, tables, lengths, scale)
    else:
        out_wide = _paged(q_wide, pool_k.reshape(num_blocks, bs, KD),
                          pool_v.reshape(num_blocks, bs, KD), tables,
                          lengths, scale)
    out = jnp.einsum("bkgjd,kj->bkgd",
                     out_wide.reshape(B, Hkv, G, Hkv, D), eye)
    return out.reshape(B, H, D)


def paged_decode_attention_reference(q, pool_k, pool_v, tables, lengths,
                                     k_scale=None, v_scale=None):
    """jnp oracle with identical semantics: materialize each row's
    logical cache by gathering its table (clip-mode keeps sentinel
    entries harmless — masked by ``lengths``), then run the dense
    ragged reference. A quantized pool (``k_scale``/``v_scale`` given)
    dequantizes right after the gather — the same
    fetch-then-dequantize order as the Pallas kernel; fp8's per-block
    planes broadcast over each block's rows."""
    B = q.shape[0]
    num_blocks, bs, Hkv, D = pool_k.shape
    mb = tables.shape[1]
    tables = jnp.asarray(tables, jnp.int32)
    k = jnp.take(pool_k, tables, axis=0,
                 mode="clip").reshape(B, mb * bs, Hkv, D)
    v = jnp.take(pool_v, tables, axis=0,
                 mode="clip").reshape(B, mb * bs, Hkv, D)
    if k_scale is not None:
        ks = jnp.take(k_scale, tables, axis=0, mode="clip")
        vs = jnp.take(v_scale, tables, axis=0, mode="clip")
        if k_scale.ndim == 2:           # fp8: [B, mb, Hkv] per-block
            ks = jnp.repeat(ks, bs, axis=1)
            vs = jnp.repeat(vs, bs, axis=1)
        else:                           # int8: [B, mb, bs, Hkv] per-row
            ks = ks.reshape(B, mb * bs, Hkv)
            vs = vs.reshape(B, mb * bs, Hkv)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    return decode_attention_reference(q, k, v, lengths)
