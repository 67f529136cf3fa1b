"""Pallas TPU fused whole-tick decode — ONE ``pallas_call`` that runs a
decode tick's ENTIRE per-token layer stack (README "One-kernel decode").

The serving stack's decode tick (``serving.decode._fused_decode_tick``)
is a ``lax.scan`` over the stacked layer weights: each scanned layer
launches the paged attention kernel plus the XLA ops between launches
(RMS norms, RoPE, the QKV/o/MLP projections, SwiGLU), and the epilogue
(final norm, lm head, sampling) launches again — so a tick is
O(num_layers) device-side launches even after multi-tick (PR 12)
amortized the HOST sync to one per n tokens. This module collapses the
tick to O(1): the layer loop becomes the Pallas **grid** dimension
(MPK's mega-kernel compilation strategy, PAPERS.md — the persistent
program owns the loop; the launch happens once), with

- **weights streamed per grid step**: every stacked weight leaf (and
  its int8 weight-only scale plane) is layer-sliced by its BlockSpec
  index map, so grid step ``l`` DMAs exactly layer ``l``'s weights into
  VMEM — the same HBM streaming discipline as the scan, without the
  per-layer launch;
- **the residual stream carried in VMEM scratch** across grid steps
  (``dimension_semantics=("arbitrary",)`` — the grid is sequential, so
  scratch persists layer to layer exactly like a scan carry);
- **paged table-indirect K/V in-kernel**: the block tables and
  post-append lengths ride the scalar-prefetch channel; the append
  scatters into the layer's pool slice (quantizing on write — int8
  per-row scale planes / fp8 saturating cast, ``_kv_write`` verbatim)
  and the attention walks the table with the SAME online-softmax
  blockwise math as ``pallas_paged_decode._paged_kernel`` (wide-query
  block-diagonal GQA, ragged skip clamp, in-kernel int8/fp8 dequant
  right after the fetch);
- **the sampling epilogue fused**: at the last grid step the final
  norm, lm-head matmul, per-row PRNG split and greedy/top-k sample run
  inside the same program, so the tick's device work is one launch,
  sampled token included.

**Bit-identity contract**: the kernel body replays the scanned tick's
op sequence EXACTLY — same primitive, same operand shapes, same
reduction order, per layer and per block — so under interpret mode
(CPU) the fused tick is byte-identical to the scanned baseline, greedy
AND seeded-sampled, across fp32/int8/fp8 pools and int8 weight-only
stacks (pinned by ``tests/test_fused_tick.py``). The jnp oracle
(:func:`fused_decode_tick_reference`) IS the scanned implementation —
it defers to ``serving.decode._fused_decode_tick`` with the fusion knob
off, so oracle divergence is impossible by construction.

Dispatch rule (:func:`fused_decode_tick`): the mega-kernel serves the
single-chip Pallas-attention geometry (``decode_attn == "pallas"``,
``tp_reduce is None``, no int8 activations). TP layer bodies need the
cross-shard all-reduce pair between projections — a remote-DMA
follow-on on real hardware, today routed to the oracle so the fused
knob still composes with ``tp`` byte-identically — and the a8/jnp
modes take the oracle for the same reason the scanned path does.

Inference-only (no VJP): decode never backpropagates.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_flash import _cparams, _interpret_mode
from .pallas_paged_decode import NEG_INF, _block_scale_vec, _head_scale_mat


def fused_decode_tick(params, stack, head, tables, sin, cos, tok, pk_all,
                      pv_all, lens, kys, app_mask, temps, top_ks, *, nh,
                      nkv, hd, eps, decode_attn, tp_reduce=None, a8=False):
    """THE fused-tick dispatch: one whole-tick ``pallas_call`` on the
    single-chip Pallas geometry, the jnp oracle (== the scanned tick)
    everywhere else. Same signature and return contract as
    ``serving.decode._fused_decode_tick`` —
    ``(next_tok, pk', pv', keys')``."""
    if decode_attn == "pallas" and tp_reduce is None and not a8:
        return _fused_tick_pallas(
            params, stack, head, tables, sin, cos, tok, pk_all, pv_all,
            lens, kys, app_mask, temps, top_ks, nh=nh, nkv=nkv, hd=hd,
            eps=eps)
    return fused_decode_tick_reference(
        params, stack, head, tables, sin, cos, tok, pk_all, pv_all, lens,
        kys, app_mask, temps, top_ks, nh=nh, nkv=nkv, hd=hd, eps=eps,
        decode_attn=decode_attn, tp_reduce=tp_reduce, a8=a8)


def fused_decode_tick_reference(params, stack, head, tables, sin, cos,
                                tok, pk_all, pv_all, lens, kys, app_mask,
                                temps, top_ks, *, nh, nkv, hd, eps,
                                decode_attn, tp_reduce=None, a8=False):
    """jnp oracle: replays the existing scanned-tick op sequence
    EXACTLY, by construction — it is a call back into
    ``serving.decode._fused_decode_tick`` with fusion off (lazy import;
    the serving module imports this one)."""
    from ..serving.decode import _fused_decode_tick
    return _fused_decode_tick(
        params, stack, head, tables, sin, cos, tok, pk_all, pv_all, lens,
        kys, app_mask, temps, top_ks, nh=nh, nkv=nkv, hd=hd, eps=eps,
        decode_attn=decode_attn, tp_reduce=tp_reduce, a8=a8, fused=False)


def _fused_tick_pallas(params, stack, head, tables, sin, cos, tok, pk_all,
                       pv_all, lens, kys, app_mask, temps, top_ks, *, nh,
                       nkv, hd, eps):
    # lazy serving imports (this module is imported by serving.decode):
    # the kernel body calls the SAME helpers the scanned tick scans
    # over, so the two paths cannot drift op-by-op
    from ..models.llama import _qkv_bshd, _rms, _swiglu_raw
    from ..serving.decode import (_apply_rope_rows, _kv_data, _kv_write,
                                  sample_rows)

    R = tok.shape[0]
    pk_data = _kv_data(pk_all)
    L, nb, bs = pk_data.shape[0], pk_data.shape[1], pk_data.shape[2]
    mb = tables.shape[1]
    s_tot = mb * bs
    wdt = params["embed"].dtype
    hdim = params["embed"].shape[1]
    kd = nkv * hd
    att_scale = 1.0 / math.sqrt(hd)

    # ---- prelude (the scanned tick's pre-scan ops, verbatim): embed
    # gather, per-row rope rows at each row's position, append target
    x = jnp.take(params["embed"], tok[:, None], axis=0)     # [R, 1, H]
    sin_r = jnp.take(sin, lens, axis=0, mode="clip")
    cos_r = jnp.take(cos, lens, axis=0, mode="clip")
    bi = jnp.minimum(lens // bs, mb - 1)
    phys = jnp.take_along_axis(tables, bi[:, None], axis=1)[:, 0]
    phys = jnp.where((app_mask > 0) & (lens < s_tot), phys, nb)
    prow = lens % bs
    att_lens = jnp.asarray(lens + app_mask, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32).reshape(R, mb)

    # ---- flatten the layer-stacked operands: each stack entry is a
    # dense [L, ...] array or an int8 weight-only (q, scale) pair —
    # every leaf gets a layer-slicing BlockSpec so grid step l streams
    # exactly layer l's bytes
    w_pairs = tuple(isinstance(e, tuple) for e in stack)
    w_leaves = []
    for entry in stack:
        w_leaves.extend(entry if isinstance(entry, tuple) else (entry,))
    kvq = isinstance(pk_all, tuple)
    if kvq:
        pool_leaves = [pk_all[0], pk_all[1], pv_all[0], pv_all[1]]
        fp8 = pk_all[0].dtype == jnp.float8_e4m3fn
    else:
        pool_leaves = [pk_all, pv_all]
        fp8 = False
    n_w, n_pool = len(w_leaves), len(pool_leaves)

    def _layer_spec(a):
        shp = (1,) + a.shape[1:]
        nd = len(shp)
        return pl.BlockSpec(shp, lambda l, *_s, _n=nd: (l,) + (0,) * (_n - 1))

    def _const_spec(a):
        nd = a.ndim
        return pl.BlockSpec(a.shape, lambda l, *_s, _n=nd: (0,) * _n)

    const_args = [x, sin_r, cos_r, phys, prow, head,
                  params["final_norm"], kys, temps,
                  jnp.asarray(top_ks, jnp.int32)]

    def kernel(tbl_ref, alen_ref, x_ref, sin_ref, cos_ref, phys_ref,
               prow_ref, head_ref, fnorm_ref, keys_ref, temps_ref,
               topk_ref, *rest):
        w_refs = rest[:n_w]
        pool_refs = rest[n_w:n_w + n_pool]
        o_nxt_ref = rest[n_w + n_pool]
        o_keys_ref = rest[n_w + n_pool + 1]
        o_pool_refs = rest[n_w + n_pool + 2:n_w + n_pool + 2 + n_pool]
        h_scr = rest[-1]
        l = pl.program_id(0)
        nL = pl.num_programs(0)

        @pl.when(l == 0)
        def _init():
            h_scr[:] = x_ref[:]

        h = h_scr[:]                                        # [R, 1, H]

        # this grid step's layer weights (int8 weight-only pairs
        # dequantize HERE, in VMEM — serving.decode._dq verbatim — so
        # HBM streamed 1 byte/weight)
        ws, i = [], 0
        for is_pair in w_pairs:
            if is_pair:
                q8, s8 = w_refs[i][0], w_refs[i + 1][0]
                ws.append((q8.astype(jnp.float32) * s8).astype(wdt))
                i += 2
            else:
                ws.append(w_refs[i][0])
                i += 1
        lwq, lwk, lwv, lwo, lgt_, lup_, ldn_, lin, lpost = ws

        hn = _rms(h, lin, eps)
        q, k, v = _qkv_bshd(hn, lwq, lwk, lwv, nh, nkv, hd)
        q = _apply_rope_rows(q, sin_ref[:], cos_ref[:])
        k = _apply_rope_rows(k, sin_ref[:], cos_ref[:])

        # append into this layer's pool slice (quantize-on-write;
        # drop-mode keeps masked rows' writes out), then attend over
        # the UPDATED slice — the same write-then-read order as the
        # scanned tick
        physv, prowv = phys_ref[:], prow_ref[:]
        if kvq:
            pk_l = (pool_refs[0][0], pool_refs[1][0])
            pv_l = (pool_refs[2][0], pool_refs[3][0])
        else:
            pk_l = pool_refs[0][0]
            pv_l = pool_refs[1][0]
        pk_l = _kv_write(pk_l, (physv, prowv), k[:, 0])
        pv_l = _kv_write(pv_l, (physv, prowv), v[:, 0])
        if kvq:
            o_pool_refs[0][0] = pk_l[0]
            o_pool_refs[1][0] = pk_l[1]
            o_pool_refs[2][0] = pv_l[0]
            o_pool_refs[3][0] = pv_l[1]
            kd_, ksc = pk_l
            vd_, vsc = pv_l
        else:
            o_pool_refs[0][0] = pk_l
            o_pool_refs[1][0] = pv_l
            kd_, vd_ = pk_l, pv_l
            ksc = vsc = None

        # table-indirect paged attention: the online-softmax blockwise
        # walk of pallas_paged_decode._paged_kernel, replayed per
        # (row, table column) with the same wide-query block-diagonal
        # GQA assembly and the same ragged-skip clamp — bit-identical
        # to the per-layer attention launch it replaces
        qh = q[:, 0]                                       # [R, nh, hd]
        eye = jnp.eye(nkv, dtype=qh.dtype)
        q_wide = jnp.einsum("bkgd,kj->bkgjd",
                            qh.reshape(R, nkv, nh // nkv, hd),
                            eye).reshape(R, nh, kd)
        tbl = tbl_ref[...]
        alens = alen_ref[...]
        outs = []
        for b in range(R):
            length = alens[b]
            last = (jnp.maximum(length, 1) - 1) // bs
            m_s = jnp.full((nh, 1), NEG_INF, jnp.float32)
            l_s = jnp.zeros((nh, 1), jnp.float32)
            acc = jnp.zeros((nh, kd), jnp.float32)
            qb = q_wide[b]
            for ki in range(mb):
                idx = jnp.clip(tbl[b, jnp.minimum(ki, last)], 0, nb - 1)
                kb = jax.lax.dynamic_index_in_dim(kd_, idx, 0,
                                                  keepdims=False)
                vb = jax.lax.dynamic_index_in_dim(vd_, idx, 0,
                                                  keepdims=False)
                if kvq:
                    kb = kb.astype(jnp.float32)
                    vb = vb.astype(jnp.float32)
                s = jax.lax.dot_general(
                    qb, kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * att_scale
                if fp8:
                    ksb = jax.lax.dynamic_index_in_dim(ksc, idx, 0,
                                                       keepdims=True)
                    s = s * _block_scale_vec(ksb, nh, nh, nkv)
                elif kvq:
                    ksb = jax.lax.dynamic_index_in_dim(ksc, idx, 0,
                                                       keepdims=False)
                    s = s * _head_scale_mat(ksb, nh, nh, nkv)
                cols = ki * bs + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(cols < length, s, NEG_INF)
                m_new = jnp.maximum(m_s, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                p = jnp.where(cols < length, p, 0.0)
                vb = jnp.where(
                    ki * bs + jax.lax.broadcasted_iota(
                        jnp.int32, vb.shape, 0) < length,
                    vb, jnp.zeros_like(vb))
                alpha = jnp.exp(m_s - m_new)
                l_new = alpha * l_s + jnp.sum(p, axis=1, keepdims=True)
                if fp8:
                    vsb = jax.lax.dynamic_index_in_dim(vsc, idx, 0,
                                                       keepdims=True)
                    p = p * _block_scale_vec(vsb, nh, nh, nkv)
                elif kvq:
                    vsb = jax.lax.dynamic_index_in_dim(vsc, idx, 0,
                                                       keepdims=False)
                    p = p * _head_scale_mat(vsb, nh, nh, nkv)
                acc_new = acc * alpha + jax.lax.dot_general(
                    p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                live = ki * bs < length    # pl.when's ragged skip
                m_s = jnp.where(live, m_new, m_s)
                l_s = jnp.where(live, l_new, l_s)
                acc = jnp.where(live, acc_new, acc)
            l_f = jnp.maximum(l_s, 1e-30)
            outs.append((acc / l_f).astype(qh.dtype))
        out_wide = jnp.stack(outs)                          # [R, nh, kd]
        attn = jnp.einsum(
            "bkgjd,kj->bkgd",
            out_wide.reshape(R, nkv, nh // nkv, nkv, hd),
            eye).reshape(R, nh, hd)

        o = jnp.einsum("bsd,dh->bsh", attn.reshape(R, 1, nh * hd), lwo)
        h = h + o
        mlp = _swiglu_raw(_rms(h, lpost, eps), lgt_, lup_, ldn_)
        h = h + mlp
        h_scr[:] = h

        # fused sampling epilogue: final norm, lm head, per-row key
        # split and greedy/top-k sample — the tick returns with the
        # token already chosen, no second launch
        @pl.when(l == nL - 1)
        def _finish():
            lastt = _rms(h[:, 0], fnorm_ref[:], eps)
            lgts = jnp.einsum("bh,hv->bv", lastt, head_ref[:])
            b2 = jax.vmap(jax.random.split)(keys_ref[:])
            o_nxt_ref[:] = sample_rows(lgts, b2[:, 1], temps_ref[:],
                                       topk_ref[:])
            o_keys_ref[:] = b2[:, 0]

    out_shape = (
        [jax.ShapeDtypeStruct((R,), jnp.int32),
         jax.ShapeDtypeStruct((R, 2), jnp.uint32)]
        + [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pool_leaves])
    out_specs = (
        [pl.BlockSpec((R,), lambda l, *_s: (0,)),
         pl.BlockSpec((R, 2), lambda l, *_s: (0, 0))]
        + [_layer_spec(a) for a in pool_leaves])
    in_specs = ([_const_spec(a) for a in const_args]
                + [_layer_spec(a) for a in w_leaves]
                + [_layer_spec(a) for a in pool_leaves])

    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((R, 1, hdim), wdt)],
        ),
        out_shape=out_shape,
        compiler_params=_cparams(("arbitrary",)),
        interpret=_interpret_mode(),
        name="fused_decode_tick",
    )(tables, att_lens, *const_args, *w_leaves, *pool_leaves)

    nxt, nkeys = res[0], res[1]
    pools = res[2:]
    if kvq:
        npk = (pools[0], pools[1])
        npv = (pools[2], pools[3])
    else:
        npk, npv = pools[0], pools[1]
    return nxt, npk, npv, nkeys
