"""Jitted serving programs for the LLaMA-family decode path.

The engine (``engine.py``) runs two programs on its default path and
three more behind switches, all over one block-table paged KV pool:

- prefill (``build_prefill_fn``) — an admission group's full forward,
  returning its per-layer K/V (installed into the slots' pool blocks),
  the first sampled token and the advanced PRNG key. Prompt lengths are
  padded to buckets by the engine, so compilations are bounded by the
  bucket count, not the prompt count.
- the unified step (``build_ragged_step_fn``): every running slot's
  span-1 decode row and every planned prefill chunk's span-n row in
  ONE packed token buffer whose shape depends only on ``(num_slots,
  packed size)`` (the engine has two sizes: the slots' rows for a step
  with no chunk, the token budget for a step with one), then
  ``n_steps - 1`` fused single-token ticks. Block
  tables, span metadata and per-slot sampling knobs (temperature /
  top-k / PRNG key) are runtime ARRAYS, not trace constants, so one
  compilation a packed size serves every request mix.
- the paged suffix prefill (prefix-cache hits prefill their uncovered
  suffix), the multi-tick step (``decode_ticks > 1``) and the
  speculative verify (``spec_decode``).

Per-row raggedness: each row writes its new K/V through its block table
at its own logical position and attends over its own length — through
the ragged paged Pallas kernels or their jnp oracles with identical
semantics. Dead rows carry sentinel tables, so their writes drop. A model
with latent attention (a tree with ``wkv_a``) writes ONE row a token, the
normalised latent and the rotated shared key, into a pool with no head axis
and no V side, and the unified step attends over it in the absorbed form
(``kernels.pallas_mla_ragged_attention``); its whole-prompt prefill attends
in the expanded form and hands the same rows to the pool's writer. A hybrid
model (a tree with ``linear_layers``: periods of Gated DeltaNet layers and
then one full-attention layer) scans its periods, the pool holding rows for
the full layers only; a linear layer's cache is a float32 state and its
convolution's last inputs, in a store by slot that rides the programs like
the pool (``_hybrid_span_forward``, ``kernels.gated_delta_rule``); both kinds
of layer are ONE body, a mixer and then an FFN (``_mixer_ffn_layer``), so a
hybrid tree with ``router`` (``models.qwen3_next``) routes its FFNs inside the
period scan. A
decoder-hybrid-decoder model (a tree with ``self_layers``: pairs of a Mamba
layer and a window-attention layer, a middle Mamba layer that makes a memory
and a middle full-attention layer whose keys and values are THE cache, then
pairs of a Gated Memory Unit and a cross-attention layer that cache nothing)
keeps ONE pool layer, the Mamba layers' states and the window layers' rings
of keys in the stores by slot, and narrows the packed buffer to one row a
slot after the middle layers (``_sambay_span_forward``,
``kernels.selective_scan``, the ragged kernel's ``window``). A model whose
blocks are ONE mixer each (a tree with ``ssd_layers``: units of a Mamba-2
block, optionally an attention block, then a routed FFN) scans its units, the
pool holding rows for the attention blocks only, a Mamba-2 block's state and
its convolution's last inputs in the store by slot (``_mixer_span_forward``,
``kernels.ssd``). A model of Mamba-1 layers around one attention layer a
period (a tree with ``mamba_layers``) runs the hybrid model's period scan with
the full layer where its tree puts it and the decoder-hybrid-decoder's Mamba
mixer with three inner norms (``_jamba_span_forward``).

Sampling is row-vectorized: greedy where ``temps <= 0``, else top-k
temperature sampling with a per-row ``jax.random.categorical`` under a
per-row key; keys advance by one split per token, so a request's token
stream depends only on its own key — not on batch composition, admission
timing, or the other slots (the property the mid-flight-admission tests
pin down).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..kernels.flash_attention import attention as _attention
from ..kernels.gated_delta_rule import (gdn_chunk_scan, gdn_recurrent_update,
                                         gdn_reference, l2norm,
                                         state_shape as gdn_state_shape)
from ..kernels.dsa import (dsa_attention_pallas, dsa_attention_reference,
                           dsa_index_scores_pallas,
                           dsa_index_scores_reference, dsa_select,
                           selection_bias)
from ..kernels.moe_ffn import moe_ffn, relu2
from ..kernels.pallas_paged_decode import (paged_decode_attention_pallas,
                                           paged_decode_attention_reference)
from ..kernels.pallas_mla_ragged_attention import (
    grid_params as _mla_grid_params, latent_row_width,
    mla_ragged_attention_pallas, mla_ragged_attention_reference)
from ..kernels.pallas_ragged_attention import (
    grid_params as _ragged_grid_params, ragged_attention_reference,
    ragged_paged_attention_pallas)
from ..kernels.selective_scan import (ssm_chunk_scan, ssm_recurrent_update,
                                      ssm_reference)
from ..kernels.ssd import (ssd_chunk_scan, ssd_recurrent_update,
                           ssd_reference, state_shape as ssd_state_shape)
from ..models.deepseek_v2 import rope_tables as _mla_rope_tables
from ..models.llama import _apply_rope, _qkv_bshd, _rms, _rope_tables, \
    _swiglu_raw
from .kv_cache import kv_rows, quantize_kv_rows, quantize_kv_rows_fp8

NEG_INF = -1e30

#: the per-layer entries of a layer with grouped-query attention and a SwiGLU,
#: in the order every program that scans them unpacks (the seven projections
#: first: ``_dq_layer``)
_STACK_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "input_ln", "post_ln")

#: the same for a layer with latent attention (a tree with ``wkv_a``; module
#: docstring of ``models.deepseek_v2``): the query's and the cache's down- and
#: up-projections with the two norms between them
_MLA_STACK_KEYS = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up",
                   "w_down", "input_ln", "post_ln", "q_a_ln", "kv_a_ln")

#: per-layer entries only some models bring; what a parameter tree holds of
#: them chooses the layer body (``_decoder_layer``): ``q_norm`` / ``k_norm``
#: normalise q and k before the rotary embedding, ``router`` makes the FFN a
#: routed one over ``w_gate`` / ``w_up`` / ``w_down`` with a leading expert
#: dim, ``ws_*`` is a shared expert every row runs beside the routed ones,
#: ``router_bias`` makes the router a sigmoid one whose selection (not its
#: weights) adds that bias (``kernels.moe_ffn``), ``idx_layer`` marks latent
#: attention over a learned SELECTION of the cached rows (``kernels.dsa``):
#: for a layer with an indexer its layer of the index-key pool, -1 for a
#: layer that attends over the set the nearest such layer before it selected;
#: ``idx_slot`` is then the indexer's place in ``_INDEXER_KEYS``' stacks
_STACK_EXTRA_KEYS = ("q_norm", "k_norm", "router", "ws_gate", "ws_up",
                     "ws_down", "router_bias", "idx_layer", "idx_slot")

#: an indexer's weights, stacked over the layers of a tree that HAVE one
#: (``[indexers, ...]``, not scanned: a layer reads its own at ``idx_slot``):
#: the index query's up-projection from the query's latent, the index key's
#: projection and LayerNorm, the per-token head weights
_INDEXER_KEYS = ("idx_wq_b", "idx_wk", "idx_k_ln_w", "idx_k_ln_b", "idx_w")

#: a hybrid model (``models.olmo_hybrid``): its layers come in PERIODS, some
#: linear-attention (Gated DeltaNet) layers and then one full-attention layer.
#: The full layers' entries are the tree's own, ``[periods, ...]``; the linear
#: layers' lie under ``linear_layers``, a tuple with one tree ``[periods,
#: ...]`` for each place in the period (arrays of their own: a scan then cuts
#: ONE layer's weights out of each, which XLA fuses into the matmul that
#: reads them; cut out of ``[periods, layers a period, ...]`` the three
#: layers' weights were copied every period, a third of a step: PERF.md).
#: Both kinds are ``_mixer_ffn_layer`` around their mixer, and what a layer's
#: tree holds of ``_HYBRID_FFN_KEYS`` chooses the rest: ``attn_out_ln`` /
#: ``ffn_out_ln`` normalise each sub-layer's OUTPUT (Olmo-Hybrid), ``input_ln``
#: / ``post_ln`` its INPUT; ``router`` makes the FFN a routed one whose expert
#: stacks ``[periods, E_held, ...]`` (one set for each place in the period)
#: are read in place at the period's index, ``ws_*`` a shared expert beside
#: it and ``ws_sgate`` that expert's sigmoid gate (Qwen3-Next), ``router_bias``
#: a sigmoid router's selection bias (MiMo-V2-Flash).
_HYBRID_FULL_KEYS = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_GDN_KEYS = ("gdn_wqkv", "gdn_wz", "gdn_wab", "gdn_conv", "gdn_A_log",
             "gdn_dt_bias", "gdn_o_norm", "gdn_wo")
_HYBRID_FFN_KEYS = ("w_gate", "w_up", "w_down", "attn_out_ln", "ffn_out_ln",
                    "input_ln", "post_ln", "router", "router_bias", "ws_gate",
                    "ws_up", "ws_down", "ws_sgate")

#: a model whose blocks are ONE mixer each (``models.nemotron_h``): UNITS of a
#: Mamba-2 block, optionally an attention block, then a routed FFN of
#: two-matrix experts. The routed FFNs' entries are the tree's own, ``[units,
#: ...]`` (no ``w_gate``: ``kernels.moe_ffn``); the Mamba-2 blocks' lie under
#: ``ssd_layers`` ``[units, ...]``, the attention blocks' under
#: ``attn_layers`` ``[attention blocks, ...]``, read at ``attn_at`` ``[units]``:
#: a unit's attention block's place among them, which is its layer of the KV
#: pool (-1: the unit has none). Every block normalises its INPUT (``ln`` /
#: ``moe_ln``).
_SSD_KEYS = ("ln", "ssd_in", "ssd_dt", "ssd_conv", "ssd_conv_b", "ssd_A_log",
             "ssd_D", "ssd_dt_b", "ssd_norm", "ssd_out")
_MIXER_ATTN_KEYS = ("ln", "wq", "wk", "wv", "wo")
_MIXER_MOE_KEYS = ("moe_ln", "router", "router_bias", "ws_up", "ws_down")

#: a model of WINDOW layers around one full layer a period (``models.
#: mimo_v2_flash``): a leading stack of dense layers (full attention, a dense
#: SwiGLU) under ``dense_layers``, then PERIODS of some window layers and one
#: full layer, every one with a routed FFN (``_hybrid_scan``). The full
#: layers' entries are the tree's own ``[periods, ...]``, the window layers'
#: lie under ``window_layers``, a tuple with one tree ``[periods, ...]`` a
#: place in the period; a window layer holds ``sink``, a float32 logit a query
#: head, and may have KV heads of its own count (what ``wk`` holds). The pool
#: has a layer a dense and a full layer (the dense ones first); a window
#: layer's keys and values lie in its ring of the store by slot
#: (``ring_coords``), whose row is the window layers' own.
_WINDOW_KEYS = ("wq", "wk", "wv", "wo", "sink")

#: what marks a tree whose layer only the default engine's two programs were
#: taught (``ContinuousBatchingEngine`` raises for every other switch): the
#: extras of ``_decoder_layer``, and the key that names each model whose
#: forward is a scan of its own (``wkv_a``: latent attention;
#: ``linear_layers``: periods of Gated DeltaNet layers around a full layer,
#: Olmo-Hybrid's and Qwen3-Next's alike; ``self_layers``: decoder-hybrid-
#: decoder; ``ssd_layers``: one mixer a block; ``mamba_layers``: Mamba-1
#: layers around one attention layer; ``window_layers``: window layers with a
#: sink around one full layer)
TAUGHT_KEYS = _STACK_EXTRA_KEYS + ("wkv_a", "linear_layers", "self_layers",
                                   "ssd_layers", "mamba_layers",
                                   "window_layers")


def attention_grid(params, pool, table_entries, heads, packed_tokens, tp=1,
                   *, head_dim, pool_v=None):
    """The tiling (``block_q``, ``pages`` and, for the dense kernel,
    ``one_token``) of the attention kernel that ``_packed_span_forward``
    runs on this tree over the stored ``pool`` ``[L, num_blocks, bs, KD]``
    (a chip's share is ``KD // tp``; a window layers' ring ``[L, R, ring
    blocks, bs, KD]`` alike) and, where its row is another, the V side
    ``pool_v``: the kernel's own ``grid_params`` of
    what its call will see, the heads as they are, so the engine's
    ``ragged_grid_counts`` counts the grid the step really runs."""
    if "wkv_a" in params:
        return _mla_grid_params(table_entries, heads, packed_tokens)
    kd = pool.shape[-1] // tp
    value_dim = None
    if pool_v is not None and pool_v.shape[-1] != pool.shape[-1]:
        value_dim = pool_v.shape[-1] // tp // (kd // head_dim)
    return _ragged_grid_params(
        pool.dtype, pool.shape[-2], kd, table_entries,
        heads, packed_tokens, head_dim=head_dim, value_dim=value_dim)


#: a routed FFN's expert weights ``[L, E, ...]``: a layer scan does not
#: slice them (the grouped matmul reads its layer of the stack in place)
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _layer_stack(params):
    """(names, arrays, experts) of the per-layer entries a layer scan
    carries: the attention's and the FFN's (``_STACK_KEYS``, or
    ``_MLA_STACK_KEYS`` for a tree with ``wkv_a``), whichever extras this
    tree holds, and last the layer's index in this stack as ``layer``: what
    is too large to slice a layer out of (the KV pool, a routed FFN's expert
    stacks) is read in place at that index. For a tree with ``router``,
    ``experts`` is the three expert stacks, whole, and their places in the
    scanned tuple hold None; else ``experts`` is None."""
    base = _MLA_STACK_KEYS if "wkv_a" in params else _STACK_KEYS
    keys = base + tuple(k for k in _STACK_EXTRA_KEYS if k in params)
    routed = "router" in params
    stack = tuple(None if routed and k in _EXPERT_KEYS else params[k]
                  for k in keys)
    layers = jnp.arange(params["input_ln"].shape[0], dtype=jnp.int32)
    return (keys + ("layer",), stack + (layers,),
            tuple(params[k] for k in _EXPERT_KEYS) if routed else None)


def _layer_stacks(params):
    """The layer scans of one forward, in order, each ``(first layer, names,
    arrays, experts)``: a model whose leading layers differ from the rest (a
    dense FFN before routed ones) brings them as a tree of their own under
    ``dense_layers``, scanned first; ``first layer`` is where a stack's
    layers start in the model (and so in the KV pool). A model whose kinds
    of layer ALTERNATE by period (a tree with ``linear_layers``) is not a
    sequence of stacks: its forward is ``_hybrid_scan``, one scan over the
    periods whose body runs a period's layers in order."""
    stacks, first = [], 0
    for tree in (params.get("dense_layers"), params):
        if tree is not None:
            stacks.append((first,) + _layer_stack(tree))
            first += tree["input_ln"].shape[0]
    return stacks


def _indexers(params):
    """The indexers' weights of each stack of ``_layer_stacks``, in its
    order: a dict by ``_INDEXER_KEYS``, or None for a stack none of whose
    layers has one."""
    return [{k: tree[k] for k in _INDEXER_KEYS} if "idx_wq_b" in tree
            else None
            for tree in (params.get("dense_layers"), params)
            if tree is not None]


#: the decode-path projection matmuls quantize_weights=True converts
#: (README "Quantized serving"); norms and the embedding gather stay
#: full-precision (a gather reads one row — there is no bandwidth to
#: win — and norm weights are tiny but numerically load-bearing)
_WEIGHT_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def llama_decode_params(model):
    """Raw-array param pytree (+tied flag) for the decode programs."""
    p = dict(
        embed=model.embed_tokens.value, wq=model.wq.value,
        wk=model.wk.value, wv=model.wv.value, wo=model.wo.value,
        w_gate=model.w_gate.value, w_up=model.w_up.value,
        w_down=model.w_down.value, input_ln=model.input_ln.value,
        post_ln=model.post_ln.value, final_norm=model.final_norm.value,
        lm_head=(model.embed_tokens.value if model.lm_head is None
                 else model.lm_head.value))
    return p, model.lm_head is None


# --------------------------------------------- int8 weight-only decode
def quantize_decode_params(params, tied):
    """Convert the decode param pytree to int8 weight-only form — the
    engine's ``quantize_weights=True`` knob (README "Quantized
    serving"), riding the same per-channel absmax machinery as
    ``quantization.ConvertedLinear`` (``quantize_weight_int8``). Each
    projection weight becomes a ``(q int8, scale f32)`` pair — a
    pytree-structure change, so quantized engines key their programs
    apart in a shared jit cache — dequantized per layer inside the
    programs (``_dq_layer``): HBM streams int8, the MXU sees the
    dequantized convert. ``lm_head`` quantizes over its contraction
    axis for the orientation it is used in (tied heads run
    ``embed.T``); the embedding table itself stays full-precision for
    the token gather."""
    from ..quantization import quantize_weight_int8
    out = dict(params)
    for k in _WEIGHT_QUANT_KEYS:
        out[k] = quantize_weight_int8(params[k], reduce_axis=1)
    out["lm_head"] = quantize_weight_int8(params["lm_head"],
                                          reduce_axis=1 if tied else 0)
    return out


def _dq(w, dt):
    """Dequantize one int8 weight-only ``(q, scale)`` pair to ``dt``;
    full-precision arrays pass through untouched (the one branch every
    decode program shares, so quantized and raw params run the same
    impl — the pytree structure IS the trace variant)."""
    if isinstance(w, tuple):
        q, s = w
        return (q.astype(jnp.float32) * s).astype(dt)
    return w


def _dq_layer(lp, dt, a8=False):
    """Per-layer weight handoff: dequantize the 7 projection entries of
    one scanned layer tuple IN the layer body — one layer materializes
    at a time, so the weight stack still streams int8 from HBM — and
    pass everything after them (norm weights, cache slices) through
    untouched. Under ``a8`` (quantize_activations, README "Quantized
    serving") NOTHING dequantizes: the ``(q, scale)`` pairs flow
    straight to the int8×int8 projection helpers (``_a8_apply``), so
    no dequantized weight copy is ever materialized in the layer
    body."""
    if a8:
        return lp
    return tuple(_dq(w, dt) for w in lp[:7]) + tuple(lp[7:])


def _dq_head(params, tied, dt, a8=False):
    """The lm-head matmul operand, dequantized when quantized (tied
    heads transpose AFTER dequant — the scales were laid out for the
    stored orientation). Under ``a8`` the int8 pair passes through for
    the int8×int8 head matmul, pre-oriented: tied pairs transpose data
    AND scales — one int8 transpose, traced once outside the scan."""
    head = params["lm_head"]
    if a8 and isinstance(head, tuple):
        q, s = head
        return (q.T, s.T) if tied else (q, s)
    head = _dq(head, dt)
    return head.T if tied else head


# ------------------------------------------- int8×int8 activation path
# The ``quantize_activations=True`` decode path (README "Quantized
# serving"): every projection input is quantized per-row AT RUNTIME
# (the shared absmax rule, ``quantization.quantize_collective_int8``)
# and the matmul runs int8×int8 on the MXU — ``dot_general`` over the
# narrow operands with int32 accumulate, then ONE fused
# ``(act_scale ⊗ weight_scale)`` rescale post-dot. The projection
# helpers below dispatch on the weight's pytree structure, so the
# dense/w8 paths trace the exact same ops as before (the structure IS
# the trace variant) and the a8 layer body never materializes a
# dequantized weight.
def quantize_act_rows(x):
    """Per-row dynamic int8 activation quantization — each row (absmax
    over the last axis) gets its own fp32 scale. Returns ``(q int8,
    scale f32 [..., 1])``."""
    from ..quantization import quantize_collective_int8
    return quantize_collective_int8(x)


def _a8_apply(qx, sx, w):
    """One int8×int8 projection: quantized activations ``(qx, sx)``
    against an int8 weight-only ``(q, scale)`` pair — int32-accumulate
    dot, fused post-dot rescale. Returns fp32."""
    qw, sw = w
    acc = jax.lax.dot_general(qx, qw, (((qx.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw.reshape(-1)


def _a8_dot(x, w):
    """Quantize ``x`` per-row and run one int8×int8 projection."""
    qx, sx = quantize_act_rows(x)
    return _a8_apply(qx, sx, w).astype(x.dtype)


def _qkv_proj(hn, lwq, lwk, lwv, nh, nkv, hd):
    """The QKV projections — ``models.llama._qkv_bshd`` verbatim on
    dense weights; under quantize_activations the input quantizes
    per-row ONCE and feeds three int8×int8 dots."""
    if isinstance(lwq, tuple):
        B, S = hn.shape[0], hn.shape[1]
        dt = hn.dtype
        qx, sx = quantize_act_rows(hn)
        q = _a8_apply(qx, sx, lwq).astype(dt).reshape(B, S, nh, hd)
        k = _a8_apply(qx, sx, lwk).astype(dt).reshape(B, S, nkv, hd)
        v = _a8_apply(qx, sx, lwv).astype(dt).reshape(B, S, nkv, hd)
        return q, k, v
    return _qkv_bshd(hn, lwq, lwk, lwv, nh, nkv, hd)


def _swiglu_proj(hn, lg, lu, ld):
    """The SwiGLU MLP — ``models.llama._swiglu_raw`` verbatim on dense
    weights; under quantize_activations gate/up share one per-row act
    quant and down re-quantizes the gated product."""
    if isinstance(lg, tuple):
        with jax.named_scope("mlp"):
            qx, sx = quantize_act_rows(hn)
            g = jax.nn.silu(_a8_apply(qx, sx, lg))
            u = _a8_apply(qx, sx, lu)
            return _a8_dot(g * u, ld).astype(hn.dtype)
    return _swiglu_raw(hn, lg, lu, ld)


def _o_proj(attn2, lwo):
    """The attention output projection ``[B, S, nh*hd] @ wo``."""
    if isinstance(lwo, tuple):
        return _a8_dot(attn2, lwo)
    return jnp.einsum("bsd,dh->bsh", attn2, lwo)


def _rms_1p(x, w, eps):
    """RMSNorm with a ZERO-CENTRED weight: ``x / sqrt(mean(x^2) + eps) * (1 +
    w)``, all of it float32, the rows' dtype out (Qwen3-Next; a tree with
    ``norm_plus_one``)."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                             + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _norm_of(params):
    """The RMSNorm a model's tree asks for: ``_rms_1p`` with the marker
    ``norm_plus_one`` (every norm weight but a Gated DeltaNet layer's output
    norm is then zero-centred), else ``_rms``."""
    return _rms_1p if "norm_plus_one" in params else _rms


def _qk_norm(q, k, q_w, k_w, eps, norm=_rms):
    """RMSNorm of q and k ``[B, S, heads, D]`` before the rotary embedding:
    over the WHOLE projection, all heads at once (OLMoE, Olmo-Hybrid), or,
    where the weight is one head wide, a HEAD at a time (Qwen3-Next; with one
    head the two are the same)."""
    if q_w.shape[-1] == q.shape[-1]:
        return norm(q, q_w, eps), norm(k, k_w, eps)
    return (norm(q.reshape(q.shape[:2] + (-1,)), q_w, eps).reshape(q.shape),
            norm(k.reshape(k.shape[:2] + (-1,)), k_w, eps).reshape(k.shape))


def _rope_tables_for(seq_len, hd, theta, mla):
    """(sin, cos) of a program's rotary embedding: ``hd`` wide over plain
    frequencies, or for latent attention ``mla.rope`` wide over YaRN's."""
    if mla is None:
        return _rope_tables(seq_len, hd, theta)
    return _mla_rope_tables(seq_len, mla.rope, theta, mla.yarn)


def latent_rows(c_kv, k_pe):
    """What the latent pool stores of a token: ``[c_kv | k_pe | 0]`` at the
    pool's row width, as one KV head ``[..., 1, W]`` for ``_kv_write``."""
    pad = latent_row_width(c_kv.shape[-1], k_pe.shape[-1]) \
        - c_kv.shape[-1] - k_pe.shape[-1]
    row = jnp.concatenate(
        [c_kv, k_pe, jnp.zeros(k_pe.shape[:-1] + (pad,), c_kv.dtype)], -1)
    return row[..., None, :]


def mla_expanded_attention(q_nope, q_pe, c_kv, k_pe, w_kvb, *, mla):
    """Latent attention in the EXPANDED form over a whole sequence (whole-
    prompt prefill, ``forward``): the latent is up-projected to per-head
    keys ``[k_nope | k_pe]`` (``k_pe`` the one rotated vector a token, for
    every head) and values, and attended causally through the attention
    path every model shares (``kernels.flash_attention.attention``: flash on
    the chip from 512 tokens, plain below). That path takes one width for
    q, k and v and scales by its root, so q and k (192 wide) and v (128)
    are zero-padded to a common 256 and the model's scale is folded into q.
    q_nope ``[B, S, nh, nope]``, q_pe ``[B, S, nh, rope]``, c_kv ``[B, S,
    rank]``, k_pe ``[B, S, rope]``; returns ``[B, S, nh, v]``."""
    B, S, nh, _ = q_nope.shape
    kv = jnp.einsum("bsr,rd->bsd", c_kv, w_kvb).reshape(B, S, nh, -1)
    k = jnp.concatenate(
        [kv[..., :mla.nope],
         jnp.broadcast_to(k_pe[:, :, None, :], (B, S, nh, mla.rope))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    width = -(-max(q.shape[-1], mla.v) // 128) * 128

    def wide(x):
        return jnp.pad(x, [(0, 0)] * 3 + [(0, width - x.shape[-1])])

    q = (q.astype(jnp.float32) * (mla.scale * math.sqrt(width))
         ).astype(q.dtype)
    return _attention(wide(q), wide(k), wide(kv[..., mla.nope:]),
                      causal=True)[..., :mla.v]


def _mla_attention(hn, lw, *, nh, eps, rope, attend, mla):
    """Latent attention's projections around the program's ``attend(q_nope,
    q_pe, c_kv, k_pe, w_kvb) -> (attn [B, S, nh, v], carry)``, which brings
    the form (expanded, or absorbed over the latent pool) and the cache
    write. Scopes ``mla`` > ``mla_proj`` (here: the query's two projections,
    the cache's down-projection, the norms and the rotary embedding; the
    program's ``attend`` adds the up-projection or its absorption; the layer
    adds ``W_o``) and ``mla_attend`` (the program's kernel)."""
    B, S = hn.shape[0], hn.shape[1]
    with jax.named_scope("mla_proj"):
        c_q = _rms(jnp.einsum("bsh,hr->bsr", hn, lw["wq_a"]), lw["q_a_ln"],
                   eps)
        q = jnp.einsum("bsr,rd->bsd", c_q, lw["wq_b"]).reshape(B, S, nh, -1)
        kv = jnp.einsum("bsh,hr->bsr", hn, lw["wkv_a"])
        c_kv = _rms(kv[..., :mla.rank], lw["kv_a_ln"], eps)
        k_pe = rope(kv[..., None, mla.rank:])[:, :, 0]
        q_nope, q_pe = q[..., :mla.nope], rope(q[..., mla.nope:])
    if "idx_layer" in lw:
        # attention over a selection: the program's ``attend`` also takes
        # what an indexer reads, the query's latent and the layer's input
        return attend(q_nope, q_pe, c_kv, k_pe, lw["wkv_b"], c_q, hn)
    return attend(q_nope, q_pe, c_kv, k_pe, lw["wkv_b"])


def _indexer_at(indexer, slot, keys=_INDEXER_KEYS):
    """One indexer's weights out of the stacks, at a traced ``slot``."""
    return {k: jax.lax.dynamic_index_in_dim(indexer[k], slot, 0,
                                            keepdims=False) for k in keys}


def _rope_head(x, rope, width):
    """``x [..., heads, D]`` with its first ``width`` values rotated."""
    return jnp.concatenate([rope(x[..., :width]), x[..., width:]], axis=-1)


def index_key(hn, iw, *, dsa, rope):
    """The index key a token caches in a layer with an indexer, ``[B, S,
    D]``: ``LayerNorm(hn W_k)`` (weight and bias, float32 inside), its first
    ``dsa.rope`` values rotated. Scope ``dsa_index_proj``."""
    with jax.named_scope("dsa_index_proj"):
        k = _layer_norm(jnp.einsum("bsh,hd->bsd", hn, iw["idx_wk"]),
                        iw["idx_k_ln_w"], iw["idx_k_ln_b"], dsa.eps)
        return _rope_head(k[:, :, None, :], rope, dsa.rope)[:, :, 0]


def index_query(c_q, hn, iw, *, dsa, rope):
    """An indexer's queries ``[B, S, heads, D]`` (from the query's latent
    ``c_q``; the first ``dsa.rope`` values of each head rotated) and the
    per-token head weights ``[B, S, heads]`` float32, ``heads^-0.5 D^-0.5``
    folded in. Scope ``dsa_index_proj``."""
    B, S = hn.shape[0], hn.shape[1]
    with jax.named_scope("dsa_index_proj"):
        q = jnp.einsum("bsr,rd->bsd", c_q, iw["idx_wq_b"]).reshape(
            B, S, dsa.heads, dsa.dim)
        w = jnp.einsum("bsh,hj->bsj", hn, iw["idx_w"]).astype(jnp.float32)
        return _rope_head(q, rope, dsa.rope), \
            w * (dsa.heads ** -0.5 * dsa.dim ** -0.5)


def dsa_sequence_select(q_idx, k_idx, w_idx, topk, block=128):
    """The selection of ONE whole sequence with no cache (whole-prompt
    prefill, ``forward``): q_idx ``[S, heads, D]``, k_idx ``[S, D]``, w_idx
    ``[S, heads]`` -> mask ``[S, S]``, row ``t`` the ``min(topk, t + 1)``
    positions ``s <= t`` with the largest index score, a block of queries
    at a time. Scopes ``dsa_index_score`` / ``dsa_select``."""
    S = q_idx.shape[0]
    blk = min(block, S)
    pad = (-S) % blk
    qp = jnp.pad(q_idx, ((0, pad), (0, 0), (0, 0)))
    wp = jnp.pad(w_idx, ((0, pad), (0, 0)))
    cols = jnp.arange(S, dtype=jnp.int32)

    def one_block(start):
        with jax.named_scope("dsa_index_score"):
            qb = jax.lax.dynamic_slice_in_dim(qp, start, blk, 0)
            wb = jax.lax.dynamic_slice_in_dim(wp, start, blk, 0)
            s = jnp.einsum("qhd,sd->qhs", qb, k_idx,
                           preferred_element_type=jnp.float32)
            s = jnp.einsum("qh,qhs->qs", wb, jnp.maximum(s, 0.0),
                           precision=jax.lax.Precision.HIGHEST)
            s = jnp.where(cols[None, :] <= start + jnp.arange(blk)[:, None],
                          s, NEG_INF)
        with jax.named_scope("dsa_select"):
            return dsa_select(s, topk)

    mask = jax.lax.map(one_block, jnp.arange(0, S + pad, blk))
    return mask.reshape(S + pad, S)[:S]


def sequence_attend_selected(lw, indexer, sel, rope, *, mla, dsa):
    """``_mla_attention``'s ``attend`` for whole sequences with no cache
    (whole-prompt prefill, ``forward``), over a selection: a layer with an
    indexer (``lw["idx_layer"] >= 0``; ``indexer`` its stack's weights, None
    where the stack has none) scores and selects, every other attends over
    ``sel [B, S, S]``, the set it was handed. The carry is what the caches
    take of the layer and the set it used: ``(latent rows, index keys [B, S,
    1, D] (width 0 without an indexer), sel')``."""
    def attend(q_nope, q_pe, c_kv, k_pe, w_kvb, c_q, hn):
        new_sel, key = sel, jnp.zeros(hn.shape[:2] + (0,), hn.dtype)
        if indexer is not None:
            iw = _indexer_at(indexer, lw["idx_slot"])
            key = index_key(hn, iw, dsa=dsa, rope=rope)

            def select(_):
                q_i, w_i = index_query(c_q, hn, iw, dsa=dsa, rope=rope)
                return jax.lax.map(
                    lambda a: dsa_sequence_select(*a, dsa.topk),
                    (q_i, key, w_i))

            new_sel = jax.lax.cond(lw["idx_layer"] >= 0, select,
                                   lambda _: sel, None)
        with jax.named_scope("dsa_attend"):
            attn = jax.lax.map(
                lambda a: dsa_expanded_attention(*a[:4], w_kvb, a[4],
                                                 mla=mla),
                (q_nope, q_pe, c_kv, k_pe, new_sel))
        return attn, (latent_rows(c_kv, k_pe), key[:, :, None, :], new_sel)

    return attend


def dsa_expanded_attention(q_nope, q_pe, c_kv, k_pe, w_kvb, mask, *, mla,
                           block=128):
    """Latent attention in the EXPANDED form of ONE whole sequence over a
    selection ``mask [S, S]`` (``dsa_sequence_select``): as
    ``mla_expanded_attention``, plain ``jnp`` a block of queries at a time
    (the flash path has no mask). q_nope ``[S, nh, nope]``, q_pe ``[S, nh,
    rope]``, c_kv ``[S, rank]``, k_pe ``[S, rope]``; returns ``[S, nh, v]``."""
    S, nh, _ = q_nope.shape
    kv = jnp.einsum("sr,rd->sd", c_kv, w_kvb).reshape(S, nh, -1)
    k = jnp.concatenate(
        [kv[..., :mla.nope],
         jnp.broadcast_to(k_pe[:, None, :], (S, nh, mla.rope))], -1)
    v = kv[..., mla.nope:]
    blk = min(block, S)
    pad = (-S) % blk
    q = jnp.pad(jnp.concatenate([q_nope, q_pe], -1),
                ((0, pad), (0, 0), (0, 0)))
    mp = jnp.pad(mask, ((0, pad), (0, 0)))

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, 0)
        mb = jax.lax.dynamic_slice_in_dim(mp, start, blk, 0)[None]
        logits = jnp.einsum("qhd,khd->hqk", qb, k,
                            preferred_element_type=jnp.float32) * mla.scale
        logits = jnp.where(mb, logits, NEG_INF)
        probs = jnp.where(mb, jax.nn.softmax(logits, axis=-1), 0.0)
        return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v)

    out = jax.lax.map(one_block, jnp.arange(0, S + pad, blk))
    return out.reshape(S + pad, nh, -1)[:S]


#: ``moe``'s entries past ``(top_k, renormalize)``, by ``moe_ffn``'s names
_ROUTING_KEYS = ("n_group", "topk_group", "first_held", "scale")


def _by_row_blocks(ffn, hn, live, rows):
    """``ffn(hn [B, S, H], live [B, S]) -> (m, stats)`` over ``rows``
    positions at a time (``_mixer_ffn_layer``'s ``ffn_rows``), the positions
    padded to whole blocks with dead rows. Of a routed FFN's ``stats`` the
    picked experts ``[B, S, top_k]`` are kept; the counts are a block's own
    and are dropped (None)."""
    B, S = hn.shape[:2]
    n = -(-S // rows)
    live = jnp.ones((B, S), bool) if live is None else live

    def blocks(a):
        a = jnp.pad(a, ((0, 0), (0, n * rows - S)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, rows) + a.shape[2:]), 1, 0)

    def whole(a):
        return jnp.moveaxis(a, 0, 1).reshape((B, n * rows) + a.shape[3:])[:, :S]

    m, stats = jax.lax.map(lambda xs: ffn(*xs), (blocks(hn), blocks(live)))
    return whole(m), ((None, whole(stats[1])) if isinstance(stats, tuple)
                      else None)


def _mixer_ffn_layer(h, lw, scope, mixer, *, eps, live=None, moe=None,
                     experts=None, tp_reduce=None, return_picks=False,
                     norm=_rms, ffn_rows=None):
    """ONE layer of "a mixer, then an FFN" on ``h [B, S, H]``, written once
    for every kind of layer that is two residual sub-layers: ``_decoder_layer``
    (softmax attention) and ``_gdn_layer`` (Gated DeltaNet) bring
    ``mixer(hn) -> (out [B, S, H], carry)`` and the named ``scope`` it runs
    under. What ``lw`` holds chooses the rest: with ``input_ln`` / ``post_ln``
    each sub-layer's INPUT is normalised (pre-norm), with ``attn_out_ln`` /
    ``ffn_out_ln`` its OUTPUT, before the residual add; with ``router`` the FFN
    is the dropless routed one (``kernels.moe_ffn``; ``moe`` is its static
    ``(top_k, renormalize)`` and then ``_ROUTING_KEYS``, ``experts`` the three
    expert stacks ``[L, E, ...]`` of which ``lw["layer"]`` names this layer's,
    ``live [B, S]`` marks the rows that make pairs) plus, with ``ws_gate``, a
    shared expert every row runs (scope ``moe_shared``, beside ``moe``), times
    ``sigmoid(hn ws_sgate)`` a row where the tree has that gate; else the
    dense SwiGLU. ``norm`` is the tree's RMSNorm (``_norm_of``). Returns ``(h,
    carry, moe_stats or None)``; with ``return_picks`` the third is
    ``(moe_stats, picked experts [B, S, top_k])``. With ``ffn_rows`` (a
    model's whole-sequence ``forward``, never a step program) the FFN runs
    that many positions at a time, so that its temporaries (a routed FFN's
    float32 ``[rows x top_k, H]``) are a block's and not the sequence's
    (``_by_row_blocks``: ``moe_stats`` is None where ``S`` is more)."""
    with jax.named_scope(scope):
        o, carry = mixer(norm(h, lw["input_ln"], eps) if "input_ln" in lw
                         else h)
        o = o if tp_reduce is None else tp_reduce(o)
        h = h + (norm(o, lw["attn_out_ln"], eps) if "attn_out_ln" in lw
                 else o)
    hn = norm(h, lw["post_ln"], eps) if "post_ln" in lw else h

    def ffn(hn, live):
        if "router" not in lw:
            return _swiglu_proj(hn, lw["w_gate"], lw["w_up"],
                                lw["w_down"]), None
        m, *stats = moe_ffn(hn, lw["router"], *experts, layer=lw["layer"],
                            top_k=moe[0], live=live, renormalize=moe[1],
                            return_picks=return_picks,
                            **({"router_bias": lw["router_bias"]}
                               if "router_bias" in lw else {}),
                            **dict(zip(_ROUTING_KEYS, moe[2:])))
        if "ws_gate" in lw:
            with jax.named_scope("moe_shared"):
                shared = _swiglu_raw(hn, lw["ws_gate"], lw["ws_up"],
                                     lw["ws_down"])
                if "ws_sgate" in lw:
                    gate = jax.nn.sigmoid(jnp.einsum(
                        "bsh,hj->bsj", hn, lw["ws_sgate"],
                        preferred_element_type=jnp.float32))
                    shared = (gate * shared).astype(shared.dtype)
                m = m + shared
        return m, tuple(stats) if return_picks else stats[0]

    m, stats = ffn(hn, live) if ffn_rows is None or h.shape[1] <= ffn_rows \
        else _by_row_blocks(ffn, hn, live, ffn_rows)
    m = m if tp_reduce is None else tp_reduce(m)
    h = h + (norm(m, lw["ffn_out_ln"], eps) if "ffn_out_ln" in lw else m)
    return h, carry, stats


def _decoder_layer(h, lw, *, nh, nkv, hd, eps, rope, attend, mla=None,
                   norm=_rms, scope="attn", v_scale=None, **ffn):
    """ONE softmax-attention decoder layer on ``h [B, S, H]``, written once
    for the programs the default engine runs (whole-prompt prefill, the
    packed-span forward of the unified step) and for the models' own
    ``forward``: ``_mixer_ffn_layer`` (which takes ``ffn``: ``live``, ``moe``,
    ``experts``, ``tp_reduce``, ``return_picks``) around the attention below,
    under the scope ``attn`` (or the program's ``scope``: a window layer's
    ``window_attn``).

    ``lw`` maps names to this layer's weights (``_layer_stack`` order, after
    ``_dq_layer``) and what it holds chooses the attention: with ``wkv_a`` the
    latent one (``_mla_attention``; ``mla`` its static numbers,
    ``models.deepseek_v2.Mla``); with ``q_norm`` q and k are normalised before
    ``rope``, over the whole projection or a head at a time (``_qk_norm``); a
    ``wq`` twice as wide as the heads holds a query and then an output GATE a
    head, and the heads' output is multiplied by ``sigmoid(gate)`` before
    ``W_o`` (Qwen3-Next); the KV heads are as many as ``wk`` holds heads of
    ``hd`` (a model whose layer kinds differ in them, MiMo-V2-Flash's 4 and 8,
    passes either ``nkv``), a value is as wide as ``wv`` makes it (its 128
    under keys of 192: ``W_o`` then reads ``nh`` values) and is multiplied by
    the static ``v_scale`` after its projection, where the model has one. A
    SINK (``lw["sink"]``, a logit a query head) is the program's ``attend``'s
    to apply, like the window it comes with. The program
    brings its own ``rope(x)`` (a rotation of part of a head is the
    program's: ``_rope_head``) and ``attend(q, k, v) -> (attn [B, S, nh, hd],
    carry)`` (latent attention: ``_mla_attention``'s): cache writes and the
    attention kernel are the program's business, not the layer's.
    Returns ``_mixer_ffn_layer``'s ``(h, carry, moe_stats or None)``."""
    B, S = h.shape[0], h.shape[1]

    def mixer(hn):
        if "wkv_a" in lw:
            with jax.named_scope("mla"):
                attn, carry = _mla_attention(hn, lw, nh=nh, eps=eps,
                                             rope=rope, attend=attend,
                                             mla=mla)
                with jax.named_scope("mla_proj"):
                    return _o_proj(attn.reshape(B, S, -1), lw["wo"]), carry
        dense = not isinstance(lw["wq"], tuple)
        gated = dense and lw["wq"].shape[-1] == 2 * nh * hd
        kv = lw["wk"].shape[-1] // hd if dense else nkv
        if dense and lw["wv"].shape[-1] != kv * hd:
            # a value narrower (or wider) than a key: a reshape of its own
            q, k, v = (jnp.einsum("bsh,hd->bsd", hn, lw[n]).reshape(
                B, S, heads, -1) for n, heads in (("wq", nh), ("wk", kv),
                                                  ("wv", kv)))
        else:
            q, k, v = _qkv_proj(hn, lw["wq"], lw["wk"], lw["wv"],
                                2 * nh if gated else nh, kv, hd)
        if v_scale is not None:
            v = (v.astype(jnp.float32) * v_scale).astype(v.dtype)
        gate = None
        if gated:       # a head's columns: its query, then its gate
            q = q.reshape(B, S, nh, 2 * hd)
            q, gate = q[..., :hd], q[..., hd:]
        if "q_norm" in lw:
            q, k = _qk_norm(q, k, lw["q_norm"], lw["k_norm"], eps, norm)
        attn, carry = attend(rope(q), rope(k), v)
        if gate is not None:
            attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(hn.dtype)
        return _o_proj(attn.reshape(B, S, -1), lw["wo"]), carry

    return _mixer_ffn_layer(h, lw, scope, mixer, eps=eps, norm=norm, **ffn)


# ------------------------------------------- linear-attention (hybrid) layers
def gdn_gates(ab, a_log, dt_bias, neg_eigval):
    """A Gated DeltaNet layer's gates from the fused ``[.., 2 * heads]``
    projection ``[a | b]``, float32: the log-decay ``g = -exp(A_log) *
    softplus(a + dt_bias)`` and the write strength ``beta = sigmoid(b)``,
    doubled where negative eigenvalues are allowed."""
    ab = ab.astype(jnp.float32)
    nh = ab.shape[-1] // 2
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        ab[..., :nh] + dt_bias.astype(jnp.float32))
    beta = jax.nn.sigmoid(ab[..., nh:])
    return g, (2.0 * beta if neg_eigval else beta)


def conv_silu(cur, prev, w, bias=None):
    """The depthwise causal convolution of width ``len(prev) + 1`` and its
    SiLU, a channel: ``silu(w[-1] * u_t + w[-2] * u_{t-1} + .. [+ bias])``,
    float32 inside, the rows' dtype out. ``prev[j - 1]`` holds ``u_{t-j}``;
    ``w`` is ``[width, C]``, its last row the current token's."""
    f32 = jnp.float32
    acc = cur.astype(f32) * w[-1].astype(f32)
    for j, p in enumerate(prev, 1):
        acc = acc + p.astype(f32) * w[-1 - j].astype(f32)
    if bias is not None:
        acc = acc + bias.astype(f32)
    return jax.nn.silu(acc).astype(cur.dtype)


def _span_conv(u, held, conv_w, *, fresh, qstart, qlen, bias=None):
    """The convolution over the packed buffer ``u [T, C]`` of a step whose
    spans keep their earlier inputs in a store by slot (``held [R, taps,
    C]``, the slot's last ``taps`` inputs, oldest first). A token's earlier
    inputs are the rows above it, except in a span's first rows, which take
    the slot's tail (a zero one where the span is ``fresh``, whatever the
    slot held): a few rows a span, scattered over the shifted buffer (a
    gather of the tail for every packed row cost 6 % of a step). Returns
    ``(silu(conv(u)) [T, C], the slots' new tails [R, taps, C])``: the
    span's last inputs, and where the span is shorter than the tail, the
    old tail moved up; a slot without a span keeps what it held."""
    T, taps = u.shape[0], held.shape[1]
    slots = jnp.arange(held.shape[0], dtype=jnp.int32)
    tail = jnp.where(fresh[:, None, None], jnp.zeros_like(held), held)
    prev = []
    for j in range(1, taps + 1):
        at = jnp.concatenate([jnp.where(qlen > i, qstart + i, T)
                              for i in range(j)])
        rows = jnp.concatenate([tail[:, taps + i - j] for i in range(j)])
        prev.append(jnp.roll(u, j, axis=0).at[at].set(rows, mode="drop"))
    up = conv_silu(u, prev, conv_w, bias)
    rows = []
    for k in range(taps):
        at = qlen - taps + k
        rows.append(jnp.where(
            (at >= 0)[:, None],
            jnp.take(u, jnp.clip(qstart + at, 0, T - 1), axis=0),
            tail[slots, jnp.clip(taps + at, 0, taps - 1)]))
    return up, jnp.where((qlen > 0)[:, None, None], jnp.stack(rows, 1), held)


def _rows_conv(a, w, bias, lengths):
    """The convolution over whole rows ``a [G, S, C]`` from a zero start:
    ``(silu(conv(a)) [G, S, C], tails [G, taps, C])``, a row's tail its last
    ``taps`` inputs before ``lengths[g]`` (zeros where the row is shorter)."""
    S, taps = a.shape[1], w.shape[0] - 1
    ext = jnp.pad(a, ((0, 0), (taps, 0), (0, 0)))
    c = conv_silu(a, [ext[:, taps - j:taps - j + S]
                      for j in range(1, taps + 1)], w, bias)
    tail = jnp.take_along_axis(
        ext, (lengths[:, None] + jnp.arange(taps)[None])[..., None], axis=1)
    return c, tail


def gdn_split(u, gdn):
    """The convolved channels ``[.., C]`` as normalised ``q, k [.., key
    heads, dk]`` (float32; q carries the ``dk^-0.5``) and ``v [.., heads,
    dv]``; the kernels read value head ``h``'s q and k at key head ``h //
    (heads / key heads)``."""
    hk = gdn.key_heads or gdn.heads
    nk = hk * gdn.dk
    lead = u.shape[:-1]
    q = l2norm(u[..., :nk].reshape(lead + (hk, gdn.dk)), gdn.dk ** -0.5)
    k = l2norm(u[..., nk:2 * nk].reshape(lead + (hk, gdn.dk)))
    return q, k, u[..., 2 * nk:].reshape(lead + (gdn.heads, gdn.dv))


def _gdn_layer(h, lw, *, eps, gdn, mix, norm=_rms, **ffn):
    """ONE Gated DeltaNet layer on ``h [B, S, H]`` (``models.olmo_hybrid``'s
    and ``models.qwen3_next``'s docstrings have the equations), written once
    for whole-prompt prefill, the unified step and the models' ``forward``:
    ``_mixer_ffn_layer`` (which takes ``ffn`` and chooses the norms' places
    and the FFN by what ``lw`` holds) around the mixer below, under the scope
    ``gdn``. ``gdn`` is the layer's static numbers
    (``models.olmo_hybrid.Gdn``). The program brings ``mix(u, g, beta, conv_w) -> (o [B, S, heads, dv]
    float32, carry)``: where the convolution's earlier rows and the state come
    from, which kernel walks the tokens, and what is written back are the
    program's business. Scopes ``gdn`` > ``gdn_proj`` (the input projections
    and ``W_o``) and ``gdn_mix`` (convolution, gates, the kernels, the gated
    norm, whose weight is NOT zero-centred in any tree). Returns
    ``_mixer_ffn_layer``'s ``(h, carry, moe_stats or None)``."""
    B, S = h.shape[0], h.shape[1]

    def mixer(hn):
        with jax.named_scope("gdn_proj"):
            u = jnp.einsum("bsh,hc->bsc", hn, lw["gdn_wqkv"])
            z = jnp.einsum("bsh,hc->bsc", hn, lw["gdn_wz"])
            # the gates' projection leaves in float32: the decay is
            # exp(-exp(A_log) softplus(a + ..)), and a bf16 rounding of a
            # moves it by tens of percent where exp(A_log) is near 16
            ab = jnp.einsum("bsh,hc->bsc", hn, lw["gdn_wab"],
                            preferred_element_type=jnp.float32)
        with jax.named_scope("gdn_mix"):
            g, beta = gdn_gates(ab, lw["gdn_A_log"], lw["gdn_dt_bias"],
                                gdn.neg_eigval)
            o, carry = mix(u, g, beta, lw["gdn_conv"])
            y = _rms(o, lw["gdn_o_norm"].astype(jnp.float32), eps) \
                * jax.nn.silu(z.astype(jnp.float32)).reshape(o.shape)
            y = y.astype(hn.dtype).reshape(B, S, -1)
        with jax.named_scope("gdn_proj"):
            return jnp.einsum("bsc,ch->bsh", y, lw["gdn_wo"]), carry

    return _mixer_ffn_layer(h, lw, "gdn", mixer, eps=eps, norm=norm, **ffn)


def _hybrid_scan(params, carry, full_layer, linear_layer):
    """A hybrid model's forward: ONE scan over the periods whose body runs
    the period's layers in order: its linear layers, and its full layer
    where the tree puts it: after them (a tree with ``linear_layers``, or
    with ``window_layers``: the "linear" layers are then window-attention
    ones, ``_WINDOW_KEYS``), or
    between the two runs of a tree with ``mamba_layers`` (``(the places
    before the full layer, the places after it)``, the full layer's entries
    under ``attn_layers``). ``linear_layer(carry, lw, index, experts)`` /
    ``full_layer(carry, lw, index, experts)`` return ``(carry, ys)``;
    ``index`` is the layer's count among its own kind: a linear layer's place
    in the state store, a full layer's in the KV pool. Where the tree's FFNs
    are routed (``router``), ``experts`` is the three expert stacks
    ``[periods, E_held, ...]`` of the layer's PLACE in the period, whole (no
    scan slices them: ``_EXPERT_KEYS``), and ``lw["layer"]``, the period, is
    where the grouped matmul reads them; else None. Returns ``(carry, linear
    ys [periods, layers a period, ...], full ys [periods, ...])``."""
    if "mamba_layers" in params:
        before, after = params["mamba_layers"]
        lin, full, full_at = before + after, params["attn_layers"], \
            len(before)
        lin_experts, full_experts = (None,) * len(lin), None
    else:
        routed = "router" in params

        def cut(tree, keys):
            held = {k: tree[k] for k in keys + _HYBRID_FFN_KEYS if k in tree}
            return held, (tuple(held.pop(k) for k in _EXPERT_KEYS)
                          if routed else None)

        kind, keys = ("window_layers", _WINDOW_KEYS) \
            if "window_layers" in params else ("linear_layers", _GDN_KEYS)
        lin, lin_experts = zip(*(cut(tree, keys) for tree in params[kind]))
        full, full_experts = cut(params, _HYBRID_FULL_KEYS)
        full_at = len(lin)
    periods, n_lin = full["wo"].shape[0], len(lin)

    def period(carry, xs):
        lin_p, full_p, p = xs
        ys = []
        for j in range(n_lin + 1):
            if j == full_at:
                carry, y_full = full_layer(carry, dict(full_p, layer=p), p,
                                           full_experts)
            if j < n_lin:
                carry, y = linear_layer(carry, dict(lin_p[j], layer=p),
                                        p * n_lin + j, lin_experts[j])
                ys.append(y)
        ys = None if ys[0] is None else jax.tree.map(
            lambda *a: jnp.stack(a), *ys)
        return carry, (ys, y_full)

    carry, (ys_lin, ys_full) = jax.lax.scan(
        period, carry, (lin, full, jnp.arange(periods, dtype=jnp.int32)))
    return carry, ys_lin, ys_full


def _hybrid_moe_stats(params, lin_stats, full_stats):
    """A hybrid model's routed FFNs' outputs in LAYER order, as
    ``_packed_span_forward``'s: ``lin_stats [periods, linear layers a period,
    ...]`` and ``full_stats [periods, ...]`` (``_hybrid_scan``'s ys) joined
    with the full layer after a period's linear ones, ``[L, ...]``; None for
    a tree whose FFNs are dense."""
    if "router" not in params:
        return None
    return jax.tree.map(
        lambda a, b: jnp.concatenate([a, b[:, None]], axis=1).reshape(
            (-1,) + b.shape[1:]), lin_stats, full_stats)


def _hybrid_rope(rotary, hd, rotate):
    """A hybrid model's rotary embedding of q or k ``[B, S, heads, hd]``
    around the program's ``rotate(x)`` over tables ``rotary`` wide (the
    program's static beside ``theta``; None: the whole head): the FIRST
    ``rotary`` values of a head are rotated, the rest left
    (``partial_rotary_factor``); a ``rotate`` of None rotates nothing
    (``rope_theta`` null: Olmo-Hybrid)."""
    if rotate is None:
        return lambda t: t
    width = rotary or hd
    return rotate if width == hd else \
        (lambda t: _rope_head(t, rotate, width))


# ------------------------------------------- decoder-hybrid-decoder (SambaY)
# ``models.phi4_flash``'s docstring has the equations. A tree with
# ``self_layers`` is three runs of layers, not a period: the self-decoder's
# pairs (a Mamba layer, then differential attention inside a window), the two
# middle layers (the Mamba layer whose scan output is the memory ``m``, the
# full-attention layer whose keys and values are THE cache) and the
# cross-decoder's pairs (a Gated Memory Unit over ``m``, then differential
# cross-attention over the middle layer's cache). Every layer is
# ``_sambay_block`` around a mixer.
def _layer_norm(x, w, b, eps):
    """LayerNorm with a bias, float32 inside, the rows' dtype out."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, -1, keepdims=True)
    out = xc * jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps)
    return out.astype(x.dtype) * w + b


def _final_norm(params, x, eps):
    """A model's norm before its head: LayerNorm where the tree has its bias
    (``final_norm_b``), else the tree's RMSNorm (``_norm_of``)."""
    if "final_norm_b" in params:
        return _layer_norm(x, params["final_norm"], params["final_norm_b"],
                           eps)
    return _norm_of(params)(x, params["final_norm"], eps)


def _sambay_block(h, lw, eps, mixer):
    """``x = x + Mixer(LN(x; ln1))``, ``x = x + SwiGLU(LN(x; ln2))`` on ``h
    [B, S, H]``; ``mixer(hn) -> (out, carry)``. Returns ``(h, carry)``."""
    out, carry = mixer(_layer_norm(h, lw["ln1_w"], lw["ln1_b"], eps))
    h = h + out
    m = _swiglu_proj(_layer_norm(h, lw["ln2_w"], lw["ln2_b"], eps),
                     lw["w_gate"], lw["w_up"], lw["w_down"])
    return h + m, carry


def _mamba_mixer(hn, lw, *, conv, scan, eps=None):
    """A Mamba-1 mixer on ``hn [B, S, H]``. The program brings ``conv(a, w,
    bias) -> (silu(conv(a)) [B, S, C], carry)`` (where the convolution's
    earlier inputs come from) and ``scan(dt, u, b, c, A) -> (y [B, S, C]
    float32, carry)`` (where the state comes from and which kernel walks the
    tokens): dt, u ``[B, S, C]``, b, c ``[B, S, N]`` float32, ``A = -exp(A_log)
    [N, C]``. A tree with ``ssm_dt_ln`` / ``ssm_b_ln`` / ``ssm_c_ln``
    (``models.jamba``) normalises the step's low-rank input and the two
    vectors between ``W_x`` and ``W_dt`` / the scan (RMSNorm at ``eps``,
    float32); one without them runs none. Returns ``(out [B, S, H], (conv
    carry, scan carry, y))``, a mixer of ``_sambay_block``: ``y``, the scan's
    output with the skip ``D * c`` and BEFORE the gate, is what the middle
    Mamba layer hands on as the memory. Scopes ``ssm_proj`` (the four
    projections) and ``ssm_mix`` (convolution, inner norms, gates, the
    kernels)."""
    f32 = jnp.float32
    C = lw["ssm_out"].shape[0]
    N = lw["ssm_A_log"].shape[0]
    rank = lw["ssm_dt"].shape[0]
    with jax.named_scope("ssm_proj"):
        az = jnp.einsum("bsh,hc->bsc", hn, lw["ssm_in"])
        a, z = az[..., :C], az[..., C:]
    with jax.named_scope("ssm_mix"):
        c, conv_carry = conv(a, lw["ssm_conv"], lw["ssm_conv_b"])
    with jax.named_scope("ssm_proj"):
        # the step and the two vectors leave in float32: dt is the exponent's
        # scale, B and C multiply a float32 state
        xdb = jnp.einsum("bsc,cr->bsr", c, lw["ssm_x"],
                         preferred_element_type=f32)
    if "ssm_dt_ln" in lw:
        with jax.named_scope("ssm_mix"):
            xdb = jnp.concatenate(
                [_rms(xdb[..., lo:hi], lw[k].astype(f32), eps)
                 for k, lo, hi in (("ssm_dt_ln", 0, rank),
                                   ("ssm_b_ln", rank, rank + N),
                                   ("ssm_c_ln", rank + N, rank + 2 * N))], -1)
    with jax.named_scope("ssm_proj"):
        dt = jnp.einsum("bsr,rc->bsc", xdb[..., :rank].astype(hn.dtype),
                        lw["ssm_dt"], preferred_element_type=f32)
    with jax.named_scope("ssm_mix"):
        dt = jax.nn.softplus(dt + lw["ssm_dt_b"].astype(f32))
        cf = c.astype(f32)
        y, scan_carry = scan(dt, dt * cf, xdb[..., rank:rank + N],
                             xdb[..., rank + N:],
                             -jnp.exp(lw["ssm_A_log"].astype(f32)))
        y = y + lw["ssm_D"].astype(f32) * cf
        gated = (y * jax.nn.silu(z.astype(f32))).astype(hn.dtype)
    with jax.named_scope("ssm_proj"):
        out = jnp.einsum("bsc,ch->bsh", gated, lw["ssm_out"])
    return out, (conv_carry, scan_carry, y)


def _mamba_rows_mixer(lengths, live, ssm, **norm):
    """A Mamba mixer of whole-prompt prefill, ``mixer(hn [G, S, H], lw) ->
    _mamba_mixer's``: the convolution and the scan run from a zero tail and a
    zero state over each row's real tokens (``live [G, S]``, ``lengths
    [G]``; ``norm``: the inner norms' ``eps``, for a tree that has them); the
    carries are what a slot's store holds of the row, its tail
    ``[G, conv - 1, C]`` and its state ``[G, N, C]`` float32."""
    G, S = live.shape
    cols = jnp.arange(S, dtype=jnp.int32)
    rows_g = jnp.arange(G, dtype=jnp.int32)

    def conv(a, w, bias):
        return _rows_conv(a, w, bias, lengths)

    def scan(dt, u, b, c, a):
        def flat(t):
            return t.reshape((G * S,) + t.shape[2:])

        zero = jnp.zeros((1, G) + a.shape, jnp.float32)
        if ssm.kernel == "pallas":
            y, st = ssm_chunk_scan(
                flat(dt), flat(u), flat(b), flat(c), a, zero, layer=0,
                start=rows_g * S, length=lengths,
                fresh=jnp.ones((G,), bool))
        else:
            y, st = ssm_reference(
                flat(dt), flat(u), flat(b), flat(c), a, zero, layer=0,
                seg=jnp.where(live, rows_g[:, None], G).reshape(-1),
                first=jnp.broadcast_to(cols == 0, (G, S)).reshape(-1))
        return jnp.where(live[..., None], y.reshape(G, S, -1), 0.0), st[0]

    return lambda hn, lw: _mamba_mixer(hn, lw, conv=conv, scan=scan, **norm)


def _mamba_span_mixer(ssm, *, seg, pos, qstart, qlen, kvlen, T, **norm):
    """A Mamba mixer of the unified step over the packed buffer, ``mixer(hn
    [1, T, H], lw, idx, ss, cs) -> _mamba_mixer's``: the layer reads and
    writes index ``idx`` of the store ``(ss [Mamba layers, R, N, C] float32,
    cs [Mamba layers, R, conv - 1, C])`` at the slots that have a span this
    step (``_hybrid_span_forward``'s rules: a span whose first position is 0
    takes a zero state and a zero tail; spans of one token through
    ``ssm_recurrent_update``, longer ones through ``ssm_chunk_scan``, which
    the decode-only program, ``T == ssm.decode_rows``, leaves out: the plan
    gave it no chunk); the carries are the two stores, whole. ``norm`` as
    ``_mamba_rows_mixer``'s."""
    R = qstart.shape[0]
    live_tok = seg < R
    seg_c = jnp.minimum(seg, R - 1)
    fresh = (kvlen - qlen) == 0
    one, many = qlen == 1, qlen > 1
    tok_one = live_tok & jnp.take(one, seg_c)
    row_at = jnp.clip(qstart, 0, T - 1)

    def mixer(hn, lw, idx, ss, cs):
        if ss.dtype != jnp.float32:
            # (a state rounded to bfloat16 a token moves the logits by less
            # than a check on logits can see: the dtype is held here)
            raise TypeError(f"a Mamba layer's state is float32, the store "
                            f"holds {ss.dtype}")

        def conv(a, w, bias):
            c, tails = _span_conv(a[0], cs[idx], w, fresh=fresh,
                                  qstart=qstart, qlen=qlen, bias=bias)
            return c[None], cs.at[idx].set(tails)

        def scan(dt, u, b, c, a):
            dt, u, b, c = dt[0], u[0], b[0], c[0]
            if ssm.kernel == "pallas":
                y1, new_ss = ssm_recurrent_update(
                    *(jnp.take(t, row_at, axis=0) for t in (dt, u, b, c)), a,
                    ss, layer=idx, live=one, fresh=fresh)
                y = jnp.take(y1, seg_c, axis=0)
                if T != ssm.decode_rows:
                    yn, new_ss = ssm_chunk_scan(
                        dt, u, b, c, a, new_ss, layer=idx, start=qstart,
                        length=jnp.where(many, qlen, 0), fresh=fresh,
                        min_span=2)
                    y = jnp.where(tok_one[:, None], y, yn)
            else:
                y, new_ss = ssm_reference(
                    dt, u, b, c, a, ss, layer=idx, seg=seg,
                    first=live_tok & (pos == 0))
            return jnp.where(live_tok[:, None], y, 0.0)[None], new_ss

        return _mamba_mixer(hn, lw, conv=conv, scan=scan, **norm)

    return mixer


def ring_coords(seg, pos, ring, table_entries):
    """Where a step's packed rows lie in the window layers' store ``ring
    [layers, R, ring blocks, bs, KD]`` and how the ragged kernel walks it:
    ``(ring_at, ring_tables)``. ``ring_at`` is a token's ``(slot, ring block,
    row)``: its row ``pos % (ring blocks * bs)`` of its slot's ring (a dead
    packed row's slot is ``R``: the write drops); a layer writes at ``(layer,)
    + ring_at``. ``ring_tables [R, table_entries]`` is the table the program
    computes for ``ring_as_pool``: logical block ``b`` of slot ``r`` is ring
    block ``b % ring blocks``. The ring is long enough that no key a query of
    the step may see was overwritten (``engine``: window + the longest span +
    a block), and the kernel's ``window`` bounds the walk below."""
    R, ring_blocks, bs = ring.shape[1:4]
    ring_at = (jnp.where(seg < R, seg, R), pos // bs % ring_blocks, pos % bs)
    ring_tables = (
        jnp.arange(R, dtype=jnp.int32)[:, None] * ring_blocks
        + jnp.arange(table_entries, dtype=jnp.int32)[None, :] % ring_blocks)
    return ring_at, ring_tables


def ring_as_pool(ring):
    """The window store as the kernel walks it: a pool of ``R * ring blocks``
    (merging two leading dims moves nothing)."""
    return ring.reshape((ring.shape[0], -1) + ring.shape[3:])


def ring_rows_at(lengths, ring_rows, seq_len):
    """What a whole-prompt prefill leaves in a slot's ring: ``[G, ring_rows]``,
    for ring row ``j`` the last position ``p < lengths[g]`` with ``p %
    ring_rows == j`` (a row no position of the prompt has maps to position 0:
    nothing a later query may see)."""
    ring = jnp.arange(ring_rows, dtype=jnp.int32)[None, :]
    span = max(ring_rows, 1)
    return jnp.clip(ring + (lengths[:, None] - 1 - ring) // span * span, 0,
                    seq_len - 1)


def _diff_queries(q):
    """Differential attention's queries ``[.., nh, hd]`` as the ragged kernel
    takes them: a KV PAIR is one head of ``2 hd`` (the pool's row is the same
    values a side) and head ``2n`` / ``2n + 1`` of a pair's four queries is
    ``[q1 | 0]`` / ``[0 | q2]``, so ``P1 V`` and ``P2 V`` over the pair's
    whole ``V`` come out of one walk of the keys. The kernel scales by ``(2
    hd)^-0.5``: the queries carry the other ``sqrt(2)``."""
    nh, hd = q.shape[-2], q.shape[-1]
    q = (q.astype(jnp.float32) * math.sqrt(2.0)).astype(q.dtype)
    q = q.reshape(q.shape[:-2] + (nh // 2, 2, hd))
    z = jnp.zeros_like(q[..., 0, :])
    wide = jnp.stack([jnp.concatenate([q[..., 0, :], z], -1),
                      jnp.concatenate([z, q[..., 1, :]], -1)], axis=-2)
    return wide.reshape(q.shape[:-3] + (nh, 2 * hd))


def _diff_combine(o, lw, eps, dtype):
    """``(1 - lambda_init) RMSNorm(o1 - lambda o2; subln)`` a differential
    head, from the kernel's ``o [.., nh, 2 hd]`` (``_diff_queries``' order).
    Returns ``[.., nh * hd]`` in ``dtype``."""
    f32 = jnp.float32
    nh = o.shape[-2]
    o = o.astype(f32).reshape(o.shape[:-2] + (nh // 2, 2, o.shape[-1]))
    lam_v = lw["lam"].astype(f32)
    init = lw["lambda_init"].astype(f32)
    lam = jnp.exp(jnp.sum(lam_v[0] * lam_v[1])) \
        - jnp.exp(jnp.sum(lam_v[2] * lam_v[3])) + init
    a = o[..., 0, :] - lam * o[..., 1, :]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps) \
        * lw["subln"].astype(f32) * (1.0 - init)
    return a.astype(dtype).reshape(a.shape[:-2] + (-1,))


def _diff_attention(hn, lw, *, nh, nkv, hd, eps, attend):
    """Differential attention's projections and combine around the program's
    ``attend(q wide [B, S, nh, 2 hd], k, v [B, S, nkv, hd] or None) -> (o
    [B, S, nh, 2 hd], carry)``: a tree with ``wqkv`` projects its own keys
    and values (the self layers), one with ``wq`` alone reads another
    layer's (the cross layers). Returns ``(out [B, S, H], carry)``."""
    B, S = hn.shape[0], hn.shape[1]
    nq = nh * hd
    if "wqkv" in lw:
        qkv = jnp.einsum("bsh,hc->bsc", hn, lw["wqkv"]) + lw["bqkv"]
        k = qkv[..., nq:nq + nkv * hd].reshape(B, S, nkv, hd)
        v = qkv[..., nq + nkv * hd:].reshape(B, S, nkv, hd)
        q = qkv[..., :nq]
    else:
        q, k, v = jnp.einsum("bsh,hc->bsc", hn, lw["wq"]) + lw["bq"], \
            None, None
    o, carry = attend(_diff_queries(q.reshape(B, S, nh, hd)), k, v)
    a = _diff_combine(o, lw, eps, hn.dtype)
    return jnp.einsum("bsc,ch->bsh", a, lw["wo"]) + lw["bo"], carry


def _sambay_attn_layer(h, lw, scope, attend, *, nh, nkv, hd, eps):
    """A block whose mixer is differential attention under the named
    ``scope`` (``window_attn`` / ``yoco_attn``). Returns ``(h, attend's
    carry)``."""
    def mixer(hn):
        with jax.named_scope(scope):
            return _diff_attention(hn, lw, nh=nh, nkv=nkv, hd=hd, eps=eps,
                                   attend=attend)
    return _sambay_block(h, lw, eps, mixer)


@jax.named_scope("gmu")
def _gmu(hn, lw, m):
    """A Gated Memory Unit: ``(m * silu(h W_1)) W_2``, ``m [B, S, C]`` float32
    the middle Mamba layer's scan output of the SAME token."""
    g = jnp.einsum("bsh,hc->bsc", hn, lw["gmu_in"]).astype(jnp.float32)
    return jnp.einsum("bsc,ch->bsh", (m * jax.nn.silu(g)).astype(hn.dtype),
                      lw["gmu_out"])


def _diff_attend_plain(qw, k, v, mask):
    """``_diff_queries``' attention in ``jax.numpy`` over whole rows: qw ``[G,
    Q, nh, 2 hd]``, k, v ``[G, S, nkv, hd]``, mask ``[G, Q, S]``."""
    G, S, nkv, hd = k.shape
    grp = qw.shape[2] // (nkv // 2)
    kp = jnp.repeat(k.reshape(G, S, nkv // 2, 2 * hd), grp, axis=2)
    vp = jnp.repeat(v.reshape(G, S, nkv // 2, 2 * hd), grp, axis=2)
    logits = jnp.einsum("gqhd,gkhd->ghqk", qw, kp,
                        preferred_element_type=jnp.float32) \
        * (1.0 / math.sqrt(2 * hd))
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jnp.where(mask[:, None], jax.nn.softmax(logits, axis=-1), 0.0)
    return jnp.einsum("ghqk,gkhd->gqhd", probs.astype(qw.dtype), vp)


def _sambay_prefill_layers(params, x, lengths, *, nh, nkv, hd, eps, ssm,
                           narrow=True):
    """A decoder-hybrid-decoder model's layers over an admission group ``x
    [G, S_pad, H]``: every Mamba layer scans from a zero state over each
    row's real tokens, the window layers attend inside their window, the
    middle full layer over everything before, and the cross-decoder (GMUs
    over the middle Mamba layer's memory, cross-attention over the middle
    full layer's keys and values) runs, with ``narrow``, on each row's LAST
    real token only: nothing in it is cached and only that token's output is
    used. Returns ``(x [G, 1 or S_pad, H], pk, pv [1, G, S_pad, Hkv, D] (the
    middle full layer's), (states [Mamba layers, G, N, C] float32, tails
    [Mamba layers, G, conv - 1, C], window keys, values [window layers, G,
    ssm.ring_rows, KD]))``: what each store holds of a sequence; a window
    layer's ring row ``j`` holds the last position ``p`` with ``p %
    ring_rows == j``."""
    G, S = x.shape[0], x.shape[1]
    cols = jnp.arange(S, dtype=jnp.int32)
    live = cols[None, :] < lengths[:, None]
    causal = cols[None, :, None] >= cols[None, None, :]
    in_row = live[:, None, :] & causal
    in_window = in_row & (cols[None, :, None] - cols[None, None, :]
                          < ssm.window)
    ring_at = ring_rows_at(lengths, ssm.ring_rows, S)

    mamba = _mamba_rows_mixer(lengths, live, ssm)

    def mamba_layer(h, lw):
        h, (tail, st, y) = _sambay_block(h, lw, eps,
                                         lambda hn: mamba(hn, lw))
        return h, (st, tail), y

    def self_attend(mask):
        def attend(qw, k, v):
            return _diff_attend_plain(qw, k, v, mask), (k, v)
        return attend

    attn_layer = functools.partial(_sambay_attn_layer, nh=nh, nkv=nkv, hd=hd,
                                   eps=eps)

    def self_pair(h, xs):
        mw, aw = xs
        h, kept, _ = mamba_layer(h, mw)
        h, (k, v) = attn_layer(h, aw, "window_attn", self_attend(in_window))
        held = tuple(jnp.take_along_axis(
            kv_rows(t), ring_at[..., None], axis=1) for t in (k, v))
        return h, (kept, held)

    x, (kept, held) = jax.lax.scan(self_pair, x, params["self_layers"])
    mw, aw = params["mid_layers"]
    x, kept_mid, m = mamba_layer(x, mw)
    x, (k, v) = attn_layer(x, aw, "yoco_attn", self_attend(in_row))
    states, tails = (jnp.concatenate([a, b[None]]) for a, b in
                     zip(kept, kept_mid))
    if narrow:
        last = (lengths - 1)[:, None, None]
        x = jnp.take_along_axis(x, last, axis=1)
        m = jnp.take_along_axis(m, last, axis=1)
        seen = live[:, None, :]
    else:
        seen = in_row

    def cross_pair(h, xs):
        gw, cw = xs
        h, _ = _sambay_block(h, gw, eps, lambda hn: (_gmu(hn, gw, m), None))
        h, _ = attn_layer(
            h, cw, "yoco_attn",
            lambda qw, _k, _v: (_diff_attend_plain(qw, k, v, seen), None))
        return h, None

    x, _ = jax.lax.scan(cross_pair, x, params["cross_layers"])
    return x, k[None], v[None], (states, tails) + tuple(held)


def _sambay_span_forward(params, x, pool_k, pool_v, store, kv_attend,
                         tables, *, seg, pos, qstart, qlen, kvlen, nh, nkv,
                         hd, eps, ssm, decode_attn):
    """A decoder-hybrid-decoder model's layers over the packed buffer ``x
    [1, T, H]``. ``store`` is ``(states [Mamba layers, R, N, C] float32,
    tails [Mamba layers, R, conv - 1, C], window keys, values [window
    layers, R, ring blocks, bs, KD])``, carried whole like the pool:

    - a Mamba layer reads and writes its own index of the first two at the
      slots that have a span this step (``_hybrid_span_forward``'s rules: a
      span whose first position is 0 takes a zero state and a zero tail;
      spans of one token through ``ssm_recurrent_update``, longer ones
      through ``ssm_chunk_scan``, which the decode-only program, ``T ==
      ssm.decode_rows``, leaves out: the plan gave it no chunk);
    - a window layer writes a token's keys and values at its row of its
      slot's ring and attends through the ragged kernel over the table the
      program computes, bounded below by the window (``ring_coords``);
    - the middle full layer appends to the pool's one layer and attends over
      it (``kv_attend(pk, pv, 0)``);
    - the cross-decoder holds no cache and its output is used at a span's
      LAST token only, so the buffer NARROWS after the middle layers to one
      row a slot (``[1, R, H]``, a dead slot's row is row 0's): its GMUs gate
      that token's memory and its attention reads the pool layer the middle
      layer has just written, never writes it.

    Returns ``(x [1, R, H] by slot, pool_k, pool_v, store)``."""
    ss, cs, wk, wv = store
    R, T = qstart.shape[0], x.shape[1]
    live_tok = seg < R
    ragged = (ragged_paged_attention_pallas if decode_attn == "pallas"
              else ragged_attention_reference)
    ring_at, ring_tables = ring_coords(seg, pos, wk, tables.shape[1])

    mamba = _mamba_span_mixer(ssm, seg=seg, pos=pos, qstart=qstart,
                              qlen=qlen, kvlen=kvlen, T=T)

    def mamba_layer(h, lw, idx, ss, cs):
        h, (cs, ss, y) = _sambay_block(
            h, lw, eps, lambda hn: mamba(hn, lw, idx, ss, cs))
        return h, ss, cs, y

    attn_layer = functools.partial(_sambay_attn_layer, nh=nh, nkv=nkv, hd=hd,
                                   eps=eps)

    def self_pair(carry, xs):
        mw, aw, idx = xs
        h, ss, cs, wk, wv = carry
        h, ss, cs, _ = mamba_layer(h, mw, idx, ss, cs)

        def attend(qw, k, v):
            at = (idx,) + ring_at
            nwk = wk.at[at].set(kv_rows(k[0]), mode="drop")
            nwv = wv.at[at].set(kv_rows(v[0]), mode="drop")
            attn = ragged(qw[0], ring_as_pool(nwk), ring_as_pool(nwv),
                          ring_tables, qstart, qlen, kvlen, layer=idx,
                          window=ssm.window)
            return attn[None], (nwk, nwv)

        h, (wk, wv) = attn_layer(h, aw, "window_attn", attend)
        return (h, ss, cs, wk, wv), None

    mamba_self, attn_self = params["self_layers"]
    n_self = attn_self["subln"].shape[0]
    (x, ss, cs, wk, wv), _ = jax.lax.scan(
        self_pair, (x, ss, cs, wk, wv),
        (mamba_self, attn_self, jnp.arange(n_self, dtype=jnp.int32)))
    mw, aw = params["mid_layers"]
    x, ss, cs, m = mamba_layer(x, mw, n_self, ss, cs)
    write_attend = kv_attend(pool_k, pool_v, 0)

    def mid_attend(qw, k, v):
        attn, pools = write_attend(qw, k, v)
        return attn[None], pools

    x, (pool_k, pool_v) = attn_layer(x, aw, "yoco_attn", mid_attend)
    # the buffer narrows: a span's last token, by slot
    last = jnp.clip(qstart + qlen - 1, 0, T - 1)
    x, m = jnp.take(x, last, axis=1), jnp.take(m, last, axis=1)
    kd, vd, _, _ = _kv_attn_args(pool_k, pool_v)
    rows = jnp.arange(R, dtype=jnp.int32)
    has = (qlen > 0).astype(jnp.int32)

    def cross_attend(qw, _k, _v):
        return ragged(qw[0], kd, vd, tables, rows, has, kvlen,
                      layer=0)[None], None

    def cross_pair(h, xs):
        gw, cw = xs
        h, _ = _sambay_block(h, gw, eps, lambda hn: (_gmu(hn, gw, m), None))
        h, _ = attn_layer(h, cw, "yoco_attn", cross_attend)
        return h, None

    x, _ = jax.lax.scan(cross_pair, x, params["cross_layers"])
    return x, pool_k, pool_v, (ss, cs, wk, wv)


# ------------------------------- window layers with a sink around a full layer
# ``models.mimo_v2_flash``'s docstring has the equations. A tree with
# ``window_layers`` is a leading stack of dense layers and then PERIODS of
# window layers and one full layer (``_hybrid_scan``), every layer
# ``_decoder_layer``: what differs between the kinds (KV heads, the rotary
# base, the window, the sink, where the keys are cached) is the layer's tree
# and the program's ``rope`` / ``attend``.
def _gqa_attend_plain(q, k, v, lengths, window=None, sink=None):
    """Causal grouped-query softmax attention in ``jax.numpy`` over whole rows
    (whole-prompt prefill, ``forward``), a row of the group and a block of
    queries at a time, so that a long sequence's scores never exist whole: q
    ``[G, S, nh, hd]``, k ``[G, S, nkv, hd]``, v ``[G, S, nkv, vd]`` (a
    value's width its own); a query at position ``i`` of a row sees keys ``j
    <= i`` under ``lengths[g]``, with ``window`` only ``j > i - window``;
    ``sink [nh]`` float32 is one more column of a head's softmax with no
    value. Scores at ``hd ** -0.5``, float32. Returns ``[G, S, nh, vd]``."""
    S, nh, hd = q.shape[1:]
    nkv = k.shape[2]
    # (a block's float32 scores, every head's, stay under 64 MiB)
    block = min(S, max(8, (1 << 24) // (nh * S) // 8 * 8))
    n = -(-S // block)
    cols = jnp.arange(S, dtype=jnp.int32)[None, :]

    def one(row):
        q, k, v, length = row
        qp = jnp.pad(q, ((0, n * block - S), (0, 0), (0, 0))).reshape(
            n * block, nkv, nh // nkv, hd)

        def one_block(start):
            rows = start + jnp.arange(block, dtype=jnp.int32)[:, None]
            mask = (cols <= rows) & (cols < length)
            if window is not None:
                mask = mask & (cols > rows - window)
            logits = jnp.einsum(
                "qkgd,skd->kgqs", jax.lax.dynamic_slice_in_dim(qp, start,
                                                               block),
                k, preferred_element_type=jnp.float32) * hd ** -0.5
            logits = jnp.where(mask, logits, NEG_INF)
            if sink is not None:
                logits = jnp.concatenate([logits, jnp.broadcast_to(
                    sink.astype(jnp.float32).reshape(nkv, -1, 1, 1),
                    logits.shape[:3] + (1,))], -1)
            probs = jnp.where(mask, jax.nn.softmax(logits, axis=-1)[..., :S],
                              0.0)
            return jnp.einsum("kgqs,skd->qkgd", probs.astype(q.dtype), v)

        out = jax.lax.map(one_block,
                          jnp.arange(n, dtype=jnp.int32) * block)
        return out.reshape(n * block, nh, -1)[:S]

    return jax.lax.map(one, (q, k, v, lengths))


def _swa_dense_stack(params, carry, layer):
    """The leading dense layers of a tree with ``window_layers``, scanned:
    ``layer(carry, lw, index) -> (carry, ys)``, ``index`` the layer's place in
    the KV pool. Returns ``(carry, ys [dense layers, ...], their count)``."""
    dense = params["dense_layers"]
    n = dense["input_ln"].shape[0]
    carry, ys = jax.lax.scan(
        lambda c, xs: layer(c, xs[0], xs[1]), carry,
        (dense, jnp.arange(n, dtype=jnp.int32)))
    return carry, ys, n


def _swa_ropes(swa, theta, rotary, hd, seq_len, take):
    """``(the full layers' rope, the window layers')``: the first ``rotary``
    values of a head rotated over ``theta`` / ``swa.theta``, the rest left;
    ``take(x, sin=, cos=)`` rotates ``x`` at the program's positions given
    the ``[seq_len, rotary]`` tables."""
    ropes = []
    for base in (theta, swa.theta):
        sin, cos = _rope_tables(seq_len, rotary or hd, base)
        ropes.append(_hybrid_rope(
            rotary, hd, functools.partial(take, sin=sin, cos=cos)))
    return ropes


def _swa_prefill_layers(params, x, lengths, *, nh, nkv, hd, eps, swa, theta,
                        rotary=None, moe=None, return_picks=False,
                        ffn_rows=None):
    """The layers of a tree with ``window_layers`` over an admission group
    ``x [G, S_pad, H]``: the dense and full layers attend causally, the window
    layers inside their window with their sink, all through
    ``_gqa_attend_plain``; routed FFNs make pairs for the real tokens only.
    Returns ``(x, pk, pv [dense + full layers, G, S_pad, Hkv, key width |
    value width], (window keys, values [window layers, G, swa.ring_rows,
    KD]), moe stats)``: what the pool and the rings hold of a sequence
    (``ring_rows_at``), the stats as ``_packed_span_forward``'s.
    ``ffn_rows`` is ``_mixer_ffn_layer``'s, for the model's ``forward``."""
    S = x.shape[1]
    live = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]
    ring_at = ring_rows_at(lengths, swa.ring_rows, S)
    rope_full, rope_win = _swa_ropes(swa, theta, rotary, hd, S, _apply_rope)

    def attend(**window):
        return lambda q, k, v: (_gqa_attend_plain(q, k, v, lengths, **window),
                                (k, v))

    def layer(h, lw, experts, rope, attend, **kw):
        return _decoder_layer(
            h, lw, nh=nh, nkv=nkv, hd=hd, eps=eps, rope=rope, attend=attend,
            v_scale=swa.v_scale, live=live, moe=moe, experts=experts,
            return_picks=return_picks and experts is not None,
            ffn_rows=ffn_rows, **kw)

    def dense_layer(h, lw, _):
        h, kv, _ = layer(h, lw, None, rope_full, attend())
        return h, kv

    def full_layer(h, lw, _, experts):
        h, kv, stats = layer(h, lw, experts, rope_full, attend())
        return h, (kv, stats)

    def window_layer(h, lw, _, experts):
        h, (k, v), stats = layer(h, lw, experts, rope_win,
                                 attend(window=swa.window,
                                        sink=lw["sink"]),
                                 scope="window_attn")
        held = tuple(jnp.take_along_axis(kv_rows(t), ring_at[..., None],
                                         axis=1) for t in (k, v))
        return h, (held, stats)

    x, kv0, _ = _swa_dense_stack(params, x, dense_layer)
    x, (held, win_stats), (kv, full_stats) = _hybrid_scan(
        params, x, full_layer, window_layer)
    pk, pv = (jnp.concatenate(side) for side in zip(kv0, kv))
    # (by window layer; explicit: a forward that keeps no ring has 0 rows)
    return (x, pk, pv, tuple(a.reshape((a.shape[0] * a.shape[1],)
                                       + a.shape[2:]) for a in held),
            _hybrid_moe_stats(params, win_stats, full_stats))


def _swa_span_forward(params, x, pool_k, pool_v, store, kv_attend, tables,
                      *, seg, pos, qstart, qlen, kvlen, nh, nkv, hd, eps, swa,
                      theta, rotary, decode_attn, moe=None,
                      return_picks=False):
    """The layers of a tree with ``window_layers`` over the packed buffer ``x
    [1, T, H]``. TWO stores of different rows ride the scan as carry, whole:
    the KV pool (the dense and the full layers; ``kv_attend(pk, pv, layer)``
    appends and attends) and ``store``, the window layers' rings ``(keys,
    values [window layers, R, ring blocks, bs, KD])``, whose rows are the
    window layers' own (their KV heads, a key and a value of different
    widths). A window layer writes a token's keys and values at its row of its
    slot's ring and attends through the ragged kernel over the table the
    program computes, bounded below by the window, its sink one more column of
    the softmax (``ring_coords``). Both kinds rotate the first ``rotary``
    values of a head, the full layers over ``theta`` and the window layers
    over ``swa.theta``. Returns ``(x, pool_k, pool_v, store,
    moe stats)``, the last as ``_packed_span_forward``'s."""
    R = qstart.shape[0]
    live_tok = seg < R
    ragged = (ragged_paged_attention_pallas if decode_attn == "pallas"
              else ragged_attention_reference)
    s_tot = tables.shape[1] * _kv_data(pool_k).shape[2]
    ring_at, ring_tables = ring_coords(seg, pos, store[0], tables.shape[1])
    rope_full, rope_win = _swa_ropes(
        swa, theta, rotary, hd, s_tot,
        lambda t, sin, cos: _apply_rope_grid(
            t, jnp.take(sin, pos, axis=0, mode="clip")[None],
            jnp.take(cos, pos, axis=0, mode="clip")[None]))
    def layer(h, lw, experts, rope, attend, **kw):
        return _decoder_layer(
            h, lw, nh=nh, nkv=nkv, hd=hd, eps=eps, rope=rope, attend=attend,
            v_scale=swa.v_scale, live=live_tok[None], moe=moe,
            experts=experts,
            return_picks=return_picks and experts is not None, **kw)

    def dense_layer(carry, lw, idx):
        h, pk, pv = carry
        h, (pk, pv), _ = layer(h, lw, None, rope_full,
                               kv_attend(pk, pv, idx))
        return (h, pk, pv), None

    (x, pool_k, pool_v), _, n_dense = _swa_dense_stack(
        params, (x, pool_k, pool_v), dense_layer)

    def full_layer(carry, lw, idx, experts):
        h, pk, pv, st = carry
        h, (pk, pv), stats = layer(h, lw, experts, rope_full,
                                   kv_attend(pk, pv, n_dense + idx))
        return (h, pk, pv, st), stats

    def window_layer(carry, lw, idx, experts):
        h, pk, pv, (wk, wv) = carry

        def attend(q, k, v):
            at = (idx,) + ring_at
            nwk = wk.at[at].set(kv_rows(k[0]), mode="drop")
            nwv = wv.at[at].set(kv_rows(v[0]), mode="drop")
            attn = ragged(q[0], ring_as_pool(nwk), ring_as_pool(nwv),
                          ring_tables, qstart, qlen, kvlen, layer=idx,
                          window=swa.window, sink=lw["sink"])
            return attn, (nwk, nwv)

        h, st, stats = layer(h, lw, experts, rope_win, attend,
                             scope="window_attn")
        return (h, pk, pv, st), stats

    (x, pool_k, pool_v, store), win_stats, full_stats = _hybrid_scan(
        params, (x, pool_k, pool_v, tuple(store)), full_layer, window_layer)
    return x, pool_k, pool_v, store, _hybrid_moe_stats(
        params, win_stats, full_stats)


# ------------------------------------- Mamba layers around one attention layer
# ``models.jamba``'s docstring has the equations. A tree with ``mamba_layers``
# is PERIODS of Mamba-1 layers with one attention layer somewhere inside
# (``_hybrid_scan``); every layer is ``_jamba_block`` around a mixer: the
# shared ``_mamba_mixer`` with its three inner norms, or ``_jamba_attention``.
def _jamba_block(h, lw, eps, mixer):
    """``x = x + Mixer(RMSNorm(x; ln1))``, ``x = x + SwiGLU(RMSNorm(x;
    ln2))`` on ``h [B, S, H]``; ``mixer(hn) -> (out, carry)``. Returns ``(h,
    carry)``. Scope ``jamba_mlp``: the second half."""
    out, carry = mixer(_rms(h, lw["ln1"], eps))
    h = h + out
    with jax.named_scope("jamba_mlp"):
        m = _swiglu_proj(_rms(h, lw["ln2"], eps), lw["w_gate"], lw["w_up"],
                         lw["w_down"])
    return h + m, carry


@jax.named_scope("jamba_attn")
def _jamba_attention(hn, lw, attend, *, nh, nkv, hd):
    """Grouped-query attention with no positional term around the program's
    ``attend(q [B, S, nh, hd], k, v [B, S, nkv, hd]) -> (attn [B, S, nh, hd],
    carry)``. Returns ``(out [B, S, H], carry)``. Scope ``jamba_attn``: the
    projections, the kernel's call and ``W_o``."""
    q, k, v = _qkv_proj(hn, lw["wq"], lw["wk"], lw["wv"], nh, nkv, hd)
    attn, carry = attend(q, k, v)
    return _o_proj(attn.reshape(hn.shape[:2] + (nh * hd,)), lw["wo"]), carry


def _jamba_prefill_layers(params, x, lengths, *, nh, nkv, hd, eps, ssm,
                          narrow=True):
    """The layers of a tree with ``mamba_layers`` over an admission group
    ``x [G, S_pad, H]``: every Mamba layer scans from a zero state over each
    row's real tokens, the attention layers attend causally. With ``narrow``
    the stream comes back at each row's LAST real token only (the one the
    first token is sampled from). Returns ``(x [G, 1 or S_pad, H], pk, pv
    [attention layers, G, S_pad, Hkv, D], (states [Mamba layers, G, N, C]
    float32, tails [Mamba layers, G, conv - 1, C]))``: what each cache holds
    of a sequence."""
    S = x.shape[1]
    live = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]
    mamba = _mamba_rows_mixer(lengths, live, ssm, eps=eps)

    def full_layer(h, lw, *_):
        return _jamba_block(h, lw, eps, lambda hn: _jamba_attention(
            hn, lw, lambda q, k, v: (_attention(q, k, v, causal=True),
                                     (k, v)), nh=nh, nkv=nkv, hd=hd))

    def linear_layer(h, lw, *_):
        h, (tail, st, _) = _jamba_block(h, lw, eps, lambda hn: mamba(hn, lw))
        return h, (st, tail)

    x, kept, (pk, pv) = _hybrid_scan(params, x, full_layer, linear_layer)
    if narrow:
        x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    return x, pk, pv, tuple(a.reshape((-1,) + a.shape[2:]) for a in kept)


def _jamba_span_forward(params, x, pool_k, pool_v, store, kv_attend, *,
                        seg, pos, qstart, qlen, kvlen, nh, nkv, hd, eps, ssm):
    """The layers of a tree with ``mamba_layers`` over the packed buffer ``x
    [1, T, H]`` (``_hybrid_scan``). The KV pool (attention layers only) and
    the store ``(states [Mamba layers, R, N, C] float32, tails [Mamba layers,
    R, conv - 1, C])`` ride the scan as carry, whole: an attention layer
    appends and attends at its own count in the pool (``kv_attend(pk, pv,
    layer)``), a Mamba layer reads and writes its own count in the store
    (``_mamba_span_mixer``). Returns ``(x [1, R, H], a span's last token by
    slot as ``_sambay_span_forward``'s, pool_k, pool_v, store)``."""
    T = x.shape[1]
    mamba = _mamba_span_mixer(ssm, seg=seg, pos=pos, qstart=qstart,
                              qlen=qlen, kvlen=kvlen, T=T, eps=eps)

    def full_layer(carry, lw, idx, _):
        h, pk, pv, ss, cs = carry
        h, (pk, pv) = _jamba_block(h, lw, eps, lambda hn: _jamba_attention(
            hn, lw, kv_attend(pk, pv, idx), nh=nh, nkv=nkv, hd=hd))
        return (h, pk, pv, ss, cs), None

    def linear_layer(carry, lw, idx, _):
        h, pk, pv, ss, cs = carry
        h, (cs, ss, _) = _jamba_block(
            h, lw, eps, lambda hn: mamba(hn, lw, idx, ss, cs))
        return (h, pk, pv, ss, cs), None

    (x, pool_k, pool_v, ss, cs), _, _ = _hybrid_scan(
        params, (x, pool_k, pool_v) + tuple(store), full_layer, linear_layer)
    last = jnp.clip(qstart + qlen - 1, 0, T - 1)
    return jnp.take(x, last, axis=1), pool_k, pool_v, (ss, cs)


# ----------------------------------------------- one mixer a block (Nemotron-H)
# ``models.nemotron_h``'s docstring has the equations. A tree with
# ``ssd_layers`` is UNITS of a Mamba-2 block, optionally an attention block,
# then a routed FFN; every block is ``x = x + Mixer(RMSNorm(x))``.
def _ssd_mixer(hn, lw, *, ssd, eps, conv, scan):
    """A Mamba-2 mixer on ``hn [B, S, H]``. The program brings ``conv(xbc, w,
    bias) -> (silu(conv(xbc)) [B, S, C], carry)`` (where the convolution's
    earlier inputs come from) and ``scan(x, dt, A, b, c) -> (y [B, S, heads,
    P] float32, carry)`` (where the state comes from and which kernel walks
    the tokens): x ``[B, S, heads, P]``, dt ``[B, S, heads]``, b, c ``[B, S,
    groups, N]`` float32, ``A = -exp(A_log) [heads]``. Returns ``(out [B, S,
    H], (conv carry, scan carry))``. Scopes ``ssd_proj`` (the two
    projections) and ``ssd_mix`` (convolution, gates, the kernels, the gated
    norm)."""
    f32 = jnp.float32
    B, S = hn.shape[0], hn.shape[1]
    H, P, G, N = ssd.heads, ssd.head_dim, ssd.groups, ssd.state
    C, GN = H * P, G * N
    with jax.named_scope("ssd_proj"):
        zx = jnp.einsum("bsh,hc->bsc", hn, lw["ssd_in"])
        # the step leaves in float32: it is the exponent's scale
        dt = jnp.einsum("bsh,hc->bsc", hn, lw["ssd_dt"],
                        preferred_element_type=f32)
    with jax.named_scope("ssd_mix"):
        # (the convolved channels stay float32: x, B and C meet a float32
        # state; the stored tail is the projection's own rows, exactly)
        xbc, conv_carry = conv(zx[..., C:].astype(f32), lw["ssd_conv"],
                               lw["ssd_conv_b"])
        x = xbc[..., :C].reshape(B, S, H, P)
        dt = jax.nn.softplus(dt + lw["ssd_dt_b"].astype(f32))
        y, scan_carry = scan(
            x, dt, -jnp.exp(lw["ssd_A_log"].astype(f32)),
            xbc[..., C:C + GN].reshape(B, S, G, N),
            xbc[..., C + GN:].reshape(B, S, G, N))
        y = y + lw["ssd_D"].astype(f32)[:, None] * x
        y = _gated_group_norm(y.reshape(B, S, C), zx[..., :C],
                              lw["ssd_norm"], G, eps).astype(hn.dtype)
    with jax.named_scope("ssd_proj"):
        out = jnp.einsum("bsc,ch->bsh", y, lw["ssd_out"])
    return out, (conv_carry, scan_carry)


def _gated_group_norm(y, z, w, groups, eps):
    """Mamba-2's gated norm, float32: the gate BEFORE the norm, the norm by
    group: ``RMSNorm(y * silu(z))`` over ``groups`` runs of channels, times
    ``w``."""
    f32 = jnp.float32
    y = y.astype(f32) * jax.nn.silu(z.astype(f32))
    g = y.reshape(y.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(y.shape) * w.astype(f32)


def _mixer_qkv(h, params, at, *, nh, nkv, hd, eps):
    """The attention block at place ``at`` (traced) of ``attn_layers``: its
    weights and its projections of ``h [B, S, H]`` after the block's norm,
    ``(lw, q [B, S, nh, hd], k, v [B, S, nkv, hd])``. ``wq`` is stored by
    output feature, ``[nh * hd, H]``: the ragged kernel takes the query
    head-major, XLA therefore computes ``W_q^T`` times the rows, and with the
    stack read in place under a ``lax.cond`` it would turn a ``[H, nh * hd]``
    stack whole, every step (a 126 MB copy at Nemotron-3-Nano's sizes: the
    compile's memory report, PR 47)."""
    lw = {k: jax.lax.dynamic_index_in_dim(params["attn_layers"][k], at, 0,
                                          keepdims=False)
          for k in _MIXER_ATTN_KEYS}
    B, S = h.shape[0], h.shape[1]
    hn = _rms(h, lw["ln"], eps)
    q = jnp.einsum("bsh,dh->bsd", hn, lw["wq"]).reshape(B, S, nh, hd)
    k = jnp.einsum("bsh,hd->bsd", hn, lw["wk"]).reshape(B, S, nkv, hd)
    v = jnp.einsum("bsh,hd->bsd", hn, lw["wv"]).reshape(B, S, nkv, hd)
    return lw, q, k, v


def _mixer_moe(hn, lw, params, unit, *, moe, live, return_picks):
    """A unit's routed FFN on ``hn``: two-matrix experts read in place at
    ``unit`` of the stacks, plus the shared expert. Returns ``(out,
    stats)``, stats as ``_decoder_layer``'s."""
    m, *stats = moe_ffn(hn, lw["router"], None, params["w_up"],
                        params["w_down"], layer=unit, top_k=moe[0],
                        live=live, renormalize=moe[1],
                        return_picks=return_picks,
                        router_bias=lw["router_bias"],
                        **dict(zip(_ROUTING_KEYS, moe[2:])))
    with jax.named_scope("moe_shared"):
        m = m + jnp.einsum(
            "bsi,ih->bsh",
            relu2(jnp.einsum("bsh,hi->bsi", hn, lw["ws_up"])),
            lw["ws_down"])
    return m, (tuple(stats) if return_picks else stats[0])


def _mixer_units_scan(params, carry, ssd_block, attn_block, moe_block):
    """The forward of a model whose blocks are one mixer each: ONE scan over
    the units whose body runs the unit's Mamba-2 block, its attention block
    where it has one, and its routed FFN. ``ssd_block(carry, lw, unit)`` /
    ``moe_block(carry, lw, unit)`` return ``(carry, ys)``; ``attn_block(carry,
    at)`` likewise, ``at`` the unit's place among the attention blocks (-1:
    none; the ``lax.cond`` on it is the program's, which knows what a
    skipped block must hand on). Returns ``(carry, ssd ys, attention ys, FFN
    ys)``, each ``[units, ...]``."""
    ssd_w = {k: params["ssd_layers"][k] for k in _SSD_KEYS}
    moe_w = {k: params[k] for k in _MIXER_MOE_KEYS}
    units = params["attn_at"].shape[0]

    def unit(carry, xs):
        sw, mw, at, u = xs
        carry, y_ssd = ssd_block(carry, sw, u)
        carry, y_attn = attn_block(carry, at)
        carry, y_moe = moe_block(carry, mw, u)
        return carry, (y_ssd, y_attn, y_moe)

    carry, ys = jax.lax.scan(
        unit, carry, (ssd_w, moe_w, params["attn_at"],
                      jnp.arange(units, dtype=jnp.int32)))
    return (carry,) + ys


def _mixer_prefill_layers(params, x, lengths, *, nh, nkv, hd, eps, ssd, moe,
                          return_picks=False):
    """The blocks of a one-mixer-a-block model over an admission group ``x
    [G, S_pad, H]``: every Mamba-2 block scans from a zero state over each
    row's real tokens, the attention blocks attend causally, the routed FFNs
    make pairs for the real tokens only. Returns ``(x, pk, pv [attention
    blocks, G, S_pad, Hkv, D], (states [units, G, groups, N, heads / groups *
    P] float32, the store's layout (``kernels.ssd``), tails [units, G, conv -
    1, C]), moe stats)``: what each cache holds of a sequence; the last as
    ``_packed_span_forward``'s."""
    G, S = x.shape[0], x.shape[1]
    cols = jnp.arange(S, dtype=jnp.int32)
    live = cols[None, :] < lengths[:, None]
    rows_g = jnp.arange(G, dtype=jnp.int32)

    def conv(a, w, bias):
        return _rows_conv(a, w, bias, lengths)

    def scan(xh, dt, a, b, c):
        def flat(t):
            return t.reshape((G * S,) + t.shape[2:])

        zero = jnp.zeros((1, G) + ssd_state_shape(
            ssd.heads, ssd.head_dim, ssd.groups, ssd.state), jnp.float32)
        if ssd.kernel == "pallas":
            y, st = ssd_chunk_scan(
                flat(xh), flat(dt), a, flat(b), flat(c), zero, layer=0,
                start=rows_g * S, length=lengths, fresh=jnp.ones((G,), bool))
        else:
            y, st = ssd_reference(
                flat(xh), flat(dt), a, flat(b), flat(c), zero, layer=0,
                seg=jnp.where(live, rows_g[:, None], G).reshape(-1),
                first=jnp.broadcast_to(cols == 0, (G, S)).reshape(-1))
        return jnp.where(live[..., None, None], y.reshape(xh.shape), 0.0), \
            st[0]

    def ssd_block(h, lw, _):
        out, (tail, st) = _ssd_mixer(_rms(h, lw["ln"], eps), lw, ssd=ssd,
                                     eps=eps, conv=conv, scan=scan)
        return h + out, (st, tail.astype(h.dtype))

    def attn_block(h, at):
        def attend(h):
            with jax.named_scope("mixer_attn"):
                lw, q, k, v = _mixer_qkv(h, params, at, nh=nh, nkv=nkv,
                                         hd=hd, eps=eps)
                o = _o_proj(_attention(q, k, v, causal=True).reshape(
                    G, S, nh * hd), lw["wo"])
            return h + o, (k, v)

        def skip(h):
            z = jnp.zeros((G, S, nkv, hd), h.dtype)
            return h, (z, z)

        return jax.lax.cond(at >= 0, attend, skip, h)

    def moe_block(h, lw, u):
        m, stats = _mixer_moe(_rms(h, lw["moe_ln"], eps), lw, params, u,
                              moe=moe, live=live, return_picks=return_picks)
        return h + m, stats

    x, kept, (k, v), stats = _mixer_units_scan(params, x, ssd_block,
                                               attn_block, moe_block)
    with_attn = jnp.nonzero(params["attn_at"] >= 0,
                            size=params["attn_layers"]["ln"].shape[0])[0]
    return (x, jnp.take(k, with_attn, axis=0), jnp.take(v, with_attn, axis=0),
            kept, stats)


def _mixer_span_forward(params, x, pool_k, pool_v, store, kv_attend, *,
                        seg, pos, qstart, qlen, kvlen, nh, nkv, hd, eps, ssd,
                        moe, return_picks=False):
    """The blocks of a one-mixer-a-block model over the packed buffer ``x
    [1, T, H]`` (``_mixer_units_scan``). The KV pool (attention blocks only)
    and the state store ``(states [units, R, groups, N, heads / groups * P]
    float32 (``kernels.ssd``'s layout), tails [units, R, conv - 1, C])`` ride
    the scan as carry, whole:

    - a Mamba-2 block reads and writes its unit's index of the store at the
      slots that have a span this step (``_hybrid_span_forward``'s rules: a
      span whose first position is 0 takes a zero state and a zero tail;
      spans of one token through ``ssd_recurrent_update``, longer ones
      through ``ssd_chunk_scan``, which the decode-only program, ``T ==
      ssd.decode_rows``, leaves out: the plan gave it no chunk);
    - an attention block appends and attends at its own place in the pool
      (``kv_attend(pk, pv, at)``), under a ``lax.cond``: a unit without one
      hands the stream and the pool on as they are;
    - a routed FFN makes pairs for the live packed rows.

    Returns ``(x, pool_k, pool_v, store, moe stats)``, the last as
    ``_packed_span_forward``'s."""
    R, T = qstart.shape[0], x.shape[1]
    live_tok = seg < R
    seg_c = jnp.minimum(seg, R - 1)
    fresh = (kvlen - qlen) == 0
    one, many = qlen == 1, qlen > 1
    tok_one = live_tok & jnp.take(one, seg_c)
    row_at = jnp.clip(qstart, 0, T - 1)

    def ssd_block(carry, lw, idx):
        h, pk, pv, ss, cs = carry

        def conv(a, w, bias):
            c, tails = _span_conv(a[0], cs[idx], w, fresh=fresh,
                                  qstart=qstart, qlen=qlen, bias=bias)
            return c[None], cs.at[idx].set(tails.astype(cs.dtype))

        def scan(xh, dt, a, b, c):
            xh, dt, b, c = xh[0], dt[0], b[0], c[0]
            if ssd.kernel == "pallas":
                x1, dt1, b1, c1 = (jnp.take(t, row_at, axis=0)
                                   for t in (xh, dt, b, c))
                y1, new_ss = ssd_recurrent_update(
                    x1, dt1, a, b1, c1, ss, layer=idx, live=one, fresh=fresh)
                y = jnp.take(y1, seg_c, axis=0)
                if T != ssd.decode_rows:
                    yn, new_ss = ssd_chunk_scan(
                        xh, dt, a, b, c, new_ss, layer=idx, start=qstart,
                        length=jnp.where(many, qlen, 0), fresh=fresh)
                    y = jnp.where(tok_one[:, None, None], y, yn)
            else:
                y, new_ss = ssd_reference(
                    xh, dt, a, b, c, ss, layer=idx, seg=seg,
                    first=live_tok & (pos == 0))
            return jnp.where(live_tok[:, None, None], y, 0.0)[None], new_ss

        out, (cs, ss) = _ssd_mixer(_rms(h, lw["ln"], eps), lw, ssd=ssd,
                                   eps=eps, conv=conv, scan=scan)
        return (h + out, pk, pv, ss, cs), None

    def attn_block(carry, at):
        def attend(hkv):
            h, pk, pv = hkv
            with jax.named_scope("mixer_attn"):
                lw, q, k, v = _mixer_qkv(h, params, at, nh=nh, nkv=nkv,
                                         hd=hd, eps=eps)
                attn, (pk, pv) = kv_attend(pk, pv, at)(q, k, v)
                o = _o_proj(attn.reshape(1, T, nh * hd), lw["wo"])
            return h + o, pk, pv

        return jax.lax.cond(at >= 0, attend, lambda hkv: hkv,
                            carry[:3]) + carry[3:], None

    def moe_block(carry, lw, u):
        h = carry[0]
        m, stats = _mixer_moe(_rms(h, lw["moe_ln"], eps), lw, params, u,
                              moe=moe, live=live_tok[None],
                              return_picks=return_picks)
        return (h + m,) + carry[1:], stats

    (x, pool_k, pool_v, ss, cs), _, _, stats = _mixer_units_scan(
        params, (x, pool_k, pool_v) + tuple(store), ssd_block, attn_block,
        moe_block)
    return x, pool_k, pool_v, (ss, cs), stats


@jax.named_scope("lm_head")
def _head_logits(last_h, head):
    """The lm-head matmul ``[B, H] @ head`` (the pair arrives
    pre-oriented from ``_dq_head`` under a8)."""
    if isinstance(head, tuple):
        return _a8_dot(last_h, head)
    return jnp.einsum("bh,hv->bv", last_h, head)


# ------------------------------------------------- int8 block-pool view
# A quantized pool arrives as ONE pytree argument per side —
# ``(data int8, scale f32)`` — so every program signature (and its
# donation spec) is unchanged; these four helpers are the only places
# the programs touch the difference. Appends quantize on write through
# ``kv_cache.quantize_kv_rows`` (THE quantization rule — shared with
# the prefill scatter); attention dequantizes inside the kernels
# (``k_scale``/``v_scale``) or right after the oracle gather.
def _kv_data(pool):
    """The raw storage array of a pool side (shape/dtype queries)."""
    return pool[0] if isinstance(pool, tuple) else pool


def _kv_attn_args(pool_k, pool_v):
    """Unpack both pool sides for an attention call: ``(k, v,
    k_scale, v_scale)`` with None scales on a full-precision pool."""
    if isinstance(pool_k, tuple):
        return pool_k[0], pool_v[0], pool_k[1], pool_v[1]
    return pool_k, pool_v, None, None


def _kv_write(pool, at, x):
    """Scatter K/V rows ``x [..., Hkv, D]`` into the pool at the index
    arrays ``at``: ``(layer, phys, row)`` into the stored pool
    ``[L, nb, bs, Hkv * D]`` (in place on a donated or carried buffer),
    ``(phys, row)`` into one layer's view of it — quantizing on write on a
    quantized pool. int8 writes data + per-row-per-head scales to the SAME
    coordinates; fp8 is a data-only saturating cast
    (``quantize_kv_rows_fp8``) — its per-BLOCK scale planes are the
    constant 1.0 and are never written by appends (the determinism
    argument in ``BlockManager``'s docstring). Drop-mode both ways, index
    by index: a dead row (``phys`` the sentinel ``nb``) vanishes from data
    and scales alike and never lands in the next layer's block 0."""
    if isinstance(pool, tuple):
        data, sc = pool
        if data.dtype == jnp.float8_e4m3fn:
            return (data.at[at].set(kv_rows(quantize_kv_rows_fp8(x)),
                                    mode="drop"), sc)
        q, s = quantize_kv_rows(x)
        return (data.at[at].set(kv_rows(q), mode="drop"),
                sc.at[at].set(s, mode="drop"))
    return pool.at[at].set(kv_rows(x), mode="drop")


def _kv_heads(pool_l, nkv):
    """One layer's pool data ``[nb, bs, Hkv * D]`` with its heads apart,
    ``[nb, bs, Hkv, D]``, for the paged-decode kernels (programs that run
    in no benchmark cell; the unified step never cuts a layer out)."""
    return pool_l.reshape(pool_l.shape[:2] + (nkv, -1))


def _kv_gather_rows(pool_l, tables, shape4):
    """Gather per-row logical caches through the block tables
    (clip-mode; the suffix-prefill oracle path). ``shape4`` is the
    target ``(G, s_tot, Hkv, D)``. On a quantized pool the rows come
    back in the pool's NATIVE narrow dtype — no dequantized fp copy is
    materialized; the upcast fuses into the attention dots and the
    scales return separately (normalized to ``[G, s_tot, Hkv]``; fp8's
    per-block planes broadcast over each block's rows) for the
    post-dot rescale (``_row_scale_bhqk``). Returns
    ``(rows, scale_rows_or_None)``."""
    if isinstance(pool_l, tuple):
        data, sc = pool_l
        rows = jnp.take(data, tables, axis=0,
                        mode="clip").reshape(shape4)
        if sc.ndim == 2:         # fp8 per-block planes [nb, Hkv]
            srows = jnp.repeat(jnp.take(sc, tables, axis=0, mode="clip"),
                               shape4[-3] // tables.shape[1], axis=1)
        else:                    # int8 per-row planes [nb, bs, Hkv]
            srows = jnp.take(sc, tables, axis=0,
                             mode="clip").reshape(shape4[:-1])
        return rows, srows
    rows = jnp.take(pool_l, tables, axis=0, mode="clip").reshape(shape4)
    return rows, None


def _row_scale_bhqk(srows, grp):
    """Reshape gathered per-KV-row scales ``[G, s_tot, Hkv]`` into the
    ``[G, H, 1, s_tot]`` factor the suffix path's post-dot rescale
    broadcasts against its ``bhqk`` logits/probs — the gather-path
    twin of the kernels' head one-hot trick (each query head h reads
    its KV group's scale)."""
    sf = jnp.repeat(srows, grp, axis=2) if grp > 1 else srows
    return jnp.transpose(sf, (0, 2, 1))[:, :, None, :]


# --------------------------------------------- tensor parallel (TP) plumbing
# Multi-chip tensor-parallel serving (README "Tensor-parallel serving"):
# the engine's ``tp=N`` knob wraps the serving programs in ``shard_map``
# over a 1-D ``("tp",)`` mesh sharded OVER HEADS — wq/wk/wv (and the MLP
# gate/up) column-sharded so each shard computes ``nh/tp`` query heads
# and ``nkv/tp`` KV heads, wo/w_down row-sharded so their matmuls yield
# partial sums, and the paged KV pool partitioned on its head axis (each
# shard owns ``Hkv/tp`` heads of EVERY physical block — int8 scale
# planes partition on the same axis, so the host-side block tables /
# BlockManager / trie bookkeeping stay replicated and untouched).
# Exactly ONE all-reduce site pair per layer — post o-proj and post
# down-proj (``tp_reduce``) — is the only cross-chip traffic;
# ``collective_dtype="int8"`` runs it EQuARX-style block-quantized
# (``quantization.quantized_psum_int8``), cutting wire bytes ~3.5x.
# Attention (the ragged paged kernel or its jnp oracle) runs fully
# local: GQA group ratio nh/nkv is preserved per shard, the span/table
# metadata is replicated, and K/V appends land in the shard's own head
# slice — so donate/truncate/preempt/restore/trie-hit carry shards for
# free. Everything after the final all-reduce (final norm, lm head,
# sampling, the PRNG walk) is replicated math: every shard computes the
# same tokens, which is what lets the host read any one shard's copy.
TP_AXIS = "tp"

_COL_KEYS = ("wq", "wk", "wv", "w_gate", "w_up")   # shard output features
_ROW_KEYS = ("wo", "w_down")                       # shard input features


@functools.lru_cache(maxsize=None)
def _tp_mesh(tp):
    """The serving TP mesh: the first ``tp`` visible devices on one
    ``("tp",)`` axis (CPU-mesh development uses
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``; the test
    suite's conftest forces 8 virtual devices)."""
    devs = jax.devices()
    if tp > len(devs):
        raise ValueError(
            f"tp={tp} exceeds the {len(devs)} visible device(s); on CPU "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={tp}")
    return Mesh(np.array(devs[:tp]), (TP_AXIS,))


def _tp_validate(nh, nkv, tp):
    if nh % tp or nkv % tp:
        raise ValueError(
            f"tp={tp} must divide num_attention_heads ({nh}) and "
            f"num_key_value_heads ({nkv}): the mesh shards over heads")


#: row chunks per overlapped tp_reduce site — each chunk's collective
#: issues independently so its wire time hides under the neighbouring
#: chunks' (and the next projection's) compute on hardware; rows-only
#: chunking keeps per-row quantization scales (and the byte ledger)
#: exact
_OVERLAP_CHUNKS = 2


def _permute_allreduce(x, tp):
    """Ring reduce-scatter + all-gather over ``collective-permute``
    steps — the fp wire schedule of ``collective_overlap=True`` (README
    "Collective overlap"; "Fused Computation-Collective Operations",
    PAPERS.md). The hidden axis splits into ``tp`` pieces; ``tp - 1``
    ``ppermute`` hops accumulate each piece's cross-shard sum around
    the ring (reduce-scatter), ``tp - 1`` more hops gather the summed
    pieces back (all-gather). Per device the wire bytes are exactly
    ``2 * (tp-1)/tp`` of the payload — the same model
    ``quantization.collective_wire_bytes`` prices, so the collective
    ledger stays exact to the byte. Accumulation order is fixed by the
    ring (deterministic); at ``tp=2`` every output element is one
    commutative add, bit-equal to ``psum``."""
    idx = jax.lax.axis_index(TP_AXIS)
    shape = x.shape
    hid = shape[-1]
    pieces = jnp.moveaxis(
        x.reshape(shape[:-1] + (tp, hid // tp)), -2, 0)

    def _piece(i):
        return jax.lax.dynamic_index_in_dim(pieces, i % tp, 0,
                                            keepdims=False)

    ring = [(j, (j + 1) % tp) for j in range(tp)]
    # reduce-scatter: after step s, this device's accumulator holds
    # piece (idx + 1 - s) summed over s + 1 consecutive ring devices
    acc = _piece(idx + 1)
    for s in range(1, tp):
        acc = jax.lax.ppermute(acc, TP_AXIS, ring)
        acc = acc + _piece(idx + 1 - s)
    # all-gather: circulate the summed pieces back around the ring,
    # then reorder into hidden order (gathered[s] came from device
    # idx - s, which owns summed piece idx - s + 2 - tp)
    gathered = [acc]
    g = acc
    for _ in range(1, tp):
        g = jax.lax.ppermute(g, TP_AXIS, ring)
        gathered.append(g)
    order = (idx + 2 - tp - jnp.arange(tp)) % tp
    out = jnp.take(jnp.stack(gathered, 0), order, axis=0)
    return jnp.moveaxis(out, 0, -2).reshape(shape).astype(x.dtype)


def _overlap_reduce(base, tp, x):
    """Chunked compute/collective-overlap schedule for one
    ``tp_reduce`` site (``collective_overlap=True``): the partial-sum
    rows split into ``_OVERLAP_CHUNKS`` row chunks and each chunk's
    reduction issues independently — int8 runs the EQuARX quantized
    all-reduce per chunk (wire format preserved), fp runs the chunked
    collective-permute ring — so on hardware each chunk's wire time
    hides under the next chunk's and the following projection's
    compute. Chunking along ROWS only: every row's absmax scale, wire
    payload and reduced value are computed exactly as unchunked, so
    streams AND the ``serving_collective_bytes_total`` ledger are
    byte-identical to the unoverlapped schedule."""
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= int(d)
    flat = x.reshape((rows, x.shape[-1]))
    n = max(1, min(_OVERLAP_CHUNKS, rows))
    bounds = [(i * rows) // n for i in range(n + 1)]
    parts = [base(flat[lo:hi])
             for lo, hi in zip(bounds[:-1], bounds[1:])]
    return jnp.concatenate(parts, axis=0).reshape(x.shape)


def _tp_allreduce(collective_dtype, tp, overlap=False):
    """The per-layer cross-shard reduction — ``tp_reduce`` in the layer
    bodies. ``"fp"`` is a plain ``psum``; ``"int8"`` is the EQuARX-style
    block-quantized all-reduce (README "Tensor-parallel serving":
    measured greedy divergence, not assumed zero). ``overlap=True``
    (the engine's ``collective_overlap`` knob) swaps in the chunked
    schedule of :func:`_overlap_reduce` — fp additionally switches from
    one ``psum`` to the ring collective-permute reduce-scatter/
    all-gather (:func:`_permute_allreduce`), byte-identical at tp=2 and
    byte-exact on the wire ledger at every tp."""
    if collective_dtype == "int8":
        from ..quantization import quantized_psum_int8
        base = functools.partial(quantized_psum_int8, axis_name=TP_AXIS,
                                 tp=tp)
    elif overlap:
        base = functools.partial(_permute_allreduce, tp=tp)
    else:
        base = functools.partial(jax.lax.psum, axis_name=TP_AXIS)
    if not overlap:
        return base
    return functools.partial(_overlap_reduce, base, tp)


def _params_pspec(wq8):
    """PartitionSpec pytree matching the decode param dict:
    column-sharded QKV/gate/up, row-sharded o/down, everything else
    (embedding, norms, lm head) replicated. ``wq8`` mirrors the
    int8 weight-only pytree — each quantized leaf is a ``(q, scale)``
    pair whose scale keeps the contraction axis as size 1, so a
    column-sharded weight's per-output-channel scales shard with it
    while a row-sharded weight's scales stay replicated."""
    # NOTE: trailing-None-free specs throughout this module — jax
    # normalizes PartitionSpec(..., "tp", None) to (..., "tp") on
    # program OUTPUTS, and a pool array fed back next step under the
    # un-normalized spelling would read as a different sharding to the
    # pjit cache (one spurious re-specialization per program, breaking
    # the compile-once pin).
    col = PartitionSpec(None, None, TP_AXIS)
    row = PartitionSpec(None, TP_AXIS)
    rep = PartitionSpec()
    spec = dict(embed=rep, input_ln=rep, post_ln=rep, final_norm=rep,
                lm_head=rep)
    for k in _COL_KEYS:
        spec[k] = col
    for k in _ROW_KEYS:
        spec[k] = row
    if wq8:
        for k in _COL_KEYS:
            spec[k] = (col, col)       # scale [L, 1, out] shards with q
        for k in _ROW_KEYS:
            spec[k] = (row, rep)       # scale [L, 1, H] is replicated
        spec["lm_head"] = (rep, rep)
    return spec


def _pool_pspec(kv_quant):
    """PartitionSpec for one pool side: blocks replicated, HEADS
    sharded (axis 3 of the stored ``[L, nb, bs, Hkv * D]``: ``tp``
    contiguous pieces of it are ``Hkv / tp`` whole heads each). A quantized
    pool's scale planes partition on the same head axis — int8's per-row
    planes ``[L, nb, bs, Hkv]`` on axis 3, fp8's per-BLOCK planes
    ``[L, nb, Hkv]`` on axis 2. ``kv_quant``: False, "int8"/"fp8", or
    True (int8 back-compat)."""
    data = PartitionSpec(None, None, None, TP_AXIS)
    if kv_quant == "fp8":
        return (data, PartitionSpec(None, None, TP_AXIS))
    if kv_quant:
        return (data, PartitionSpec(None, None, None, TP_AXIS))
    return data


def _prefill_kv_pspec():
    """Spec of the cold prefill's returned K/V ``[L, G, S, Hkv, D]`` —
    always full-precision (quantize-on-write happens in the pool
    scatter, not here), heads sharded on axis 3."""
    return PartitionSpec(None, None, None, TP_AXIS)


def _tp_shard(impl, tp, in_specs, out_specs):
    """shard_map over the serving TP mesh. ``check_vma=False``: the
    replicated outputs (tokens, keys) are replicated by construction —
    every shard runs the same post-all-reduce math — and the sampling
    primitives defeat the automatic replication checker."""
    return jax.shard_map(impl, mesh=_tp_mesh(tp), in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def place_tp_params(params, tp, wq8):
    """Commit the decode param pytree onto the TP mesh per
    :func:`_params_pspec` — done ONCE per (model, tp, wq8) by the
    engine (cached model-resident, so rebuilds and fleet replicas share
    the placed arrays and the jit cache never re-uploads)."""
    mesh = _tp_mesh(tp)
    spec = _params_pspec(wq8)

    def _put(leaf, s):
        return jax.device_put(leaf, NamedSharding(mesh, s))

    out = {}
    for k, v in params.items():
        s = spec[k]
        if isinstance(v, tuple):
            out[k] = tuple(_put(leaf, ls) for leaf, ls in zip(v, s))
        else:
            out[k] = _put(v, s)
    return out


def _apply_rope_rows(x, sin_p, cos_p):
    """Rope with a DIFFERENT position per batch row (ragged decode).

    x: [B, 1, H, D]; sin_p/cos_p: [B, D] gathered at each row's position.
    """
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos_p[:, None, None, :]
            + rotated * sin_p[:, None, None, :]).astype(x.dtype)


@jax.named_scope("sample")
def sample_rows(logits, keys, temps, top_ks):
    """Per-row sampling: greedy where temps<=0, else top-k temperature.

    logits: [B, V]; keys: [B, 2] uint32; temps: [B] f32; top_ks: [B] i32
    (<=0 = no top-k filter). All knobs are runtime values — no retrace.
    """
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _sampled(_):
        lg = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
        k_eff = jnp.clip(jnp.where(top_ks <= 0, V, top_ks), 1, V)
        srt = jnp.sort(lg, axis=-1)  # ascending; kth-largest = srt[V - k]
        kth = jnp.take_along_axis(srt, (V - k_eff)[:, None], axis=-1)
        lg = jnp.where(lg < kth, NEG_INF, lg)
        sampled = jax.vmap(jax.random.categorical)(keys, lg)
        return jnp.where(temps > 0.0, sampled.astype(jnp.int32), greedy)

    # all-greedy batches (the model.generate default) must not pay the
    # [B, V] sort + categorical every tick just to discard the result
    return jax.lax.cond(jnp.any(temps > 0.0), _sampled,
                        lambda _: greedy, None)


# ------------------------------------------------------------------ prefill
@jax.named_scope("prefill")
def _moe_outputs(stats):
    """A program's routed-FFN outputs as a tuple: nothing for a dense model,
    the layers' summary ``[L, 5]``, and where the layers also returned
    their picks (``[L, ..., top_k]``) those as ``[L, rows, top_k]``."""
    if stats is None:
        return ()
    if isinstance(stats, tuple):
        st, picks = stats
        return (st, picks.reshape(picks.shape[0], -1, picks.shape[-1]))
    return (stats,)


def _hybrid_prefill_layers(params, x, lengths, *, nh, nkv, hd, eps, gdn,
                           theta=None, rotary=None, moe=None,
                           return_picks=False):
    """A hybrid model's layers over an admission group ``x [G, S_pad, H]``
    (``_hybrid_scan``): the full layers attend causally (rotating what
    ``_hybrid_rope`` says, by ``theta``) and return their K/V, the linear
    layers run the chunked scan from a zero state over each row's real tokens
    (a padding column has ``beta`` 0 and ``g`` 0: the state passes it
    unchanged) and return what their cache holds of a sequence: the final
    state ``[G, dk, heads * dv]`` float32 (the store's layout,
    ``kernels.gated_delta_rule.state_shape``) and the convolution's last
    inputs ``[G, conv - 1, C]``; routed FFNs make pairs for the real tokens
    only. Returns ``(x, pk, pv [full layers, G, S_pad, Hkv, D], (states,
    tails) [linear layers, G, ...], moe stats)``, the last as
    ``_packed_span_forward``'s."""
    G, S = x.shape[0], x.shape[1]
    cols = jnp.arange(S, dtype=jnp.int32)
    live = cols[None, :] < lengths[:, None]
    rows_g = jnp.arange(G, dtype=jnp.int32)
    norm = _norm_of(params)
    rotate = None
    if theta is not None:
        sin, cos = _rope_tables(S, rotary or hd, theta)

        def rotate(t):
            return _apply_rope(t, sin, cos)
    rope = _hybrid_rope(rotary, hd, rotate)

    def ffn(experts):
        return dict(norm=norm, live=live, moe=moe, experts=experts,
                    return_picks=return_picks and experts is not None)

    def full_layer(h, lw, _, experts):
        h, kv, stats = _decoder_layer(
            h, lw, nh=nh, nkv=nkv, hd=hd, eps=eps, rope=rope,
            attend=lambda q, k, v: (_attention(q, k, v, causal=True),
                                    (k, v)), **ffn(experts))
        return h, (kv, stats)

    def linear_layer(h, lw, _, experts):
        def mix(u, g, beta, conv_w):
            up, tail = _rows_conv(u, conv_w, None, lengths)
            q, k, v = gdn_split(up, gdn)
            g = jnp.where(live[..., None], g, 0.0)
            beta = jnp.where(live[..., None], beta, 0.0)

            def flat(a):
                return a.reshape((G * S,) + a.shape[2:])

            zero = jnp.zeros((1, G) + gdn_state_shape(
                gdn.heads, gdn.dk, gdn.dv), jnp.float32)
            if gdn.kernel == "pallas":
                o, st = gdn_chunk_scan(
                    flat(q), flat(k), flat(v), flat(g), flat(beta), zero,
                    layer=0, start=rows_g * S, length=lengths,
                    fresh=jnp.ones((G,), bool))
            else:
                o, st = gdn_reference(
                    flat(q), flat(k), flat(v), flat(g), flat(beta), zero,
                    layer=0, seg=jnp.where(live, rows_g[:, None], G).reshape(
                        -1), first=jnp.broadcast_to(cols == 0, (G, S)
                                                    ).reshape(-1))
            o = jnp.where(live[..., None, None],
                          o.reshape(G, S, gdn.heads, gdn.dv), 0.0)
            return o, (st[0], tail)

        h, kept, stats = _gdn_layer(h, lw, eps=eps, gdn=gdn, mix=mix,
                                    **ffn(experts))
        return h, (kept, stats)

    x, (kept, lin_stats), ((pk, pv), full_stats) = _hybrid_scan(
        params, x, full_layer, linear_layer)
    return (x, pk, pv, tuple(a.reshape((-1,) + a.shape[2:]) for a in kept),
            _hybrid_moe_stats(params, lin_stats, full_stats))


def _prefill_impl(params, ids, lengths, keys, temps, top_ks, *, nh, nkv,
                  hd, eps, theta, tied, tp_reduce=None, a8=False, moe=None,
                  mla=None, return_picks=False, gdn=None, ssm=None,
                  dsa=None, ssd=None, rotary=None, swa=None):
    """Batched prefill: ids [G, S_pad] (right-padded prompts), lengths
    [G] real token counts, per-row keys/temps/top_ks.

    Returns (pk, pv, tok0, keys') with pk/pv: [L, G, S_pad, Hkv, D] —
    one admission group in one device call (the engine pads G to a power
    of two so the compile count stays bounded). Padding rows/columns
    produce K/V garbage past each row's ``lengths`` — causal masking
    keeps it out of every real position's attention, and the cache slot
    masks it by ``lengths`` until decode overwrites it. A routed-FFN
    model (``router`` in ``params``) returns a fifth value, the layers'
    routing summary ``[L, 5]`` int32 (``kernels.moe_ffn.STATS``); its
    padding columns make no (token, expert) pair; with ``return_picks`` a
    sixth, the experts every position picked, ``[L, G * S_pad, top_k]`` int32
    by the router's ids (``serving.routing_record``). A model with latent
    attention (``wkv_a``; ``mla`` its static numbers) attends in the
    expanded form and returns as ``pk`` the rows its latent pool stores,
    ``[L, G, S_pad, 1, W]``, and a ``pv`` of width 0: no per-head K or V
    leaves the layer. Where that attention is over a learned selection
    (``idx_layer``; ``dsa`` its static numbers, ``models.glm_moe_dsa.Dsa``)
    ``pv`` is the index keys of the layers that have an indexer, ``[L_full,
    G, S_pad, 1, D]``: the pool's second side. A hybrid model
    (``linear_layers``; ``gdn`` its linear
    layers' static numbers) returns ``pk`` / ``pv`` of its FULL layers only,
    its routed FFNs' summary (and picks) where it has them and, last, what
    its linear layers' cache holds of each row (``_hybrid_prefill_layers``).
    A decoder-hybrid-decoder model
    (``self_layers``; ``ssm`` its static numbers) returns ``pk`` / ``pv`` of
    its ONE layer with a row a token and, last, what its Mamba layers' and
    window layers' stores hold of each row (``_sambay_prefill_layers``); a
    tree with ``mamba_layers`` under the same ``ssm`` returns ``pk`` / ``pv``
    of its attention layers and its Mamba layers' states and tails
    (``_jamba_prefill_layers``). A
    model whose blocks are one mixer each (``ssd_layers``; ``ssd`` its
    Mamba-2 blocks' static numbers) returns ``pk`` / ``pv`` of its attention
    blocks, its routed FFNs' summary (and picks) and, last, what its Mamba-2
    blocks' cache holds of each row (``_mixer_prefill_layers``). A tree with
    ``window_layers`` (``swa``) returns ``pk`` / ``pv`` of its dense and full
    layers (a key and a value of different widths), its routed FFNs' summary
    (and picks) and, last, what its window layers' rings hold of each row
    (``_swa_prefill_layers``).
    """
    B, S = ids.shape
    if swa is not None:
        x = jnp.take(params["embed"], ids, axis=0)
        x, pk, pv, state, stats = _swa_prefill_layers(
            params, x, lengths, nh=nh, nkv=nkv, hd=hd, eps=eps, swa=swa,
            theta=theta, rotary=rotary, moe=moe, return_picks=return_picks)
        tok0, keys2 = _first_token(
            params, _dq_head(params, tied, params["embed"].dtype, a8), x,
            lengths, keys, temps, top_ks, eps)
        return (pk, pv, tok0, keys2) + _moe_outputs(stats) + (state,)
    if ssd is not None:
        x = jnp.take(params["embed"], ids, axis=0)
        x, pk, pv, state, stats = _mixer_prefill_layers(
            params, x, lengths, nh=nh, nkv=nkv, hd=hd, eps=eps, ssd=ssd,
            moe=moe, return_picks=return_picks)
        tok0, keys2 = _first_token(
            params, _dq_head(params, tied, params["embed"].dtype, a8), x,
            lengths, keys, temps, top_ks, eps)
        return (pk, pv, tok0, keys2) + _moe_outputs(stats) + (state,)
    if ssm is not None:
        x = jnp.take(params["embed"], ids, axis=0)
        layers = _jamba_prefill_layers if "mamba_layers" in params \
            else _sambay_prefill_layers
        x, pk, pv, state = layers(
            params, x, lengths, nh=nh, nkv=nkv, hd=hd, eps=eps, ssm=ssm)
        tok0, keys2 = _first_token(
            params, _dq_head(params, tied, params["embed"].dtype, a8), x,
            jnp.ones_like(lengths), keys, temps, top_ks, eps)
        return pk, pv, tok0, keys2, state
    if gdn is not None:
        x = jnp.take(params["embed"], ids, axis=0)
        x, pk, pv, state, stats = _hybrid_prefill_layers(
            params, x, lengths, nh=nh, nkv=nkv, hd=hd, eps=eps, gdn=gdn,
            theta=theta, rotary=rotary, moe=moe, return_picks=return_picks)
        tok0, keys2 = _first_token(
            params, _dq_head(params, tied, params["embed"].dtype, a8), x,
            lengths, keys, temps, top_ks, eps)
        return (pk, pv, tok0, keys2) + _moe_outputs(stats) + (state,)
    sin, cos = _rope_tables_for(S, hd, theta, mla)
    wdt = params["embed"].dtype
    head = _dq_head(params, tied, wdt, a8)
    live = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]

    if mla is None:
        def attend(q, k, v):
            return _attention(q, k, v, causal=True), (k, v)
    else:
        def attend(q_nope, q_pe, c_kv, k_pe, w_kvb):
            with jax.named_scope("mla_attend"):
                attn = mla_expanded_attention(q_nope, q_pe, c_kv, k_pe,
                                              w_kvb, mla=mla)
            rows = latent_rows(c_kv, k_pe)
            return attn, (rows, rows[..., :0])

    def rope(x):
        return _apply_rope(x, sin, cos)

    x = jnp.take(params["embed"], ids, axis=0)
    kvs, stats = [], None
    # (attention over a selection: the set rides the layers as carry, from
    # a layer with an indexer to the ones that borrow it)
    sel = None if dsa is None else jnp.zeros((B, S, S), bool)
    for (_, names, stack, experts), indexer in zip(_layer_stacks(params),
                                                   _indexers(params)):
        def prefill_layer(carry, lp):
            h, sel = carry
            lw = dict(zip(names, _dq_layer(lp, wdt, a8)))

            h, kv, st = _decoder_layer(
                h, lw, nh=nh, nkv=nkv, hd=hd, eps=eps, rope=rope,
                attend=attend if dsa is None else sequence_attend_selected(
                    lw, indexer, sel, rope, mla=mla, dsa=dsa),
                live=live, moe=moe, experts=experts,
                tp_reduce=tp_reduce, mla=mla,
                return_picks=return_picks and experts is not None)
            if dsa is not None:
                kv, sel = kv[:2], kv[2]
            return (h, sel), (kv, st)

        (x, sel), (kv, st) = jax.lax.scan(prefill_layer, (x, sel), stack)
        if dsa is not None:
            # the index keys of the layers that have an indexer, in order
            full = () if indexer is None else jnp.nonzero(
                stack[names.index("idx_layer")] >= 0,
                size=indexer["idx_wk"].shape[0])[0]
            kv = (kv[0], jnp.take(kv[1], jnp.asarray(full, jnp.int32),
                                  axis=0))
        kvs.append(kv)
        stats = st if experts is not None else stats
    pk, pv = (jnp.concatenate(side) if len(kvs) > 1 else side[0]
              for side in zip(*kvs))
    tok0, keys2 = _first_token(params, head, x, lengths, keys, temps, top_ks,
                               eps)
    return (pk, pv, tok0, keys2) + _moe_outputs(stats)


def _first_token(params, head, x, lengths, keys, temps, top_ks, eps):
    """A prefilled group's first token: the head on each row's last real
    position, sampled under the row's key. Returns ``(tok0, keys')``."""
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None], axis=1)[:, 0]  # [G, H]
    logits = _head_logits(_final_norm(params, last, eps), head)
    both = jax.vmap(jax.random.split)(keys)  # [G, 2, 2]
    return sample_rows(logits, both[:, 1], temps, top_ks), both[:, 0]


def build_prefill_fn(*, nh, nkv, hd, eps, theta, tied, tp=1,
                     collective_dtype="fp", wq8=False, a8=False, moe=None,
                     mla=None, return_picks=False, gdn=None, ssm=None,
                     dsa=None, ssd=None, rotary=None, swa=None):
    """One jitted prefill; jax retraces per (group, prompt-bucket)
    shape — both padded to powers of two by the engine. ``tp > 1``
    wraps it in shard_map over the heads-sharded mesh (README
    "Tensor-parallel serving"): the returned K/V carries each shard's
    ``Hkv/tp`` heads, partitioned exactly like the pool it is about to
    be scattered into."""
    if int(tp) > 1:
        tp = int(tp)
        _tp_validate(nh, nkv, tp)
        impl = functools.partial(
            _prefill_impl, nh=nh // tp, nkv=nkv // tp, hd=hd, eps=eps,
            theta=theta, tied=tied,
            tp_reduce=_tp_allreduce(collective_dtype, tp), a8=a8)
        rep = PartitionSpec()
        return jax.jit(_tp_shard(
            impl, tp,
            in_specs=(_params_pspec(wq8),) + (rep,) * 5,
            out_specs=(_prefill_kv_pspec(), _prefill_kv_pspec(),
                       rep, rep)))
    return jax.jit(functools.partial(
        _prefill_impl, nh=nh, nkv=nkv, hd=hd, eps=eps, theta=theta,
        tied=tied, a8=a8, moe=moe, mla=mla, return_picks=return_picks,
        **({} if gdn is None else {"gdn": gdn}),
        **({} if ssm is None else {"ssm": ssm}),
        **({} if dsa is None else {"dsa": dsa}),
        **({} if ssd is None else {"ssd": ssd}),
        **({} if rotary is None else {"rotary": rotary}),
        **({} if swa is None else {"swa": swa})))


# ------------------------------------------------------------ suffix prefill
def _apply_rope_grid(x, sin_p, cos_p):
    """Rope with a different position per (row, column) — suffix prefill.

    x: [G, S, H, D]; sin_p/cos_p: [G, S, D] gathered at each token's
    global position (prefix offset + column).
    """
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos_p[:, :, None, :]
            + rotated * sin_p[:, :, None, :]).astype(x.dtype)


# ----------------------------------------------------- paged suffix prefill
@jax.named_scope("paged_suffix_prefill")
def _paged_suffix_prefill_impl(params, pool_k, pool_v, tables, prefix_lens,
                               ids, suffix_lens, keys, temps, top_ks, *,
                               nh, nkv, hd, eps, theta, tied,
                               tp_reduce=None, a8=False):
    """Prefill only the UNCOVERED suffix of prompts whose leading blocks
    a prefix-cache hit already installed into their block tables.

    ids: [G, S_pad] right-padded suffix token ids; prefix_lens: [G] rows
    already valid behind each row's table (the installed cached blocks);
    suffix_lens: [G] real suffix token counts.
    tables: [G, max_blocks] int32 physical block ids (sentinel
    ``num_blocks`` marks unmapped entries and padding rows). Suffix
    token K/V at column i lands at logical position
    ``prefix_lens[g] + i`` -> physical ``(tables[g, pos//bs], pos%bs)``
    — always a block the row privately owns, because the covered prefix
    is block-aligned and everything past it was freshly allocated. The
    shared prefix blocks are READ through the same table but never
    written: that is the zero-copy COW discipline in one line. Each
    query attends over rows ``0..pos`` — cached prefix plus the suffix
    written so far, exactly the rows a cold full prefill would attend.

    Shapes depend only on (G_pad, S_pad, pool geometry, max_blocks);
    tables/lengths/knobs are runtime arrays, so the compile set stays
    the same pow2 (group, bucket) grid as the cold prefill.

    Returns (pool_k', pool_v', tok0, keys'). On a quantized pool
    (int8 or fp8) each side arrives (and returns) as a
    ``(data, scale)`` pair: suffix K/V quantize on write
    (``_kv_write``) and the in-program attention reads the pool
    NATIVELY — the table gather keeps the narrow dtype
    (``_kv_gather_rows``), the upcast fuses into the attention dots,
    and the scales apply post-dot — no materialized fp round-trip.
    """
    G, S = ids.shape
    nb, bs = _kv_data(pool_k).shape[1], _kv_data(pool_k).shape[2]
    mb = tables.shape[1]
    s_tot = mb * bs
    sin, cos = _rope_tables(s_tot, hd, theta)
    stack = tuple(params[k] for k in _STACK_KEYS)
    wdt = params["embed"].dtype
    head = _dq_head(params, tied, wdt, a8)

    pos = prefix_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    sin_p = jnp.take(sin, pos, axis=0, mode="clip")   # [G, S, D]
    cos_p = jnp.take(cos, pos, axis=0, mode="clip")
    rows = jnp.arange(s_tot, dtype=jnp.int32)
    # causal-over-ragged mask: query at global pos p sees rows r <= p
    mask = rows[None, None, :] <= pos[:, :, None]        # [G, S, s_tot]
    # rows ever valid for this row's attention; later rows may hold
    # clip-gathered garbage from sentinel entries — zeroed out of PV
    row_valid = rows[None, :] < (prefix_lens + S)[:, None]  # [G, s_tot]
    grp = nh // nkv
    scale = 1.0 / (hd ** 0.5)
    # pool write coordinates: padding columns MUST drop — a junk write
    # into the pool could land in a block another sequence owns only via
    # a bug, but dropping keeps the invariant airtight: only
    # (col < suffix_len) positions write.
    bi = jnp.minimum(pos // bs, mb - 1)
    phys = jnp.take_along_axis(tables, bi, axis=1)        # [G, S]
    cols = jnp.arange(S, dtype=jnp.int32)[None, :]
    phys = jnp.where(cols < suffix_lens[:, None], phys, nb)
    prow = pos % bs

    def layer(h, lp):
        (lwq, lwk, lwv, lwo, lg, lu, ld, lin, lpost, pk_l, pv_l) = \
            _dq_layer(lp, wdt, a8)
        with jax.named_scope("attn"):
            hn = _rms(h, lin, eps)
            q, k, v = _qkv_proj(hn, lwq, lwk, lwv, nh, nkv, hd)
            q = _apply_rope_grid(q, sin_p, cos_p)
            k = _apply_rope_grid(k, sin_p, cos_p)
            # write the suffix K/V through the table (quantize-on-write on
            # a quantized pool), then gather each row's logical cache
            # (shared prefix + own suffix) in the pool's NATIVE dtype — the
            # upcast fuses into the attention dots and the scales apply
            # POST-dot (``_row_scale_bhqk``), so a quantized pool never
            # round-trips through a materialized fp copy; the causal mask
            # keeps columns from seeing rows past their position
            pk_l = _kv_write(pk_l, (phys, prow), k)
            pv_l = _kv_write(pv_l, (phys, prow), v)
            ck, ksr = _kv_gather_rows(pk_l, tables, (G, s_tot, nkv, hd))
            cv, vsr = _kv_gather_rows(pv_l, tables, (G, s_tot, nkv, hd))
            kf = jnp.repeat(ck, grp, axis=2) if grp > 1 else ck
            vf = jnp.repeat(cv, grp, axis=2) if grp > 1 else cv
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf.astype(q.dtype),
                                preferred_element_type=jnp.float32) * scale
            if ksr is not None:
                logits = logits * _row_scale_bhqk(ksr, grp)
            logits = jnp.where(mask[:, None], logits, NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1)
            probs = jnp.where(mask[:, None], probs, 0.0)
            if vsr is not None:
                probs = probs * _row_scale_bhqk(vsr, grp)
            vf = jnp.where(row_valid[:, :, None, None], vf,
                           jnp.zeros_like(vf))
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype),
                              vf.astype(q.dtype))
            o = _o_proj(attn.reshape(G, S, nh * hd), lwo)
            h = h + (o if tp_reduce is None else tp_reduce(o))
        m = _swiglu_proj(_rms(h, lpost, eps), lg, lu, ld)
        h = h + (m if tp_reduce is None else tp_reduce(m))
        return h, (pk_l, pv_l)

    x = jnp.take(params["embed"], ids, axis=0)
    x, (npk, npv) = jax.lax.scan(layer, x, stack + (pool_k, pool_v))
    last = jnp.take_along_axis(
        x, (suffix_lens - 1)[:, None, None], axis=1)[:, 0]  # [G, H]
    last_h = _rms(last, params["final_norm"], eps)
    logits = _head_logits(last_h, head)
    both = jax.vmap(jax.random.split)(keys)  # [G, 2, 2]
    tok0 = sample_rows(logits, both[:, 1], temps, top_ks)
    return npk, npv, tok0, both[:, 0]


def build_paged_suffix_prefill_fn(*, nh, nkv, hd, eps, theta, tied,
                                  donate=None, tp=1,
                                  collective_dtype="fp", kv_quant=False,
                                  wq8=False, a8=False):
    """One jitted paged suffix prefill; retraces per (group, bucket)
    shape — same bounded pow2 grid as the cold prefill.
    ``tp > 1`` runs it sharded over heads with the pool partitioned per
    shard (README "Tensor-parallel serving")."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    if int(tp) > 1:
        tp = int(tp)
        _tp_validate(nh, nkv, tp)
        impl = functools.partial(
            _paged_suffix_prefill_impl, nh=nh // tp, nkv=nkv // tp,
            hd=hd, eps=eps, theta=theta, tied=tied,
            tp_reduce=_tp_allreduce(collective_dtype, tp), a8=a8)
        rep = PartitionSpec()
        pool = _pool_pspec(kv_quant)
        return jax.jit(_tp_shard(
            impl, tp,
            in_specs=(_params_pspec(wq8), pool, pool) + (rep,) * 7,
            out_specs=(pool, pool, rep, rep)),
            donate_argnums=(1, 2) if donate else ())
    return jax.jit(
        functools.partial(_paged_suffix_prefill_impl, nh=nh, nkv=nkv, hd=hd,
                          eps=eps, theta=theta, tied=tied, a8=a8),
        donate_argnums=(1, 2) if donate else ())


# ------------------------------------------------------ unified ragged step
def _fused_decode_tick(params, stack, head, tables, sin, cos, tok, pk_all,
                       pv_all, lens, kys, app_mask, temps, top_ks, *, nh,
                       nkv, hd, eps, decode_attn, tp_reduce=None, a8=False):
    """ONE fused decode tick over all rows — THE shared tail body of
    the unified ragged step's scan and the multi-tick step's
    while_loop (the two must compute identically or ``decode_ticks>1``
    streams could drift from the single-tick baseline). ``app_mask``
    [R] int32 is 1 where the row's append/length-advance is real (the
    ragged tail's ``dec_mask``; the multi-tick tail's alive mask) —
    masked rows drop their append and attend at their frozen length.
    Returns ``(next_tok, pk', pv', keys')``; the CALLER advances
    ``lens`` by ``app_mask``.
    """
    R = tok.shape[0]
    nb, bs = _kv_data(pk_all).shape[1], _kv_data(pk_all).shape[2]
    mb = tables.shape[1]
    s_tot = mb * bs
    wdt = params["embed"].dtype
    x = jnp.take(params["embed"], tok[:, None], axis=0)     # [R, 1, H]
    sin_r = jnp.take(sin, lens, axis=0, mode="clip")
    cos_r = jnp.take(cos, lens, axis=0, mode="clip")
    bi = jnp.minimum(lens // bs, mb - 1)
    phys = jnp.take_along_axis(tables, bi[:, None], axis=1)[:, 0]
    # masked rows (idle slots, chunk rows, alive-mask-retired rows)
    # must not append: their next write belongs to a later program
    phys = jnp.where((app_mask > 0) & (lens < s_tot), phys, nb)
    prow = lens % bs

    def layer(h, xs):
        lwq, lwk, lwv, lwo, lg, lu, ld, lin, lpost, pk_l, pv_l = \
            _dq_layer(xs, wdt, a8)
        with jax.named_scope("attn"):
            hn = _rms(h, lin, eps)
            q, k, v = _qkv_proj(hn, lwq, lwk, lwv, nh, nkv, hd)
            q = _apply_rope_rows(q, sin_r, cos_r)
            k = _apply_rope_rows(k, sin_r, cos_r)
            pk_l = _kv_write(pk_l, (phys, prow), k[:, 0])
            pv_l = _kv_write(pv_l, (phys, prow), v[:, 0])
            kd, vd, ksc, vsc = _kv_attn_args(pk_l, pv_l)
            attend = (paged_decode_attention_pallas
                      if decode_attn == "pallas"
                      else paged_decode_attention_reference)
            attn = attend(q[:, 0], _kv_heads(kd, nkv), _kv_heads(vd, nkv),
                          tables, lens + app_mask, k_scale=ksc, v_scale=vsc)
            o = _o_proj(attn.reshape(R, 1, nh * hd), lwo)
            h = h + (o if tp_reduce is None else tp_reduce(o))
        m = _swiglu_proj(_rms(h, lpost, eps), lg, lu, ld)
        h = h + (m if tp_reduce is None else tp_reduce(m))
        return h, (pk_l, pv_l)

    x, (npk, npv) = jax.lax.scan(layer, x, stack + (pk_all, pv_all))
    lastt = _rms(x[:, 0], params["final_norm"], eps)
    lgt = _head_logits(lastt, head)
    b2 = jax.vmap(jax.random.split)(kys)
    nxt = sample_rows(lgt, b2[:, 1], temps, top_ks)
    return nxt, npk, npv, b2[:, 0]


def _span_last_sample(params, head, x, qstart, qlen, keys, temps, top_ks,
                      eps):
    """Tick 0's per-slot sample — each slot samples from its span's
    LAST packed position (decode rows: the one token; chunk rows: the
    chunk end — live only when the chunk completes the prompt).
    Shared by the unified and multi-tick steps so the sampling rule
    cannot drift. Returns ``(tok0, keys')`` after one split per row.
    """
    T = x.shape[1]
    last_idx = jnp.clip(qstart + qlen - 1, 0, T - 1)
    return _rows_sample(params, head, jnp.take(x[0], last_idx, axis=0),
                        keys, temps, top_ks, eps)


def _rows_sample(params, head, last, keys, temps, top_ks, eps):
    """One sample a slot from ``last [R, H]``, each slot's hidden state at
    the position it samples from. Returns ``(tok0, keys')``."""
    last_h = _final_norm(params, last, eps)
    logits = _head_logits(last_h, head)
    both = jax.vmap(jax.random.split)(keys)                 # [R, 2, 2]
    tok0 = sample_rows(logits, both[:, 1], temps, top_ks)
    return tok0, both[:, 0]


def _hybrid_span_forward(params, x, pool_k, pool_v, state, kv_attend, *,
                         seg, pos, qstart, qlen, kvlen, nh, nkv, hd, eps,
                         gdn, rotate=None, rotary=None, moe=None,
                         return_picks=False):
    """A hybrid model's layers over the packed buffer ``x [1, T, H]``
    (``_hybrid_scan``). The KV pool (full layers only) and the state store
    ``(states [linear layers, R, dk, heads * dv] float32, tails [linear
    layers, R, conv - 1, C])`` ride the scan as carry, whole: a full layer
    appends and attends at its own count in the pool (``kv_attend(pk, pv,
    layer)``; ``rotate`` the program's rotation at the packed rows'
    positions, ``_hybrid_rope``), a linear layer reads and writes its own
    count in the store, at the slots that have a span this step and nowhere
    else; a routed FFN, after either kind of mixer, makes pairs for the live
    packed rows.

    What a linear layer needs of the span table: where a span starts in the
    buffer (``qstart``) says which of the convolution's earlier inputs are
    rows of this buffer and which the slot's stored tail; a span whose first
    position ``kvlen - qlen`` is 0 takes a zero tail and a zero state,
    whatever its slot held (no program ever zeroes a slot); spans of one
    token (decode rows) go through ``gdn_recurrent_update`` together, longer
    ones (prefill chunks) through ``gdn_chunk_scan``, which the decode-only
    program, ``T == gdn.decode_rows``, leaves out (the plan gave it no chunk),
    both following the live spans and not the buffer
    (``decode_attention="jnp"``: the token-by-token oracle over the whole
    buffer). Returns ``(x, pool_k, pool_v, state, moe stats)``, the last as
    ``_packed_span_forward``'s."""
    R, T = qstart.shape[0], x.shape[1]
    live_tok = seg < R
    seg_c = jnp.minimum(seg, R - 1)
    fresh = (kvlen - qlen) == 0
    one, many = qlen == 1, qlen > 1
    tok_one = live_tok & jnp.take(one, seg_c)
    row_at = jnp.clip(qstart, 0, T - 1)
    norm = _norm_of(params)
    rope = _hybrid_rope(rotary, hd, rotate)

    def ffn(experts):
        return dict(norm=norm, live=live_tok[None], moe=moe, experts=experts,
                    return_picks=return_picks and experts is not None)

    def full_layer(carry, lw, idx, experts):
        h, pk, pv, st = carry
        h, (pk, pv), stats = _decoder_layer(
            h, lw, nh=nh, nkv=nkv, hd=hd, eps=eps, rope=rope,
            attend=kv_attend(pk, pv, idx), **ffn(experts))
        return (h, pk, pv, st), stats

    def linear_layer(carry, lw, idx, experts):
        h, pk, pv, (ss, cs) = carry
        if ss.dtype != jnp.float32:
            # (as ``_mamba_span_mixer``: a state rounded to bfloat16 a token
            # moves the logits by less than a check on logits can see)
            raise TypeError(f"a Gated DeltaNet layer's state is float32, "
                            f"the store holds {ss.dtype}")

        def mix(u, g, beta, conv_w):
            u, g, beta = u[0], g[0], beta[0]
            up, tails = _span_conv(u, cs[idx], conv_w, fresh=fresh,
                                   qstart=qstart, qlen=qlen)
            new_cs = cs.at[idx].set(tails)
            q, k, v = gdn_split(up, gdn)
            if gdn.kernel == "pallas":
                o1, new_ss = gdn_recurrent_update(
                    *(jnp.take(a, row_at, axis=0) for a in (q, k, v, g, beta)),
                    ss, layer=idx, live=one, fresh=fresh)
                o = jnp.take(o1, seg_c, axis=0)
                if T != gdn.decode_rows:
                    on, new_ss = gdn_chunk_scan(
                        q, k, v, g, beta, new_ss, layer=idx, start=qstart,
                        length=jnp.where(many, qlen, 0), fresh=fresh)
                    o = jnp.where(tok_one[:, None, None], o, on)
            else:
                o, new_ss = gdn_reference(
                    q, k, v, g, beta, ss, layer=idx, seg=seg,
                    first=live_tok & (pos == 0))
            o = jnp.where(live_tok[:, None, None], o, 0.0)
            return o[None], (new_ss, new_cs)

        h, st, stats = _gdn_layer(h, lw, eps=eps, gdn=gdn, mix=mix,
                                  **ffn(experts))
        return (h, pk, pv, st), stats

    (x, pool_k, pool_v, state), lin_stats, full_stats = _hybrid_scan(
        params, (x, pool_k, pool_v, state), full_layer, linear_layer)
    return x, pool_k, pool_v, state, _hybrid_moe_stats(
        params, lin_stats, full_stats)


def _packed_span_forward(params, pool_k, pool_v, tables, ids, seg, pos,
                         qstart, qlen, kvlen, sin, cos, *, nh, nkv, hd,
                         eps, decode_attn, tp_reduce=None, a8=False,
                         moe=None, mla=None, return_picks=False, state=None,
                         gdn=None, ssm=None, dsa=None, ssd=None, swa=None,
                         theta=None):
    """ONE forward pass over a packed buffer of variable-length query
    spans through the block tables — the shared tick-0 assembly of the
    unified ragged step AND the speculative verify program (the two
    must write/attend identically or their streams could drift). K/V
    for every live packed token is scattered through its slot's table
    at its logical position (dead rows — ``seg == R`` — and positions
    past the logical capacity DROP), attention runs through the ragged
    paged kernel or its jnp oracle. Returns ``(x [1, T, H], pk, pv,
    moe_stats)``: the routed layers' summary ``[L, 5]`` int32 of a
    routed-FFN model (dead packed rows make no pair), else None; with
    ``return_picks`` the pair ``(summary, picked experts [L, 1, T, top_k])``.
    A model with latent attention (``mla``) writes one row a token into the K side,
    the latent pool (its V side has width 0), and every span, decode row
    and chunk alike, attends in the absorbed form. Where that attention is
    over a learned selection (``dsa``), the V side is the index-key pool: a
    layer with an indexer writes the step's index keys into it, scores each
    query against its row's cached keys and selects, and every layer attends
    over the set of the last such layer (``attend_selected`` below). A hybrid model (``state``
    its linear layers' store, ``gdn`` their static numbers) rotates what
    ``_hybrid_rope`` says (nothing where ``sin`` is None), runs
    ``_hybrid_span_forward`` and returns the store fourth and its routed
    FFNs' stats (None: dense FFNs) fifth. A decoder-hybrid-decoder model (``ssm``) runs
    ``_sambay_span_forward`` over ``state``, its Mamba and window layers'
    stores, and returns ``x`` NARROWED to one row a slot, ``[1, R, H]``; so
    does a tree with ``mamba_layers`` (``_jamba_span_forward``, ``state`` its
    Mamba layers' store). A
    model whose blocks are one mixer each (``ssd``) runs
    ``_mixer_span_forward`` over ``state``, its Mamba-2 blocks' store, and
    returns the store fourth and its routed FFNs' stats fifth; so does a
    tree with ``window_layers`` (``swa``; ``_swa_span_forward`` over
    ``state``, its window layers' rings, building its two rotary tables from
    ``theta`` and ``swa.theta`` itself).
    """
    R = tables.shape[0]
    nb, bs = _kv_data(pool_k).shape[1], _kv_data(pool_k).shape[2]
    mb = tables.shape[1]
    s_tot = mb * bs
    T = ids.shape[0]
    wdt = params["embed"].dtype
    if sin is not None:
        sin_p = jnp.take(sin, pos, axis=0, mode="clip")[None]   # [1, T, D]
        cos_p = jnp.take(cos, pos, axis=0, mode="clip")[None]
    # pool write coordinates: token t appends at its logical position
    # through its OWN slot's table; dead packed rows (seg == R) and
    # positions past the logical capacity drop — never clamp into a
    # block another sequence owns
    live_tok = seg < R
    seg_c = jnp.minimum(seg, R - 1)
    bi = jnp.minimum(pos // bs, mb - 1)
    phys0 = jnp.take_along_axis(jnp.take(tables, seg_c, axis=0),
                                bi[:, None], axis=1)[:, 0]
    phys0 = jnp.where(live_tok & (pos < s_tot), phys0, nb)
    prow0 = pos % bs

    def kv_attend(pk, pv, layer):
        at = (layer, phys0, prow0)

        def attend(q, k, v):
            # write the packed K/V through the tables (quantize-on-write
            # on an int8 pool), then attend over each span causally at
            # its row's kv length — THE one dequant site: the ragged
            # kernel (or its oracle) dequantizes right after the
            # table-indirect fetch, and every consumer of this forward
            # (unified step, multi-tick tick 0, speculative verify)
            # rides it
            npk = _kv_write(pk, at, k[0])
            npv = _kv_write(pv, at, v[0])
            kd, vd, ksc, vsc = _kv_attn_args(npk, npv)
            ragged = (ragged_paged_attention_pallas
                      if decode_attn == "pallas"
                      else ragged_attention_reference)
            attn = ragged(q[0], kd, vd, tables, qstart, qlen, kvlen,
                          k_scale=ksc, v_scale=vsc, layer=layer)
            return attn, (npk, npv)

        return attend

    if swa is not None:
        x = jnp.take(params["embed"], ids[None], axis=0)        # [1, T, H]
        return _swa_span_forward(
            params, x, pool_k, pool_v, state, kv_attend, tables, seg=seg,
            pos=pos, qstart=qstart, qlen=qlen, kvlen=kvlen, nh=nh, nkv=nkv,
            hd=hd, eps=eps, swa=swa, theta=theta,
            rotary=None if sin is None else sin.shape[-1],
            decode_attn=decode_attn, moe=moe, return_picks=return_picks)
    if ssd is not None:
        x = jnp.take(params["embed"], ids[None], axis=0)        # [1, T, H]
        return _mixer_span_forward(
            params, x, pool_k, pool_v, state, kv_attend, seg=seg, pos=pos,
            qstart=qstart, qlen=qlen, kvlen=kvlen, nh=nh, nkv=nkv, hd=hd,
            eps=eps, ssd=ssd, moe=moe, return_picks=return_picks)
    if "mamba_layers" in params:
        x = jnp.take(params["embed"], ids[None], axis=0)        # [1, T, H]
        return _jamba_span_forward(
            params, x, pool_k, pool_v, state, kv_attend, seg=seg, pos=pos,
            qstart=qstart, qlen=qlen, kvlen=kvlen, nh=nh, nkv=nkv, hd=hd,
            eps=eps, ssm=ssm)
    if ssm is not None:
        x = jnp.take(params["embed"], ids[None], axis=0)        # [1, T, H]
        return _sambay_span_forward(
            params, x, pool_k, pool_v, state, kv_attend, tables, seg=seg,
            pos=pos, qstart=qstart, qlen=qlen, kvlen=kvlen, nh=nh, nkv=nkv,
            hd=hd, eps=eps, ssm=ssm, decode_attn=decode_attn)
    if state is not None:
        x = jnp.take(params["embed"], ids[None], axis=0)        # [1, T, H]
        return _hybrid_span_forward(
            params, x, pool_k, pool_v, state, kv_attend, seg=seg, pos=pos,
            qstart=qstart, qlen=qlen, kvlen=kvlen, nh=nh, nkv=nkv, hd=hd,
            eps=eps, gdn=gdn, moe=moe, return_picks=return_picks,
            # (the tables are as wide as what of a head is rotated)
            rotary=None if sin is None else sin.shape[-1],
            rotate=None if sin is None
            else lambda t: _apply_rope_grid(t, sin_p, cos_p))

    def scan_stack(carry, first, names, stack, experts, indexer=None):
        def layer0(carry, lp):
            h, pk, pv = carry[:3]
            lw = dict(zip(names, _dq_layer(lp, wdt, a8)))
            layer = first + lw["layer"]     # this layer's place in the pool
            at = (layer, phys0, prow0)
            attend = kv_attend(pk, pv, layer)

            def attend_latent(q_nope, q_pe, c_kv, k_pe, w_kvb):
                # one row a token into the latent pool, then the ABSORBED
                # form over it for every span: W_UK folded into the query,
                # W_UV into the output, all heads reading the same fetched
                # rows. The jnp path is the oracle in the expanded form.
                npk = _kv_write(pk, at, latent_rows(c_kv, k_pe)[0])
                span = dict(scale=mla.scale, layer=layer)
                if decode_attn != "pallas":
                    with jax.named_scope("mla_attend"):
                        attn = mla_ragged_attention_reference(
                            q_nope[0], q_pe[0], w_kvb, npk, tables, qstart,
                            qlen, kvlen, **span)
                    return attn[None], (npk, pv)
                w = w_kvb.reshape(mla.rank, nh, mla.nope + mla.v)
                with jax.named_scope("mla_proj"):
                    q_lat = jnp.einsum("thd,rhd->thr", q_nope[0],
                                       w[..., :mla.nope])
                with jax.named_scope("mla_attend"):
                    o_lat = mla_ragged_attention_pallas(
                        q_lat, q_pe[0], npk, tables, qstart, qlen, kvlen,
                        **span)
                with jax.named_scope("mla_proj"):
                    attn = jnp.einsum("thr,rhd->thd", o_lat,
                                      w[..., mla.nope:])
                return attn[None], (npk, pv)

            def rope(x):
                return _apply_rope_grid(x, sin_p, cos_p)

            def attend_selected(q_nope, q_pe, c_kv, k_pe, w_kvb, c_q, hn):
                # attention over a selection (``kernels.dsa``): the latent
                # row as above; a layer with an indexer also writes the
                # step's index keys (a layer without one drops the write),
                # scores and selects under ``lax.cond``; every layer then
                # attends over the set the carry holds
                sel = carry[3]
                npk = _kv_write(pk, at, latent_rows(c_kv, k_pe)[0])
                npv, has = pv, lw["idx_layer"] >= 0
                key_layer = jnp.maximum(lw["idx_layer"], 0)
                if indexer is not None:
                    small = _indexer_at(indexer, lw["idx_slot"],
                                        _INDEXER_KEYS[1:])
                    key = index_key(hn, small, dsa=dsa, rope=rope)
                    npv = _kv_write(
                        pv, (key_layer, jnp.where(has, phys0, nb), prow0),
                        key[0][:, None, :])

                    def select(_):
                        iw = dict(small, idx_wq_b=_indexer_at(
                            indexer, lw["idx_slot"], ("idx_wq_b",)
                        )["idx_wq_b"])
                        q_i, w_i = index_query(c_q, hn, iw, dsa=dsa,
                                               rope=rope)
                        with jax.named_scope("dsa_index_score"):
                            scores = (
                                dsa_index_scores_pallas
                                if decode_attn == "pallas"
                                else dsa_index_scores_reference)(
                                q_i[0], w_i[0], npv, tables, qstart, qlen,
                                kvlen, layer=key_layer)
                        with jax.named_scope("dsa_select"):
                            return _selection(dsa_select(scores, dsa.topk))

                    sel = jax.lax.cond(has, select, lambda _: sel, None)
                span = dict(scale=mla.scale, layer=layer)
                if decode_attn != "pallas":
                    with jax.named_scope("dsa_attend"):
                        attn = dsa_attention_reference(
                            q_nope[0], q_pe[0], w_kvb, npk, tables, qstart,
                            qlen, kvlen, sel, k=dsa.topk, **span)
                    return attn[None], (npk, npv, sel)
                w = w_kvb.reshape(mla.rank, nh, mla.nope + mla.v)
                with jax.named_scope("mla_proj"):
                    q_lat = jnp.einsum("thd,rhd->thr", q_nope[0],
                                       w[..., :mla.nope])
                with jax.named_scope("dsa_attend"):
                    o_lat = dsa_attention_pallas(
                        q_lat, q_pe[0], npk, tables, qstart, qlen, kvlen,
                        sel, **span)
                with jax.named_scope("mla_proj"):
                    attn = jnp.einsum("thr,rhd->thd", o_lat,
                                      w[..., mla.nope:])
                return attn[None], (npk, npv, sel)

            h, kv, stats = _decoder_layer(
                h, lw, nh=nh, nkv=nkv, hd=hd, eps=eps, rope=rope,
                attend=attend if mla is None else attend_latent
                if dsa is None else attend_selected,
                live=live_tok[None], moe=moe, experts=experts,
                tp_reduce=tp_reduce, mla=mla,
                return_picks=return_picks and experts is not None)
            return (h,) + kv, stats

        return jax.lax.scan(layer0, carry, stack)

    # the pool rides the scans as CARRY, whole: a layer appends its rows and
    # reads its blocks at [layer, ...] of the one buffer, so no op of a
    # scan slices a layer out of the pool or stacks one back into it
    x = jnp.take(params["embed"], ids[None], axis=0)        # [1, T, H]
    carry, stats = (x, pool_k, pool_v), None
    if dsa is not None:
        # the carry holds a selection in the form its reader takes: the
        # oracle a mask, the kernel the walk's own tiles (walk and mask, for
        # decode rows and chunks alike: gathering 16 rows' 2,048 selected
        # rows took twice the masked walk's time on the v5e, PERF.md PR 43)
        def _selection(mask):
            if decode_attn != "pallas":
                return mask
            return selection_bias(mask, nh, table_entries=mb, block_size=bs)

        carry += (_selection(jnp.zeros((T, s_tot), bool)),)
    for (first, names, stack, experts), indexer in zip(
            _layer_stacks(params), _indexers(params)):
        carry, st = scan_stack(carry, first, names, stack, experts, indexer)
        stats = st if experts is not None else stats
    return carry[:3] + (stats,)


@jax.named_scope("ragged_step")
def _ragged_step_impl(params, pool_k, pool_v, tables, ids, seg, pos,
                      qstart, qlen, kvlen, dec_mask, keys, temps, top_ks,
                      prev_toks, take, chunk_keys, adopt, state=None,
                      *, n_steps, nh, nkv, hd, eps, theta, tied,
                      decode_attn, tp_reduce=None, a8=False, moe=None,
                      mla=None, return_picks=False, gdn=None, ssm=None,
                      dsa=None, ssd=None, rotary=None, swa=None):
    """THE unified serving step: one device call that advances every
    slot's span — decode rows (span 1) and prefill chunks (span n) —
    through the same block tables (README "Unified ragged attention").

    Packed layout (host-built, all runtime arrays — shapes depend only
    on ``(num_slots, T)``, the packed size ``T`` being whatever the
    caller's buffer holds: the engine packs a step with no chunk at
    ``num_slots`` rows rounded up to 8 and a step with one at its token
    budget, one body specialised on the two shapes):

    ids:     [T] int32 — packed input token ids (decode rows carry the
             slot's last sampled token; chunk rows carry their prompt
             slice; dead packed rows carry 0)
    seg:     [T] int32 — owning slot per packed token (``num_slots`` =
             dead row: every write drops)
    pos:     [T] int32 — logical position per packed token
             (``kvlen[r] - qlen[r] + i`` for span token i)
    qstart/qlen/kvlen: [R] span metadata (``serving/decode`` twin of the
             kernel's row metadata; ``qlen == 0`` = idle slot)
    dec_mask: [R] int32 — 1 where the span is a RUNNING decode row
             (spans that may keep ticking in the fused tail and whose
             appends are real), 0 for chunk rows / idle slots (their
             tail-tick writes are forced to drop)
    keys:    [R, 2] the engine's per-slot key state, the previous step's
             ``keys'`` handed back as it is (a device array: no fetch)
    temps/top_ks: [R] per-slot sampling knobs, live on a chunk row only
             on its FINAL chunk
    prev_toks/take: [R] — the dispatch-ahead inputs. ``prev_toks`` is
             the previous step's ``tok_fin``, still on the device when
             this step is dispatched before the host has read it;
             ``take[r] = 1`` makes slot r's decode row read its input
             token from ``prev_toks[r]`` instead of ``ids[qstart[r]]``.
             A step with nothing in flight passes zeros for both: one
             signature, one program.
    chunk_keys/adopt: [R, 2] / [R] — a chunk row (``qlen > 0`` and
             ``dec_mask == 0``) samples with the sequence's own resume
             key ``chunk_keys[r]`` (host-known), merged over ``keys``
             in-program; ``adopt[r] = 1`` (decode rows, a fresh
             sequence's final chunk) stores the row's advanced key in
             ``keys'``, 0 keeps the merged input key (a restored
             sequence's final chunk resumes ITS walk; idle slots keep
             theirs).

    Tick 0 runs the packed buffer through one forward pass — K/V
    scattered through the tables at per-token positions, attention via
    the ragged paged kernel (or its jnp oracle) — then samples one
    token per slot from its span's LAST position. Ticks ``1..n_steps-1``
    are fused single-token decode ticks (``_fused_decode_tick``; the
    engine only fuses when no prefill work is pending, so the tail
    ticks are pure decode; ``dec_mask`` keeps a stray non-decode row's
    appends out of the pool regardless).

    Returns ``(pool_k', pool_v', toks [n_steps, R], tok_fin [R],
    keys' [R, 2])``: ``toks[0]`` is tick 0's per-slot sample (a final
    chunk row's token 0 — the same split walk as a one-shot prefill, so
    streams stay byte-identical); ``tok_fin`` is the last tick's sample
    (the next step's ``prev_toks``) and ``keys'`` the next step's
    ``keys``, both handed on without a host round trip. A hybrid model
    passes ``state``, its linear layers' store (``_hybrid_span_forward``;
    donated like the pool), and gets it back as the last value (after the
    routing summary, where its FFNs are routed: ``_mixer_span_forward``,
    ``_hybrid_span_forward``).
    """
    # dispatch-ahead: a decode row dispatched before the previous step's
    # tokens reached the host takes its input token here, on the device
    T = ids.shape[0]
    ids = ids.at[jnp.where(take > 0, qstart, T)].set(prev_toks,
                                                     mode="drop")
    keys_in = jnp.where(((qlen > 0) & (dec_mask == 0))[:, None],
                        chunk_keys, keys)
    s_tot = tables.shape[1] * _kv_data(pool_k).shape[2]
    # (a model may rotate part of a head: the tables are that wide)
    sin, cos = (None, None) if theta is None else _rope_tables_for(
        s_tot, rotary or hd, theta, mla)
    # the fused tail's own layer body scans the GQA entries alone (a model
    # it was not taught never runs with n_steps > 1: the engine raises)
    stack = (tuple(params[k] for k in _STACK_KEYS) if n_steps > 1 else None)
    head = _dq_head(params, tied, params["embed"].dtype, a8)

    # ----------------------------------- tick 0 (shared packed forward)
    if state is not None:
        x, pk, pv, state, *moe_stats = _packed_span_forward(
            params, pool_k, pool_v, tables, ids, seg, pos, qstart, qlen,
            kvlen, sin, cos, nh=nh, nkv=nkv, hd=hd, eps=eps,
            decode_attn=decode_attn, state=state, gdn=gdn, ssm=ssm, ssd=ssd,
            moe=moe, return_picks=return_picks,
            **({} if swa is None else {"swa": swa, "theta": theta}))
        if ssm is not None:     # x came back one row a slot
            tok0, keys_t0 = _rows_sample(params, head, x[0], keys_in, temps,
                                         top_ks, eps)
        else:
            tok0, keys_t0 = _span_last_sample(params, head, x, qstart, qlen,
                                              keys_in, temps, top_ks, eps)
        keys_out = jnp.where((adopt > 0)[:, None], keys_t0, keys_in)
        return (pk, pv, tok0[None], tok0, keys_out) + _moe_outputs(
            moe_stats[0] if moe_stats else None) + (state,)
    x, pk, pv, moe_stats = _packed_span_forward(
        params, pool_k, pool_v, tables, ids, seg, pos, qstart, qlen,
        kvlen, sin, cos, nh=nh, nkv=nkv, hd=hd, eps=eps,
        decode_attn=decode_attn, tp_reduce=tp_reduce, a8=a8, moe=moe,
        mla=mla, return_picks=return_picks, dsa=dsa)
    tok0, keys_t0 = _span_last_sample(params, head, x, qstart, qlen,
                                      keys_in, temps, top_ks, eps)

    # ------------------------------------------- fused tail (pure decode)
    lens0 = jnp.where(dec_mask > 0, kvlen, 0)

    def one_step(carry, _):
        tok, pk_all, pv_all, lens, kys = carry
        # non-decode rows (idle slots, a chunk row that just finished)
        # ride dec_mask=0: their appends drop inside the shared tick —
        # their next write belongs to the next step's program
        nxt, npk, npv, nkeys = _fused_decode_tick(
            params, stack, head, tables, sin, cos, tok, pk_all, pv_all,
            lens, kys, dec_mask, temps, top_ks, nh=nh, nkv=nkv, hd=hd,
            eps=eps, decode_attn=decode_attn, tp_reduce=tp_reduce, a8=a8)
        return (nxt, npk, npv, lens + dec_mask, nkeys), nxt

    if n_steps > 1:
        carry0 = (tok0, pk, pv, lens0, keys_t0)
        (_, pk, pv, _, keys_fin), toks_rest = jax.lax.scan(
            one_step, carry0, None, length=n_steps - 1)
        toks = jnp.concatenate([tok0[None], toks_rest], axis=0)
    else:
        toks, keys_fin = tok0[None], keys_t0
    keys_out = jnp.where((adopt > 0)[:, None], keys_fin, keys_in)
    return (pk, pv, toks, toks[-1], keys_out) + _moe_outputs(moe_stats)


def build_ragged_step_fn(*, n_steps, nh, nkv, hd, eps, theta, tied,
                         decode_attn, donate=None, tp=1,
                         collective_dtype="fp", kv_quant=False,
                         wq8=False, a8=False, collective_overlap=False,
                         moe=None, mla=None, return_picks=False, gdn=None,
                         ssm=None, dsa=None, ssd=None, rotary=None,
                         swa=None):
    """One jitted unified serving step (``_ragged_step_impl``): shapes
    depend only on ``(num_slots, packed size)`` plus the fused
    ``n_steps`` — one compilation per (packed size, ``n_steps``) serves
    every span mix. The engine calls it at two packed sizes (the slots'
    rows alone for a step with no prefill chunk, the token budget for a
    step with one) and ``jax.jit`` specialises the one body on each;
    everything a step hands the next is ``[num_slots, ...]`` or
    pool-shaped, so the two executables follow each other freely.
    ``tp > 1`` wraps the WHOLE step in shard_map over the heads-sharded
    mesh (README "Tensor-parallel serving"): attention and the QKV/MLP
    projections run fully sharded, the paged pool partitions per shard
    on its head axis, and the only cross-chip traffic is the per-layer
    all-reduce pair (``collective_dtype`` picks fp vs EQuARX-style
    int8). The compile-once contract is unchanged — the TP degree joins
    the engine's jit key, not the trace's shapes."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    if int(tp) > 1:
        tp = int(tp)
        _tp_validate(nh, nkv, tp)
        impl = functools.partial(
            _ragged_step_impl, n_steps=n_steps, nh=nh // tp,
            nkv=nkv // tp, hd=hd, eps=eps, theta=theta, tied=tied,
            decode_attn=decode_attn,
            tp_reduce=_tp_allreduce(collective_dtype, tp,
                                    overlap=collective_overlap),
            a8=a8)
        rep = PartitionSpec()
        pool = _pool_pspec(kv_quant)
        return jax.jit(_tp_shard(
            impl, tp,
            in_specs=(_params_pspec(wq8), pool, pool) + (rep,) * 15,
            out_specs=(pool, pool, rep, rep, rep)),
            donate_argnums=(1, 2) if donate else ())
    return jax.jit(
        functools.partial(
            _ragged_step_impl, n_steps=n_steps, nh=nh, nkv=nkv, hd=hd,
            eps=eps, theta=theta, tied=tied, decode_attn=decode_attn,
            a8=a8, moe=moe, mla=mla, return_picks=return_picks,
            **({} if gdn is None else {"gdn": gdn}),
            **({} if ssm is None else {"ssm": ssm}),
            **({} if dsa is None else {"dsa": dsa}),
            **({} if ssd is None else {"ssd": ssd}),
            **({} if rotary is None else {"rotary": rotary}),
            **({} if swa is None else {"swa": swa})),
        # argument 18: the stores by slot of a model with recurrent or
        # window layers (absent otherwise)
        donate_argnums=((1, 2) + ((18,) if (gdn, ssm, ssd, swa)
                                  != (None,) * 4 else ()))
        if donate else ())


# ------------------------------------------------------- multi-tick decode
@jax.named_scope("multitick_step")
def _multitick_step_impl(params, pool_k, pool_v, tables, ids, seg, pos,
                         qstart, qlen, kvlen, dec_mask, keys, temps,
                         top_ks, eos_ids, budgets, n_ticks, *, max_ticks,
                         nh, nkv, hd, eps, theta, tied, decode_attn,
                         tp_reduce=None, a8=False):
    """THE multi-tick serving step (README "Multi-tick decode"): the
    unified ragged step with the host driven out of the per-token loop.
    Tick 0 is ``_ragged_step_impl``'s packed forward verbatim (decode
    rows span 1, prefill chunks span n, K/V written through the block
    tables, one sample per span); the fused tail is the same decode
    scan UPGRADED with

    - a **runtime tick count**: ``n_ticks`` (host-chosen each step,
      1..max_ticks) bounds a ``lax.while_loop`` instead of a static
      ``lax.scan`` length, so ONE compilation serves every tick count —
      mixed-traffic steps pass 1 and pay exactly the unified step's
      work, decode-heavy steps pass ``decode_ticks``;
    - an **on-device alive mask**: per-slot EOS hits (``eos_ids``, -1 =
      no EOS configured) and remaining-budget counters (``budgets`` =
      ``max_new_tokens - len(tokens)`` at step start) retire a row
      inside the loop — a finished row's appends drop exactly like a
      ``dec_mask`` dead row, its length stops advancing, and the loop
      EXITS EARLY once every row is dead (a program can return with
      ticks to spare);

    so the host syncs once per ``n_ticks`` tokens instead of once per
    token, and accepts the whole block in one ``host-accept``.

    The alive update replays the host's ``_maybe_finish`` rule
    exactly — after emitting token index ``t`` a row stays alive iff
    the token is not its EOS and ``t + 1 < budget`` — so the device's
    append cut equals the host's trim cut and the donation invariant
    (the last emitted token's KV is never in the cache) is preserved
    tick-for-tick. Appends per row == tokens the host accepts.

    Per-row sampling walks are positionally identical to sequential
    decode (split once per tick per row, all rows, dead or alive), so
    streams are byte-identical to ``n_ticks = 1`` — greedy AND
    seeded-sampled; the host adopts ``keys_walk[m - 1]`` for a row
    that emitted ``m`` tokens, the same contract as the speculative
    verify's key walk.

    Returns ``(pool_k', pool_v', toks [max_ticks, R],
    keys_walk [max_ticks, R, 2], ticks_run)``: row 0 is tick 0's
    sample + advanced key (what a final chunk row adopts — the same
    split walk as a one-shot prefill); rows past ``ticks_run`` are
    zeros the host never reads.
    """
    R = tables.shape[0]
    s_tot = tables.shape[1] * _kv_data(pool_k).shape[2]
    sin, cos = _rope_tables(s_tot, hd, theta)
    stack = tuple(params[k] for k in _STACK_KEYS)
    head = _dq_head(params, tied, params["embed"].dtype, a8)

    # ----------------------------------- tick 0 (shared packed forward)
    x, pk, pv, _ = _packed_span_forward(
        params, pool_k, pool_v, tables, ids, seg, pos, qstart, qlen,
        kvlen, sin, cos, nh=nh, nkv=nkv, hd=hd, eps=eps,
        decode_attn=decode_attn, tp_reduce=tp_reduce, a8=a8)
    tok0, keys_t0 = _span_last_sample(params, head, x, qstart, qlen, keys,
                                      temps, top_ks, eps)

    # ------------------------------- fused tail (alive-masked, runtime n)
    lens0 = jnp.where(dec_mask > 0, kvlen, 0)
    # after tick 0 a decode row has emitted 1 token: it keeps ticking
    # iff that token is not its EOS and its budget allows a second
    alive0 = (dec_mask > 0) & (tok0 != eos_ids) & (budgets > 1)
    toks_buf = jnp.zeros((max_ticks, R), jnp.int32).at[0].set(tok0)
    keys_buf = jnp.zeros((max_ticks, R, 2),
                         jnp.uint32).at[0].set(keys_t0)

    def cond(state):
        t, alive = state[0], state[1]
        return jnp.logical_and(t < n_ticks, jnp.any(alive))

    def body(state):
        t, alive, tok, pk_all, pv_all, lens, kys, tb, kb = state
        # dead rows — idle slots, chunk rows, and rows the alive mask
        # retired (EOS hit / budget spent on an earlier tick) — ride
        # app_mask=0 through the shared tick: appends drop, length
        # frozen (a retired row's next write belongs to nobody)
        am = alive.astype(jnp.int32)
        nxt, npk, npv, nkeys = _fused_decode_tick(
            params, stack, head, tables, sin, cos, tok, pk_all, pv_all,
            lens, kys, am, temps, top_ks, nh=nh, nkv=nkv, hd=hd,
            eps=eps, decode_attn=decode_attn, tp_reduce=tp_reduce, a8=a8)
        tb = tb.at[t].set(nxt)
        kb = kb.at[t].set(nkeys)
        # the host's _maybe_finish rule, in-program: after emitting
        # token index t a row stays alive iff the token is not its EOS
        # and t + 1 more tokens fit its budget
        alive = alive & (nxt != eos_ids) & (t + 1 < budgets)
        return (t + 1, alive, nxt, npk, npv, lens + am, nkeys, tb, kb)

    state0 = (jnp.int32(1), alive0, tok0, pk, pv, lens0, keys_t0,
              toks_buf, keys_buf)
    (ticks_run, _, _, pk, pv, _, _, toks_buf, keys_buf) = \
        jax.lax.while_loop(cond, body, state0)
    return pk, pv, toks_buf, keys_buf, ticks_run


def build_multitick_step_fn(*, max_ticks, nh, nkv, hd, eps, theta, tied,
                            decode_attn, donate=None, tp=1,
                            collective_dtype="fp", kv_quant=False,
                            wq8=False, a8=False, collective_overlap=False):
    """One jitted multi-tick serving step (``_multitick_step_impl``):
    shapes depend only on ``(num_slots, token_budget, max_ticks)`` —
    the tick count actually run is a RUNTIME argument, so one
    compilation serves every span mix AND every adaptive tick count
    from 1 to ``max_ticks``. The compile-once contract covers the
    multi-tick geometry with a single trace. ``tp > 1`` shards it over
    heads exactly like the unified step it extends."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    if int(tp) > 1:
        tp = int(tp)
        _tp_validate(nh, nkv, tp)
        impl = functools.partial(
            _multitick_step_impl, max_ticks=int(max_ticks), nh=nh // tp,
            nkv=nkv // tp, hd=hd, eps=eps, theta=theta, tied=tied,
            decode_attn=decode_attn,
            tp_reduce=_tp_allreduce(collective_dtype, tp,
                                    overlap=collective_overlap),
            a8=a8)
        rep = PartitionSpec()
        pool = _pool_pspec(kv_quant)
        return jax.jit(_tp_shard(
            impl, tp,
            in_specs=(_params_pspec(wq8), pool, pool) + (rep,) * 14,
            out_specs=(pool, pool, rep, rep, rep)),
            donate_argnums=(1, 2) if donate else ())
    return jax.jit(
        functools.partial(
            _multitick_step_impl, max_ticks=int(max_ticks), nh=nh,
            nkv=nkv, hd=hd, eps=eps, theta=theta, tied=tied,
            decode_attn=decode_attn, a8=a8),
        donate_argnums=(1, 2) if donate else ())


# ------------------------------------------------- speculative verify step
@jax.named_scope("spec_verify")
def _spec_verify_impl(params, pool_k, pool_v, tables, ids, seg, pos,
                      qstart, qlen, kvlen, sample_start, keys, temps,
                      top_ks, *, spec_len, nh, nkv, hd, eps, theta, tied,
                      decode_attn, tp_reduce=None, a8=False):
    """THE speculative serving step (README "Speculative decoding"):
    one device call that scores every slot's draft-extended span — a
    verify row packs ``[last_token, d_1 .. d_k]`` at positions
    ``len .. len+k`` and a prefill chunk packs its prompt slice, both
    writing K/V through the block tables exactly like
    ``_ragged_step_impl``'s tick 0 (the forward IS that tick's shared
    assembly, ``_packed_span_forward``) — then samples ``spec_len``
    consecutive positions per row under the standard split-per-token
    PRNG walk, so the host can accept the longest draft prefix whose
    tokens the target model reproduces and adopt the key exactly where
    sequential decode would have left it.

    Packed layout (host-built runtime arrays; shapes depend only on
    ``(num_slots, spec token budget, spec_len)``):

    ids/seg/pos:   [T] — as in ``_ragged_step_impl`` (dead rows drop)
    qstart/qlen/kvlen: [R] span metadata (``qlen == 0`` = idle slot;
                   ``kvlen`` counts KV valid AFTER this step's writes)
    sample_start:  [R] — the packed row the sampling walk starts at:
                   a VERIFY row samples from its span START (position
                   ``j`` scores the token after input ``j``), a chunk
                   row from its span END (only its final-position
                   sample — token 0 — is ever adopted); reads clamp
                   inside the span, so short spans repeat their last
                   position and the host ignores the surplus.
    keys/temps/top_ks: [R] per-slot sampling state (chunk rows carry
                   the sequence's resume key, live only on their final
                   chunk — exactly like ``_suffix_call`` rows).

    Walk step ``j``: split every row's key, sample position ``j``'s
    logits with the split — byte-identical to ``spec_len`` sequential
    decode ticks for any prefix the drafts match, which is the whole
    acceptance argument: an accepted token was sampled with the same
    key and the same logits sequential decode would have used, so
    streams with speculation ON equal streams with it OFF, greedy AND
    seeded-sampled. Rejected positions' samples/keys are garbage the
    host never adopts (and their K/V rows are truncated away).

    Returns ``(pool_k', pool_v', toks [spec_len, R],
    keys_walk [spec_len, R, 2])`` — ``keys_walk[j]`` is each row's key
    after ``j + 1`` splits; a row that emits ``m`` tokens adopts
    ``keys_walk[m - 1]``.
    """
    T = ids.shape[0]
    R = tables.shape[0]
    s_tot = tables.shape[1] * _kv_data(pool_k).shape[2]
    sin, cos = _rope_tables(s_tot, hd, theta)
    head = _dq_head(params, tied, params["embed"].dtype, a8)

    x, pk, pv, _ = _packed_span_forward(
        params, pool_k, pool_v, tables, ids, seg, pos, qstart, qlen,
        kvlen, sin, cos, nh=nh, nkv=nkv, hd=hd, eps=eps,
        decode_attn=decode_attn, tp_reduce=tp_reduce, a8=a8)
    # per-row sample positions: spec_len consecutive packed rows from
    # sample_start, clamped inside the row's span (idle rows clamp to
    # row 0 — garbage the host never reads)
    span_end = jnp.clip(qstart + jnp.maximum(qlen, 1) - 1, 0, T - 1)
    j_idx = jnp.arange(spec_len, dtype=jnp.int32)
    idx = jnp.clip(sample_start[:, None] + j_idx[None, :],
                   qstart[:, None], span_end[:, None])       # [R, S]
    hsel = jnp.take(x[0], idx.reshape(-1), axis=0)           # [R*S, H]
    last_h = _rms(hsel, params["final_norm"], eps)
    logits = _head_logits(last_h, head)
    logits = logits.reshape(R, spec_len, -1)

    def walk(kys, lg_j):
        both = jax.vmap(jax.random.split)(kys)               # [R, 2, 2]
        tok = sample_rows(lg_j, both[:, 1], temps, top_ks)
        return both[:, 0], (tok, both[:, 0])

    _, (toks, keys_walk) = jax.lax.scan(
        walk, keys, jnp.moveaxis(logits, 1, 0))
    return pk, pv, toks, keys_walk


def build_spec_verify_fn(*, spec_len, nh, nkv, hd, eps, theta, tied,
                         decode_attn, donate=None, tp=1,
                         collective_dtype="fp", kv_quant=False,
                         wq8=False, a8=False, collective_overlap=False):
    """One jitted speculative verify step (``_spec_verify_impl``):
    shapes depend only on ``(num_slots, spec token budget, spec_len)``
    — one compilation serves every draft/acceptance/chunk mix, the
    same compile-once contract as the programs it replaces. ``tp > 1``
    shards it over heads exactly like the unified step whose tick-0
    assembly it shares."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    if int(tp) > 1:
        tp = int(tp)
        _tp_validate(nh, nkv, tp)
        impl = functools.partial(
            _spec_verify_impl, spec_len=spec_len, nh=nh // tp,
            nkv=nkv // tp, hd=hd, eps=eps, theta=theta, tied=tied,
            decode_attn=decode_attn,
            tp_reduce=_tp_allreduce(collective_dtype, tp,
                                    overlap=collective_overlap),
            a8=a8)
        rep = PartitionSpec()
        pool = _pool_pspec(kv_quant)
        return jax.jit(_tp_shard(
            impl, tp,
            in_specs=(_params_pspec(wq8), pool, pool) + (rep,) * 11,
            out_specs=(pool, pool, rep, rep)),
            donate_argnums=(1, 2) if donate else ())
    return jax.jit(
        functools.partial(
            _spec_verify_impl, spec_len=spec_len, nh=nh, nkv=nkv, hd=hd,
            eps=eps, theta=theta, tied=tied, decode_attn=decode_attn,
            a8=a8),
        donate_argnums=(1, 2) if donate else ())
