"""Flash attention (reference: ``paddle/phi/kernels/gpu/flash_attn_kernel.cu``
wrapping the cutlass flash-attention lib; varlen variant
``FlashAttnUnpadded``).

TPU: memory-efficient attention as a Pallas kernel (tiled online-softmax,
one pass over KV in VMEM-sized blocks). The jnp reference path is used off
TPU and for small sequences where XLA's fusion already saturates the MXU.
Layout follows paddle: [batch, seq, num_heads, head_dim].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..core import random as random_mod
from ..core.tensor import Tensor
from ..ops._op import tensor_op
from ..parallel import mesh as mesh_mod
from ..utils.flags import get_flag


def _use_pallas(seq_len):
    """Pallas flash on a TPU for sequences long enough to tile; the jnp
    path on the CPU backend (tests) and for short sequences, where XLA's
    fusion already saturates the MXU. Any other backend is an error."""
    if not get_flag("FLAGS_use_pallas_kernels", True):
        return False
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"attention dispatch knows the 'tpu' and 'cpu' backends; the "
            f"default JAX backend is {backend!r}")
    return backend == "tpu" and seq_len >= 512


def shard_over_mesh(fn, q, k, v, *replicated, head_axis):
    """Run ``fn(q, k, v, *replicated)`` — a Mosaic kernel call — under the
    global hybrid mesh.

    GSPMD cannot partition a Mosaic custom call (lowering a ``pallas_call``
    inside a jit over more than one device raises "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    under a mesh the call runs in a ``shard_map`` that is manual over every
    mesh axis not already manual: the batch (dim 0) split over the data
    axes, the heads (dim ``head_axis``) over ``mp``, the way the model's
    annotations already lay the activations out; ``replicated`` operands
    (rope tables) go to every shard whole. Attention is independent per
    (batch row, head), so no shard needs a collective. A dimension the
    axes do not divide stays whole on every shard. K/V with fewer heads
    than Q (GQA) are repeated first, as the kernel wrapper would anyway.
    Without a mesh, or on one device, this is a plain call."""
    mesh = mesh_mod.get_mesh()
    if mesh is None or mesh.size == 1:
        return fn(q, k, v, *replicated)
    ctx = jax.sharding.get_abstract_mesh()
    manual = set(getattr(ctx, "manual_axes", ()))
    names = set(mesh.axis_names) - manual
    if not names:       # already inside a fully manual region
        return fn(q, k, v, *replicated)
    H = q.shape[head_axis]
    if k.shape[head_axis] != H:
        rep = H // k.shape[head_axis]
        k = jnp.repeat(k, rep, axis=head_axis)
        v = jnp.repeat(v, rep, axis=head_axis)
    batch = tuple(a for a in ("dp", "sharding") if a in names)
    if q.shape[0] % math.prod(mesh.shape[a] for a in batch):
        batch = ()
    heads = "mp" if "mp" in names and H % mesh.shape["mp"] == 0 else None
    dims = [None] * q.ndim
    dims[0], dims[head_axis] = batch or None, heads
    spec = PartitionSpec(*dims)
    return jax.shard_map(
        fn, mesh=ctx if manual else mesh, axis_names=names,
        in_specs=(spec,) * 3 + (PartitionSpec(),) * len(replicated),
        out_specs=spec, check_vma=False)(q, k, v, *replicated)


def _pallas_attention(q, k, v, causal):
    from .pallas_flash import flash_attention_pallas
    return shard_over_mesh(
        functools.partial(flash_attention_pallas, causal=causal), q, k, v,
        head_axis=2)


def attention(q, k, v, causal=True):
    """Raw-array attention dispatcher for model internals: Pallas flash on
    TPU for long sequences, jnp reference otherwise."""
    B, S, H, D = q.shape
    # no seq-length divisibility guard: the kernels mask the padded tail
    # block explicitly, so any S is safe
    if _use_pallas(S) and D % 8 == 0:
        return _pallas_attention(q, k, v, causal)
    return _ref_attention(q, k, v, causal)


# --------------------------------------------------------------- jnp reference
def _ref_attention(q, k, v, causal, segment_ids=None):
    Bq, Sq, H, D = q.shape
    Sk = k.shape[1]
    Hk = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    if Hk != H:  # grouped-query attention: repeat kv heads
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool))[None, None]
    if segment_ids is not None:
        seg = (segment_ids[:, :, None] == segment_ids[:, None, :])[:, None]
        mask = seg if mask is None else (mask & seg)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


@tensor_op
def _flash_impl(q, k, v, causal):
    if _use_pallas(q.shape[1]):
        return _pallas_attention(q, k, v, causal)
    return _ref_attention(q, k, v, causal)


@tensor_op
def _flash_dropout_impl(q, k, v, causal, dropout, key):
    out = _ref_attention(q, k, v, causal)  # dropout path: reference only
    # NOTE: the reference applies dropout to attention probs; post-output
    # dropout is not equivalent, so recompute with probs dropout:
    return out


def flash_attention(query, key, value, causal=False, dropout=0.0,
                    training=True):
    if dropout and training:
        # fall back to the general sdpa (probs dropout needs the probs)
        from ..nn import functional as F
        return F.scaled_dot_product_attention(query, key, value,
                                              dropout_p=dropout,
                                              is_causal=causal,
                                              training=training)
    return _flash_impl(query, key, value, bool(causal))


@tensor_op
def _flash_varlen_impl(q, k, v, seg_q, causal):
    # q: [total_q, H, D] packed; add batch dim 1 and use segment mask
    out = _ref_attention(q[None], k[None], v[None], causal,
                         segment_ids=seg_q[None])
    return out[0]


def flash_attn_varlen(q, k, v, cu_seqlens_q, cu_seqlens_k, causal=False):
    """Packed/varlen attention via segment-id masking (static shapes — the
    TPU answer to FlashAttnUnpadded's ragged batching)."""
    import numpy as np
    cs = cu_seqlens_q.value if isinstance(cu_seqlens_q, Tensor) else cu_seqlens_q
    cs = np.asarray(cs)
    total = int(cs[-1])
    seg = np.zeros(total, np.int32)
    for i in range(len(cs) - 1):
        seg[cs[i]:cs[i + 1]] = i
    return _flash_varlen_impl(q, k, v, jnp.asarray(seg), bool(causal))
