"""LLaMA-2 family (reference: PaddleNLP ``llama/modeling.py`` running on the
reference's Fleet hybrid-parallel stack — config 3 of BASELINE.json, the
north-star model).

TPU-native design, not a port:

- **Scan-over-layers**: decoder weights are stacked with a leading layer dim
  and the layer loop is ``lax.scan`` — one compiled layer body, constant
  compile time in depth, and the idiomatic substrate for pipeline sharding
  (the layer dim carries the 'pp' axis; XLA moves each layer's weights to
  its stage).
- **Hybrid shardings**: qkv/gate/up are column-sharded over 'mp', o/down
  row-sharded, embedding+lm-head vocab-sharded ('mp'), activations
  batch-sharded over ('dp','sharding') and sequence-sharded over 'sep'
  (context parallelism), ZeRO via the 'sharding' axis in TrainStep.
- **Remat**: each layer body is ``jax.checkpoint``-ed (the reference's
  recompute_configs), trading FLOPs for HBM exactly where the 1F1B schedule
  would.
- **Flash attention**: routed through paddle_tpu.kernels (Pallas on TPU,
  jnp reference elsewhere); GQA (n_kv_heads < n_heads) supported.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..core.tensor import Tensor
from ..kernels.flash_attention import attention as _attention
from ..nn import functional as F
from ..ops._op import tensor_op
from ..parallel import mesh as mesh_mod
from ..parallel.fleet.mp import mark_sharding


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_recompute: bool = True
    recompute_policy: str = "full"  # "full" | "dots" (save matmul outputs)
    sequence_parallel: bool = False
    # >0 routes the decoder stack through parallel.pp.pipeline_spmd when the
    # mesh has pp>1: stage-resident weights + ppermute handoffs over M
    # microbatches (the real pipeline schedule, vs pp-sharding the scan's
    # layer dim). Batch size must be divisible by this.
    pipeline_microbatches: int = 0
    # >1 uses the interleaved (virtual-stage) schedule: each pp device
    # holds this many layer chunks and microbatches make that many ring
    # passes — cuts the pipeline bubble ~by this factor (reference
    # PipelineParallelWithInterleave). Microbatches must be <= pp degree
    # or a multiple of it (group injection).
    pipeline_virtual_stages: int = 1
    # "" | "ring" | "ulysses": context parallelism over the 'sep' mesh axis
    # (parallel.sp_attention). "ring" composes with the pipeline schedule
    # (the sep shard_map nests inside the manual 'pp' region via the
    # context AbstractMesh; training that combination needs the legacy
    # partitioner — see _llama_forward). "ulysses" cannot nest in the
    # pipeline: its all_to_all can't partition inside a manual region.
    context_parallel: str = ""
    # "bshd" ([B,S,H,D], paddle layout) | "bhsd" (head-major: the qkv
    # projections emit [B,H,S,D] directly and the o-projection consumes it,
    # so the flash kernel's head-fold needs no HBM transpose pass).
    attention_layout: str = "bshd"
    # >0: compute the shifted-CE loss in sequence chunks of this size under
    # jax.checkpoint, so only one [B, chunk, V] f32 logits block is ever
    # live (the reference's c_softmax_with_cross_entropy memory trick,
    # TPU-style). 0 = single fused [B,S,V] logsumexp.
    loss_chunk: int = 0
    # "pallas" routes generate()'s per-token attention through the ragged
    # single-query Pallas kernel (kernels/pallas_decode.py — GQA resolved
    # in-kernel, kv blocks past the current position skipped); "jnp" keeps
    # the masked-softmax-over-S_max path.
    decode_attention: str = "pallas"
    # apply rotary embedding INSIDE the flash kernels (prologue + dq/dk
    # adjoint — the reference's fused_rope_kernel.cu fusion): no rotated
    # q/k HBM round-trip. Takes effect on the bhsd layout's Pallas path.
    fuse_rope: bool = False
    # Pallas flash block sizes (bench sweep lever; 0 = kernel default)
    flash_block_q: int = 0
    flash_block_k: int = 0
    dtype: str = "float32"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def llama_7b(**kw):
    return LlamaConfig(**kw)


def llama_13b(**kw):
    return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                       num_hidden_layers=40, num_attention_heads=40,
                       num_key_value_heads=40, **kw)


def llama_tiny(**kw):
    """Test/dryrun config."""
    defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=4, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=128)
    defaults.update(kw)
    return LlamaConfig(**defaults)


def _ann(x, *spec):
    """Sharding-constraint annotation valid for the current global mesh."""
    mesh = mesh_mod.get_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def ok(s):
        if s is None:
            return None
        if isinstance(s, tuple):
            kept = tuple(n for n in s if n in names)
            return kept if kept else None
        return s if s in names else None

    clean = tuple(ok(s) for s in spec)
    try:
        from jax.sharding import NamedSharding
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*clean)))
    except (ValueError, TypeError):
        return x


def _rope_tables(seq_len, head_dim, theta):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.sin(emb), jnp.cos(emb)


_BUILDERS = {}


def build_once(config, build):
    """``jax.jit(build)`` of the first model with this configuration (its
    ``repr``: every field, the dtype among them), for every later one: a
    model's ``build(key)`` closes over shapes that follow from the
    configuration alone, and a new jit an instance compiled the same
    program again for every model a process made (2-5 s each at the test
    presets' sizes)."""
    return _BUILDERS.setdefault((type(config).__name__, repr(config)),
                                jax.jit(build))


def _apply_rope(x, sin, cos):
    # x: [B, S, H, D] neox-style
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos[None, :, None, :] + rotated * sin[None, :, None, :]).astype(x.dtype)


def _apply_rope_bhsd(x, sin, cos):
    # x: [B, H, S, D]
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos[None, None, :, :] + rotated * sin[None, None, :, :]).astype(x.dtype)


def _attention_bhsd(q, k, v, nh, rope=None, block_q=0, block_k=0):
    """[B, H, S, D] attention: Pallas flash on TPU, jnp reference elsewhere.

    ``rope=(sin, cos)`` means q/k arrive UN-rotated and rotation happens
    inside the Pallas kernels (or is applied here on the fallback path)."""
    B, Hq, S, D = q.shape
    Hk = k.shape[1]
    if Hk != Hq:
        rep = Hq // Hk
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    from ..kernels.flash_attention import _use_pallas, shard_over_mesh
    if _use_pallas(S) and S % 128 == 0 and D % 8 == 0:
        from ..kernels.pallas_flash import flash_attention_bhsd
        kw = {}
        if block_q:
            kw["block_q"] = block_q
        if block_k:
            kw["block_k"] = block_k

        def flash(q, k, v, *rope):      # local [b, h, S, D] shards
            b, h = q.shape[:2]
            o = flash_attention_bhsd(q.reshape(b * h, S, D),
                                     k.reshape(b * h, S, D),
                                     v.reshape(b * h, S, D), causal=True,
                                     rope=rope or None, **kw)
            return o.reshape(b, h, S, D)

        return shard_over_mesh(flash, q, k, v, *(rope or ()), head_axis=1)
    if rope is not None:  # fallback path rotates explicitly
        sin, cos = rope
        q = _apply_rope_bhsd(q, sin, cos)
        k = _apply_rope_bhsd(k, sin, cos)
    import math as _m
    scale = 1.0 / _m.sqrt(D)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _qkv_bshd(hn, lwq, lwk, lwv, nh, nkv, hd):
    B, S = hn.shape[0], hn.shape[1]
    q = jnp.einsum("bsh,hd->bsd", hn, lwq).reshape(B, S, nh, hd)
    k = jnp.einsum("bsh,hd->bsd", hn, lwk).reshape(B, S, nkv, hd)
    v = jnp.einsum("bsh,hd->bsd", hn, lwv).reshape(B, S, nkv, hd)
    return q, k, v


@jax.named_scope("mlp")
def _swiglu_raw(hn, lg, lu, ld):
    return jnp.einsum(
        "bsi,ih->bsh",
        jax.nn.silu(jnp.einsum("bsh,hi->bsi", hn, lg)) *
        jnp.einsum("bsh,hi->bsi", hn, lu), ld)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (out.astype(x.dtype)) * w


class LlamaForCausalLM(nn.Layer):
    """Decoder-only LM with stacked-layer scan execution.

    ``forward(input_ids)`` returns logits; ``forward(input_ids, labels)``
    returns (loss, logits is skipped to save HBM).
    """

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        c = config
        H, I, V, L = c.hidden_size, c.intermediate_size, c.vocab_size, c.num_hidden_layers
        nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        dt = c.dtype
        init = nn.initializer.Normal(0.0, 0.02)
        ones = nn.initializer.Constant(1.0)
        mk = self.create_parameter

        self.embed_tokens = mk([V, H], dtype=dt, default_initializer=init)
        mark_sharding(self.embed_tokens, "mp", None)
        # stacked decoder weights [L, ...] — layer dim sharded over 'pp'
        self.wq = mk([L, H, nh * hd], dtype=dt, default_initializer=init)
        mark_sharding(self.wq, "pp", None, "mp")
        self.wk = mk([L, H, nkv * hd], dtype=dt, default_initializer=init)
        mark_sharding(self.wk, "pp", None, "mp")
        self.wv = mk([L, H, nkv * hd], dtype=dt, default_initializer=init)
        mark_sharding(self.wv, "pp", None, "mp")
        self.wo = mk([L, nh * hd, H], dtype=dt, default_initializer=init)
        mark_sharding(self.wo, "pp", "mp", None)
        self.w_gate = mk([L, H, I], dtype=dt, default_initializer=init)
        mark_sharding(self.w_gate, "pp", None, "mp")
        self.w_up = mk([L, H, I], dtype=dt, default_initializer=init)
        mark_sharding(self.w_up, "pp", None, "mp")
        self.w_down = mk([L, I, H], dtype=dt, default_initializer=init)
        mark_sharding(self.w_down, "pp", "mp", None)
        self.input_ln = mk([L, H], dtype=dt, default_initializer=ones)
        mark_sharding(self.input_ln, "pp", None)
        self.post_ln = mk([L, H], dtype=dt, default_initializer=ones)
        mark_sharding(self.post_ln, "pp", None)
        self.final_norm = mk([H], dtype=dt, default_initializer=ones)
        if c.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = mk([H, V], dtype=dt, default_initializer=init)
            mark_sharding(self.lm_head, None, "mp")

    # ------------------------------------------------------------------ fwd
    def forward(self, input_ids, labels=None, position_ids=None):
        c = self.config
        params = dict(
            embed=self.embed_tokens, wq=self.wq, wk=self.wk, wv=self.wv,
            wo=self.wo, w_gate=self.w_gate, w_up=self.w_up, w_down=self.w_down,
            input_ln=self.input_ln, post_ln=self.post_ln,
            final_norm=self.final_norm,
            lm_head=self.lm_head if self.lm_head is not None else self.embed_tokens)
        out = _llama_forward(
            input_ids, labels, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, float(c.rms_norm_eps), float(c.rope_theta),
            bool(c.use_recompute), self.lm_head is None,
            policy=c.recompute_policy,
            pipeline_microbatches=int(c.pipeline_microbatches),
            pipeline_virtual_stages=int(c.pipeline_virtual_stages),
            context_parallel=str(c.context_parallel),
            attention_layout=str(c.attention_layout),
            loss_chunk=int(c.loss_chunk), fuse_rope=bool(c.fuse_rope),
            flash_block_q=int(c.flash_block_q),
            flash_block_k=int(c.flash_block_k), **params)
        return out

    def num_params(self):
        import numpy as np
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    def decode_params(self):
        """``(params, tied)`` for the serving step programs: what the
        engine asks of every model it serves."""
        from ..serving.decode import llama_decode_params
        return llama_decode_params(self)


@tensor_op
def _llama_forward(input_ids, labels, nh, nkv, hd, eps, theta, remat, tied,
                   policy="full", pipeline_microbatches=0,
                   pipeline_virtual_stages=1, context_parallel="",
                   attention_layout="bshd", loss_chunk=0, fuse_rope=False,
                   flash_block_q=0, flash_block_k=0,
                   *, embed, wq, wk, wv, wo, w_gate, w_up, w_down, input_ln,
                   post_ln, final_norm, lm_head):
    B, S = input_ids.shape
    H = embed.shape[1]
    batch_spec = ("dp", "sharding")

    x = jnp.take(embed, input_ids, axis=0)
    x = _ann(x, batch_spec, "sep", None)
    sin, cos = _rope_tables(S, hd, theta)
    mesh = mesh_mod.get_mesh()
    sep_deg = (int(mesh.shape["sep"]) if mesh is not None and
               "sep" in mesh.axis_names else 1)
    use_cp = bool(context_parallel) and sep_deg > 1

    head_major = attention_layout == "bhsd"

    def layer_body(h, lp):
        (lwq, lwk, lwv, lwo, lg, lu, ld, lin, lpost) = lp
        Bh, Sh = h.shape[0], h.shape[1]  # microbatch-sized under pipeline
        resid = h
        hn = _rms(h, lin, eps)
        hn = _ann(hn, batch_spec, "sep", None)
        H_ = hn.shape[-1]
        if head_major:
            # head-major: projections emit [B, H, S, D] directly, so the
            # flash kernel's head fold is a free reshape — no HBM transpose
            q = jnp.einsum("bsh,hnd->bnsd", hn, lwq.reshape(H_, nh, hd))
            k = jnp.einsum("bsh,hnd->bnsd", hn, lwk.reshape(H_, nkv, hd))
            v = jnp.einsum("bsh,hnd->bnsd", hn, lwv.reshape(H_, nkv, hd))
            defer_rope = fuse_rope and not use_cp
            if not defer_rope:
                q = _apply_rope_bhsd(q, sin, cos)
                k = _apply_rope_bhsd(k, sin, cos)
            q = _ann(q, batch_spec, "mp", None, None)
            k = _ann(k, batch_spec, "mp", None, None)
        else:
            q, k, v = _qkv_bshd(hn, lwq, lwk, lwv, nh, nkv, hd)
            q = _apply_rope(q, sin, cos)
            k = _apply_rope(k, sin, cos)
            q = _ann(q, batch_spec, None, "mp", None)
            k = _ann(k, batch_spec, None, "mp", None)
        if use_cp:
            # context parallelism: seq stays sep-sharded through attention
            from ..parallel.sp_attention import (ring_attention,
                                                 ulysses_attention)
            rep_ax = 1 if head_major else 2
            kr, vr = k, v
            if nkv != nh:  # GQA: the cp kernels take equal head counts
                kr = jnp.repeat(k, nh // nkv, axis=rep_ax)
                vr = jnp.repeat(v, nh // nkv, axis=rep_ax)
            cp_fn = (ring_attention if context_parallel == "ring"
                     else ulysses_attention)
            if head_major:
                attn = cp_fn(q, kr, vr, causal=True, mesh=mesh)
            else:
                attn = jnp.swapaxes(
                    cp_fn(jnp.swapaxes(q, 1, 2), jnp.swapaxes(kr, 1, 2),
                          jnp.swapaxes(vr, 1, 2), causal=True, mesh=mesh),
                    1, 2)
        elif head_major:
            attn = _attention_bhsd(
                q, k, v, nh,
                rope=(sin, cos) if defer_rope else None,
                block_q=flash_block_q, block_k=flash_block_k)
        else:
            attn = _attention(q, k, v, causal=True)
        if head_major:
            # o-projection consumes [B, H, S, D]: transpose folds into matmul
            h = resid + _ann(
                jnp.einsum("bnsd,ndh->bsh", attn, lwo.reshape(nh, hd, H_)),
                batch_spec, "sep", None)
        else:
            attn = attn.reshape(Bh, Sh, nh * hd)
            h = resid + _ann(jnp.einsum("bsd,dh->bsh", attn, lwo),
                             batch_spec, "sep", None)
        resid = h
        hn = _rms(h, lpost, eps)
        hn = _ann(hn, batch_spec, "sep", None)
        h = resid + _ann(_swiglu_raw(hn, lg, lu, ld), batch_spec, "sep", None)
        return h, None

    if remat:
        ck_policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                     if policy == "dots" else None)
        body = jax.checkpoint(layer_body, policy=ck_policy)
    else:
        body = layer_body
    stack = (wq, wk, wv, wo, w_gate, w_up, w_down, input_ln, post_ln)
    pp_deg = (int(mesh.shape["pp"]) if mesh is not None and
              "pp" in mesh.axis_names else 1)
    # CP composes inside the pipeline: the ring shard_map re-binds to the
    # context AbstractMesh when it runs inside the schedule's manual 'pp'
    # region (sp_attention.ring_attention), and the ring position arrives
    # as a P('sep')-sharded iota instead of jax.lax.axis_index — the one
    # lowering Shardy rejects in nested partial-manual regions — so BOTH
    # partitioners compile fwd+bwd (tests/_cp_pp_child.py runs each).
    if use_cp and pp_deg > 1 and pipeline_microbatches > 0:
        if context_parallel == "ulysses":
            raise ValueError(
                "context_parallel='ulysses' cannot run inside the pipeline "
                "schedule: XLA cannot partition the head-scatter all_to_all "
                "inside a nested manual region (GSPMD CHECK "
                "IsManualSubgroup); use context_parallel='ring'")
    if pipeline_microbatches > 0 and pp_deg > 1:
        # real pipeline: stage-resident weight slices + ppermute handoffs
        from ..parallel.pp import pipeline_interleaved, pipeline_spmd

        def stage_fn(local_stack, h):
            h, _ = jax.lax.scan(lambda hh, lp: body(hh, lp), h, local_stack)
            return h

        if pipeline_virtual_stages > 1:
            x = pipeline_interleaved(
                stage_fn, stack, x, num_microbatches=pipeline_microbatches,
                num_virtual=pipeline_virtual_stages, mesh=mesh)
        else:
            x = pipeline_spmd(stage_fn, stack, x,
                              num_microbatches=pipeline_microbatches,
                              mesh=mesh)
    else:
        x, _ = jax.lax.scan(lambda h, lp: body(h, lp), x, stack)

    x = _rms(x, final_norm, eps)
    head = lm_head.T if tied else lm_head
    if labels is None:
        logits = jnp.einsum("bsh,hv->bsv", x, head)
        return _ann(logits, batch_spec, None, "mp")

    return _shifted_ce_loss(x, head, labels, loss_chunk, batch_spec)


@jax.named_scope("loss")
def _shifted_ce_loss(x, head, labels, loss_chunk, batch_spec):
    """The training loss head on the final hidden states ``x`` [B, S, H]."""
    B, S, H = x.shape
    # training: shifted CE via logsumexp (loss = lse - picked_logit)
    if loss_chunk > 0 and S % loss_chunk != 0:
        import warnings
        warnings.warn(
            f"loss_chunk={loss_chunk} does not divide seq_len={S}; falling "
            f"back to the unfused CE (full [B,S,V] f32 logits materialize)")
    if loss_chunk > 0 and S % loss_chunk == 0:
        # chunked lm-head+CE: only one [B, chunk, V] f32 logits block is
        # ever live; jax.checkpoint recomputes it per-chunk in the backward
        # instead of saving S/chunk of them (the reference's fused
        # c_softmax_with_cross_entropy memory behavior, scan-style)
        nch = S // loss_chunk
        tgt = jnp.concatenate(
            [labels[:, 1:], jnp.full((B, 1), -1, labels.dtype)], axis=1)
        xs = jnp.swapaxes(x.reshape(B, nch, loss_chunk, H), 0, 1)
        tc = jnp.swapaxes(tgt.reshape(B, nch, loss_chunk), 0, 1)

        def ce_chunk(carry, xt):
            xc, t = xt
            lg = jnp.einsum("bch,hv->bcv", xc, head,
                            preferred_element_type=jnp.float32)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            picked = jnp.take_along_axis(
                lg, jnp.maximum(t, 0)[..., None], axis=-1)[..., 0]
            m = (t >= 0).astype(jnp.float32)
            s, n = carry
            return (s + jnp.sum((lse - picked) * m), n + jnp.sum(m)), None

        (tot, cnt), _ = jax.lax.scan(jax.checkpoint(ce_chunk),
                                     (jnp.float32(0.0), jnp.float32(0.0)),
                                     (xs, tc))
        return tot / jnp.maximum(cnt, 1.0)

    # unfused path: the f32 [B,S,V] logits materialize once
    logits = jnp.einsum("bsh,hv->bsv", x[:, :-1], head)
    logits = _ann(logits, batch_spec, None, "mp")
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    tgt = labels[:, 1:]
    picked = jnp.take_along_axis(lf, tgt[..., None], axis=-1)[..., 0]
    mask = (tgt >= 0).astype(jnp.float32)
    loss = jnp.sum((lse - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss


class LlamaPretrainCriterion(nn.Layer):
    """Loss wrapper matching the PaddleNLP criterion surface."""

    def __init__(self, config=None):
        super().__init__()

    def forward(self, loss_or_logits, labels=None):
        if labels is None:
            return loss_or_logits
        return F.cross_entropy(loss_or_logits, labels)


# ----------------------------------------------------------------- generate
def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
             top_k=0, max_cache_len=None, seed=None, eos_token_id=None,
             _decode_chunk=16):
    """Autoregressive generation over the continuous-batching decode
    engine (``serving/engine.py``): a jitted per-prompt prefill feeds a
    slot KV cache, then one compiled single-token decode program —
    shapes depend only on ``(batch, cache_len)``, sampling knobs are
    runtime arrays — ticks all rows together. Greedy by default;
    ``temperature>0`` enables top-k sampling; ``eos_token_id`` stops a
    row early (its tail is padded with the EOS id).

    The decode/prefill executables live on the model (``_serving_jit``)
    and are reused per cache shape: sampling-knob changes (temperature /
    top_k / seed) never retrace; max_new_tokens changes retrace only
    when they change the cache length — pin ``max_cache_len`` (or rely
    on the ``max_position_embeddings`` clamp) to make every call share
    one set of executables.
    """
    import numpy as np

    from ..core import random as _random_mod
    from ..core.tensor import Tensor as _T
    from ..serving import ContinuousBatchingEngine, GenerationRequest

    c = self.config
    ids = input_ids.value if isinstance(input_ids, _T) else \
        jnp.asarray(input_ids)
    ids_np = np.asarray(ids)
    B, S = ids_np.shape
    s_max = int(max_cache_len or min(c.max_position_embeddings,
                                     S + max_new_tokens))
    if S + int(max_new_tokens) > s_max:
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"the KV cache length ({s_max}); raise max_cache_len / "
            f"max_position_embeddings or generate fewer tokens")
    base_key = (jax.random.PRNGKey(seed) if seed is not None
                else _random_mod.next_key())
    engine = ContinuousBatchingEngine(
        self, num_slots=B, max_seq_len=s_max,
        # exact-length prefill: same-shape prompts compile one program,
        # exactly like the pre-engine monolith did. chunk=16 bounds the
        # host round-trips of this offline all-at-once case (no queue to
        # starve) — floor(m/16)+m%16 dispatches for m decode steps (a
        # model whose layer the fused tail was not taught passes 1)
        prefill_bucketing="exact", decode_chunk=_decode_chunk,
        jit_cache=self.__dict__.setdefault("_serving_jit", {}))
    reqs = [GenerationRequest(
        prompt=ids_np[i], max_new_tokens=int(max_new_tokens),
        temperature=float(temperature), top_k=int(top_k),
        eos_token_id=eos_token_id,
        prng_key=jax.random.fold_in(base_key, i)) for i in range(B)]
    outs = engine.generate(reqs)
    pad = int(eos_token_id) if eos_token_id is not None else 0
    out = np.stack([
        np.pad(o, (0, int(max_new_tokens) - len(o)), constant_values=pad)
        for o in outs])
    return _T(jnp.asarray(out.astype(ids_np.dtype)))


LlamaForCausalLM.generate = generate
