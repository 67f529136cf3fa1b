"""DeepSeek-V2 (``model_type`` ``deepseek_v2``; deepseek-ai/DeepSeek-V2):
multi-head latent attention (MLA), a leading dense layer, then layers whose
FFN is shared experts plus group-limited routed experts. Served, not trained.

Per layer, ``x`` the residual stream, ``h = RMSNorm(x; input_ln)``, no bias:

- ``c_q = RMSNorm(h W_qa; q_a_ln)``; ``q = c_q W_qb``, by head ``q_nope |
  q_pe``;
- ``[c_kv | k_pe] = h W_kva``; ``c_kv = RMSNorm(c_kv; kv_a_ln)``; ``k_pe`` is
  one vector a token, shared by all heads; ``[k_nope | v] = c_kv W_kvb`` by
  head;
- RoPE on ``q_pe`` and ``k_pe`` (half-split pairs ``(i, i + rope / 2)``) with
  YaRN's frequencies (``yarn_inv_freq``); the cos / sin factor is
  ``mscale / mscale_all_dim`` = 1 for the published values;
- ``score = (q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-0.5 *
  mscale(factor, mscale_all_dim)^2``, causal softmax in float32,
  ``x = x + concat_heads(softmax . v) W_o``;
- what a cache holds of a token: ``c_kv`` after its norm and ``k_pe`` after
  RoPE, nothing else. The serving step attends in the ABSORBED form
  (``W_UK`` folded into the query, ``W_UV`` into the output, over the latent
  pool: ``kernels.pallas_mla_ragged_attention``), whole-prompt prefill and
  ``forward`` in the expanded form; both are ``serving.decode._decoder_layer``;
- FFN on ``g = RMSNorm(x; post_ln)``: the first ``first_k_dense_replace``
  layers a SwiGLU of ``intermediate_size``; the others ``x + SwiGLU(g;
  shared, n_shared_experts * moe_intermediate_size) + routed_scaling_factor *
  sum_e s_e SwiGLU(g; expert e)``, ``s = softmax(g W_r)`` in float32 over the
  router's whole width, limited to the ``topk_group`` best of ``n_group``
  groups (a group's score the max of its experts'), the
  ``num_experts_per_tok`` largest as they are (``kernels.moe_ffn``).

**A share of the experts.** ``n_routed_experts`` is what THIS instance holds,
the contiguous ids ``first_held_expert ..``; ``router_experts`` (default: the
same) is the router's width. With fewer held than routed, a layer adds the
shared expert and the held experts' part of the routed sum and leaves the rest
out: one chip's part of an expert-parallel layer, with no stand-in for the
exchange that would complete it.

Parameters are stacked over layers in two stacks (the leading dense layers
under ``dense_*``, the expert layers under the plain names), built in their
dtype by one jitted call. The auxiliary balance losses (``seq_aux``) belong to
training and are not here.

**The routing the serving programs made** is kept by ``routing_record``
(``serving.routing_record``): with a share of the experts held, a pick that
lands elsewhere leaves no trace in the output, so the step programs return
their picks and ``served_router_picks`` reads them back for a check. Weights
are Normal(0, 0.02) throughout, norm weights 1. With ``routed_scaling_factor``
16 the routed sum then dominates a layer's update and one near-tie of the
router decided the other way moves the stream by a tenth (PERF.md, PR 31):
a check against a reference has to follow the served routing, not its own.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as _random
from ..core import dtype as dtype_mod
from ..core.tensor import Tensor
from ..nn.layer import Parameter
from .llama import _rms, build_once
from .llama import generate as _llama_generate

_YARN = dict(type="yarn", factor=40, original_max_position_embeddings=4096,
             beta_fast=32, beta_slow=1, mscale=0.707, mscale_all_dim=0.707)


class Mla(NamedTuple):
    """Latent attention's static numbers for the step programs
    (``config.mla``): the latent's ``rank``, a head's rope-free / rope / value
    widths, the softmax ``scale`` and YaRN's ``(factor, original context,
    beta_fast, beta_slow, cos / sin factor)`` or None."""
    rank: int
    nope: int
    rope: int
    v: int
    scale: float
    yarn: tuple | None


@dataclass
class DeepseekV2Config:
    """The source's keys by the source's names, plus ``router_experts`` and
    ``first_held_expert`` (module docstring). ``dtype`` and
    ``decode_attention`` as ``LlamaConfig``."""
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160
    router_experts: int | None = None
    first_held_expert: int = 0
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict | None = field(default_factory=lambda: dict(_YARN))
    tie_word_embeddings: bool = False
    decode_attention: str = "pallas"
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        if self.moe_layer_freq != 1 or not (
                0 <= self.first_k_dense_replace < self.num_hidden_layers):
            raise ValueError(
                "DeepseekV2Config: the layers are first_k_dense_replace "
                "dense ones, then expert layers (moe_layer_freq 1), at "
                "least one of them")
        if self.router_experts % self.n_group or not (
                0 <= self.first_held_expert
                <= self.router_experts - self.n_routed_experts):
            raise ValueError(
                f"DeepseekV2Config: the held experts "
                f"{self.first_held_expert}..+{self.n_routed_experts} must "
                f"lie inside the router's {self.router_experts}, which "
                f"n_group {self.n_group} must divide")

    @property
    def head_dim(self):
        """Width of a query / key head: the rope-free and the rope part."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def mla(self):
        """The attention's static numbers for the step programs."""
        rs = self.rope_scaling
        yarn, scale = None, self.head_dim ** -0.5
        if rs:
            yarn = (float(rs["factor"]),
                    int(rs["original_max_position_embeddings"]),
                    float(rs["beta_fast"]), float(rs["beta_slow"]),
                    yarn_mscale(rs["factor"], rs["mscale"])
                    / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
            scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
        return Mla(self.kv_lora_rank, self.qk_nope_head_dim,
                   self.qk_rope_head_dim, self.v_head_dim, float(scale), yarn)

    @property
    def routing(self):
        """The routed FFN's static numbers for the step programs: ``(top_k,
        renormalize, n_group, topk_group, first_held, scale)``."""
        return (int(self.num_experts_per_tok), bool(self.norm_topk_prob),
                int(self.n_group), int(self.topk_group),
                int(self.first_held_expert),
                float(self.routed_scaling_factor))


def deepseek_v2_tiny(**kw):
    """Test / rehearsal config: hidden 64, 4 heads (nope 16, rope 8, v 16),
    latent 32 / 48, 1 dense + 2 expert layers, a router over 2 groups of 4
    experts of width 32 of which group 0 is held, 2 a token from 1 group,
    vocab 256, YaRN over an original context of 32."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=4, router_experts=8,
        n_shared_experts=2, num_experts_per_tok=2, n_group=2, topk_group=1,
        max_position_embeddings=128,
        rope_scaling=dict(_YARN, factor=4,
                          original_max_position_embeddings=32))
    defaults.update(kw)
    return DeepseekV2Config(**defaults)


def yarn_mscale(factor, mscale):
    """``0.1 m ln s + 1`` (1 for a factor of at most 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """YaRN's ``dim / 2`` rotary frequencies: ``theta^(-2i/dim)`` where a
    frequency turns more than ``beta_fast`` times inside the original
    context, the same over ``factor`` where fewer than ``beta_slow`` times,
    and the linear ramp between the two correction dims in between."""
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)
    extra = 1.0 / theta ** (i / dim)

    def correction_dim(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_tables(seq_len, dim, theta, yarn):
    """(sin, cos) ``[seq_len, dim]`` for the half-split layout; ``yarn`` is
    ``Mla.yarn`` (None: plain RoPE)."""
    if yarn is None:
        inv, factor = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                      / dim), 1.0
    else:
        inv, factor = yarn_inv_freq(dim, theta, *yarn[:4]), yarn[4]
    freqs = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.sin(emb) * factor, jnp.cos(emb) * factor


_ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "q_a_ln", "kv_a_ln",
         "input_ln", "post_ln")
_FFN = ("w_gate", "w_up", "w_down")
_SHARED = ("ws_gate", "ws_up", "ws_down")


def _stack_shapes(c, n, width, experts=None):
    """(normal, ones) shapes of a stack of ``n`` layers whose FFN is
    ``width`` wide, with ``experts`` of them a layer (None: dense)."""
    H, nh = c.hidden_size, c.num_attention_heads
    e = () if experts is None else (experts,)
    normal = dict(
        wq_a=(n, H, c.q_lora_rank), wq_b=(n, c.q_lora_rank, nh * c.head_dim),
        wkv_a=(n, H, c.kv_lora_rank + c.qk_rope_head_dim),
        wkv_b=(n, c.kv_lora_rank,
               nh * (c.qk_nope_head_dim + c.v_head_dim)),
        wo=(n, nh * c.v_head_dim, H), w_gate=(n,) + e + (H, width),
        w_up=(n,) + e + (H, width), w_down=(n,) + e + (width, H))
    ones = dict(q_a_ln=(n, c.q_lora_rank), kv_a_ln=(n, c.kv_lora_rank),
                input_ln=(n, H), post_ln=(n, H))
    return normal, ones


def _param_shapes(c):
    n_dense = c.first_k_dense_replace
    normal, ones = _stack_shapes(c, c.num_hidden_layers - n_dense,
                                 c.moe_intermediate_size, c.n_routed_experts)
    H, shared = c.hidden_size, c.n_shared_experts * c.moe_intermediate_size
    normal.update(router=(normal["wq_a"][0], H, c.router_experts),
                  ws_gate=(normal["wq_a"][0], H, shared),
                  ws_up=(normal["wq_a"][0], H, shared),
                  ws_down=(normal["wq_a"][0], shared, H),
                  embed_tokens=(c.vocab_size, H))
    ones["final_norm"] = (H,)
    if n_dense:
        dn, do = _stack_shapes(c, n_dense, c.intermediate_size)
        normal.update({"dense_" + k: v for k, v in dn.items()})
        ones.update({"dense_" + k: v for k, v in do.items()})
    if not c.tie_word_embeddings:
        normal["lm_head"] = (H, c.vocab_size)
    return normal, ones


class DeepseekV2ForCausalLM(nn.Layer):
    """Decoder-only LM with latent attention and a shared + routed FFN,
    parameters stacked over layers. ``forward(input_ids)`` returns logits;
    ``generate`` runs the serving engine."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        normal, ones = _param_shapes(config)
        dt = dtype_mod.to_jax_dtype(config.dtype)

        def draw(key, shape, std):
            # a stack is drawn a layer at a time: the float32 draw is then a
            # layer's, never the stack's (2.2 G values of experts here)
            if len(shape) >= 3:
                return jax.lax.map(
                    lambda k: draw(k, shape[1:], std),
                    jax.random.split(key, shape[0]))
            return (std * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dt)

        def build(key):
            keys = jax.random.split(key, len(normal))
            out = {n: draw(k, s, 0.02)
                   for k, (n, s) in zip(keys, sorted(normal.items()))}
            out.update({n: jnp.ones(s, dt) for n, s in ones.items()})
            return out

        built = build_once(config, build)(_random.next_key())
        for name, value in built.items():
            setattr(self, name, Parameter(value))
        if config.tie_word_embeddings:
            self.lm_head = None
        from ..serving.routing_record import RoutingRecord
        self.routing_record = RoutingRecord()

    def decode_params(self):
        """``(params, tied)`` for the serving step programs: the expert
        layers' stack by the plain names (``wkv_a``: latent attention;
        ``router``: the routed FFN; ``ws_*``: the shared expert), the leading
        dense layers' stack under ``dense_layers``."""
        p = {n: getattr(self, n).value
             for n in _ATTN + _FFN + _SHARED + ("router", "final_norm")}
        if self.config.first_k_dense_replace:
            p["dense_layers"] = {n: getattr(self, "dense_" + n).value
                                 for n in _ATTN + _FFN}
        p["embed"] = self.embed_tokens.value
        p["lm_head"] = (self.embed_tokens.value if self.lm_head is None
                        else self.lm_head.value)
        return p, self.lm_head is None

    def forward(self, input_ids, return_router_picks=False):
        """Logits ``[B, S, V]``; with ``return_router_picks`` also the
        experts every position picked in every EXPERT layer, by the router's
        ids, held here or not: ``[L_expert, B, S, top_k]`` int32. Where the
        serving engine served every row of ``input_ids``
        (:meth:`served_router_picks`), the picks are the step programs' own
        at the positions they ran, since those are the routing a check has
        to judge; this forward's elsewhere."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params, tied = self.decode_params()
        c = self.config
        logits, picks = _forward(
            params, ids, nh=c.num_attention_heads, eps=float(c.rms_norm_eps),
            theta=float(c.rope_theta), tied=tied, mla=c.mla, moe=c.routing,
            return_picks=bool(return_router_picks))
        if return_router_picks:
            served = self.served_router_picks(ids)
            if served is not None:
                picks = jnp.where(served >= 0, served, picks)
            return Tensor(logits), picks
        return Tensor(logits)

    def served_router_picks(self, input_ids):
        """The experts the serving step programs picked for sequences the
        engine served, ``[L_expert, B, S, top_k]`` int32 by the router's ids,
        -1 where no program ran (past a sequence's end, and its last sampled
        token, which is never fed back); None unless the record holds every
        row of ``input_ids`` (a served sequence's content, prompt then
        generated tokens, is a prefix of its row)."""
        import numpy as np
        rows = [self.routing_record.lookup(r) for r in np.asarray(input_ids)]
        if any(r is None for r in rows):
            return None
        out = np.stack(rows, axis=1)
        # (a row that made no pick carries the router's width: says nothing)
        return np.where(out < self.config.router_experts, out, -1)

    def num_params(self):
        import numpy as np
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # the engine's fused decode tail (decode_chunk > 1) has a layer body
    # of its own that was not taught this layer: one tick a step
    generate = functools.partialmethod(_llama_generate, _decode_chunk=1)


@functools.partial(jax.jit, static_argnames=(
    "nh", "eps", "theta", "tied", "mla", "moe", "return_picks"))
def _forward(params, ids, *, nh, eps, theta, tied, mla, moe,
             return_picks=False):
    """Plain whole-sequence forward: (logits [B, S, V], picked experts
    [L_expert, B, S, top_k] or None). The layer body is the serving
    programs' own, in the expanded form, with no cache; one sequence at a
    time, so that a check of a few long sequences fits beside an engine
    (128 heads of keys and values are 1 GB a tensor at 4 x 4k tokens)."""
    from ..serving.decode import (_apply_rope, _decoder_layer, _layer_stacks,
                                  mla_expanded_attention)
    sin, cos = rope_tables(ids.shape[1], mla.rope, theta, mla.yarn)
    head = params["lm_head"].T if tied else params["lm_head"]

    def one_sequence(row):
        x = jnp.take(params["embed"], row[None], axis=0)
        picks = None
        for _, keys, stack, experts in _layer_stacks(params):
            routed = return_picks and experts is not None

            def layer(h, lp):
                h, _, stats = _decoder_layer(
                    h, dict(zip(keys, lp)), nh=nh, nkv=nh,
                    hd=mla.nope + mla.rope, eps=eps,
                    rope=lambda x: _apply_rope(x, sin, cos),
                    attend=lambda *a: (
                        mla_expanded_attention(*a, mla=mla), None),
                    mla=mla, moe=moe, experts=experts, return_picks=routed)
                return h, (stats[1][0] if routed else None)

            x, p = jax.lax.scan(layer, x, stack)
            picks = p if experts is not None else picks
        x = _rms(x[0], params["final_norm"], eps)
        return jnp.einsum("sh,hv->sv", x, head), picks

    logits, picks = jax.lax.map(one_sequence, ids)
    return logits, (None if picks is None else jnp.moveaxis(picks, 0, 1))
