"""Qwen3-Next (``model_type`` ``qwen3_next``;
Qwen/Qwen3-Next-80B-A3B-Instruct): a pre-norm decoder whose layers come in
periods of Gated DeltaNet (arXiv:2412.06464) layers and then one gated
full-attention layer, every layer followed by a routed FFN with a gated shared
expert. Served, not trained.

Every layer, ``x`` the residual stream, no bias anywhere:

    x = x + Mixer(N(x; ln1));   x = x + FFN(N(x; ln2))

``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` in float32: the norm's weight
is ZERO-CENTRED (before each mixer, before each FFN, before the head, and on
``q``, ``k`` a HEAD). Layer ``i`` is full attention where ``(i + 1) %
full_attention_interval == 0``, else linear.

- *Full-attention mixer* (``num_attention_heads`` query heads on
  ``num_key_value_heads`` KV heads of ``head_dim``): ``[q | gate] = x W_q``, a
  head's ``2 head_dim`` columns its query then its gate; ``k = x W_k``, ``v = x
  W_v``; ``q = N(q; q_norm)``, ``k = N(k; k_norm)`` a head; the first
  ``partial_rotary_factor * head_dim`` values of a head of ``q`` and ``k``
  rotated (rotate-half inside them, ``rope_theta``), the rest left; causal
  softmax attention at ``head_dim^-0.5``; ``o = (attn * sigmoid(gate)) W_o``.
  The cache holds ``k`` after norm and rotation, and ``v``.
- *Gated DeltaNet mixer*: ``models.olmo_hybrid``'s, with three differences:
  ``q`` and ``k`` come at ``linear_num_key_heads`` heads and value head ``h``
  (of ``linear_num_value_heads``) reads key head ``h // (value heads / key
  heads)``; ``beta = sigmoid(b)`` is never doubled; the layer's INPUT is
  normalised, not its output. The output norm's weight ``w_o`` (one vector for
  all heads) is NOT zero-centred: ``y = (o / sqrt(mean(o^2) + eps) * w_o) *
  silu(z)``.
- *FFN*: router ``x W_r`` over ``router_experts``, softmax in float32, the
  ``num_experts_per_tok`` largest, their probabilities divided by their sum
  (``norm_topk_prob``); an expert is a SwiGLU at ``moe_intermediate_size``;
  plus ``sigmoid(x w_sg) * SwiGLU_shared(x)`` at
  ``shared_expert_intermediate_size``. ``num_experts`` is what THIS chip
  holds, ids ``first_held_expert .. + num_experts`` of the router's width; what
  the absent experts would add is left out.

What the cache holds of a sequence: a row a token in each FULL layer's pool
layer; a linear layer's state ``[dk, value heads x dv]`` float32 and its
convolution's last 3 inputs in the store by slot.

Not built: the multi-token prediction module the model card describes (the
published ``config.json`` has no key for it); serving without it is the
model's plain decoding.

Storage, not mathematics: the published code holds ``q, k, v, z`` of a linear
layer as one matrix whose columns are grouped by key head, and ``b, a``
likewise; here ``[W_q | W_k | W_v]`` is ``gdn_wqkv`` (what the convolution
runs over, in that order), ``W_z`` ``gdn_wz`` and ``[W_a | W_b]`` ``gdn_wab``,
each head-major, as ``models.olmo_hybrid`` holds them.

Parameters are stacked by layer KIND and place in the period, built in their
dtype by one jitted call from the seed, a layer of a stack at a time: the full
layers' under the plain names ``[periods, ...]``, the linear layers' under
``linear_layers``, one tree ``[periods, ...]`` a place; every layer's FFN
entries (``router``, the expert stacks ``[periods, E_held, ...]``, ``ws_*``)
beside its mixer's. Normal(0, 0.02); zero-centred norm weights Normal(0, 0.1)
(the published initial value 0 would let ``w`` pass for ``1 + w``); ``w_o`` 1;
the decay FLA's (float32): ``A_log = log U(0, 16)``, ``dt_bias =
softplus^-1(exp(U(log 0.001, log 0.1)))``; the convolution's weight ``U(-1/2,
1/2)`` (fan-in 4). The layer bodies are the serving programs' own
(``serving.decode._decoder_layer`` / ``_gdn_layer``, both
``_mixer_ffn_layer``), chosen by what the tree holds.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as _random
from ..core import dtype as dtype_mod
from ..core.tensor import Tensor
from ..nn.layer import Parameter
from .deepseek_v2 import DeepseekV2ForCausalLM
from .llama import build_once
from .llama import generate as _llama_generate
from .olmo_hybrid import Gdn


@dataclass
class Qwen3NextConfig:
    """The source's keys by the source's names, plus ``router_experts`` (the
    router's published width) and ``first_held_expert`` where ``num_experts``
    is a chip's share. ``dtype`` and ``decode_attention`` as
    ``LlamaConfig``."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5120       # (no layer is dense: read by nothing)
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512
    router_experts: int | None = None
    first_held_expert: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    decode_attention: str = "pallas"
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.num_experts
        per = self.full_attention_interval
        if per < 2 or self.num_hidden_layers % per:
            raise ValueError(
                f"Qwen3NextConfig: num_hidden_layers "
                f"({self.num_hidden_layers}) is whole periods of "
                f"full_attention_interval ({per}) layers, the last of each "
                f"the full one (the step programs scan the periods)")
        if self.linear_num_value_heads % self.linear_num_key_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                "Qwen3NextConfig: a linear layer's value heads are whole "
                "groups of its key heads, the query heads whole groups of "
                "the KV heads")
        if self.decoder_sparse_step != 1 or self.tie_word_embeddings \
                or not (0 <= self.first_held_expert
                        <= self.router_experts - self.num_experts):
            raise ValueError(
                f"Qwen3NextConfig: every layer's FFN is routed "
                f"(decoder_sparse_step 1), the head is untied, and the held "
                f"experts {self.first_held_expert}..+{self.num_experts} "
                f"must lie inside the router's {self.router_experts}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"Qwen3NextConfig: partial_rotary_factor x head_dim "
                f"({self.rotary_dim}) is an even part of a head")

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def linear_per_period(self):
        return self.full_attention_interval - 1

    @property
    def num_kv_layers(self):
        """Layers that hold keys and values: the full-attention ones."""
        return self.num_hidden_layers // self.full_attention_interval

    @property
    def num_linear_layers(self):
        return self.num_hidden_layers - self.num_kv_layers

    @property
    def gdn(self):
        return Gdn(int(self.linear_num_value_heads),
                   int(self.linear_key_head_dim),
                   int(self.linear_value_head_dim),
                   int(self.linear_conv_kernel_dim), False,
                   self.decode_attention,
                   key_heads=int(self.linear_num_key_heads))

    @property
    def conv_channels(self):
        return self.linear_num_key_heads * self.linear_key_head_dim * 2 \
            + self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def routing(self):
        """``models.deepseek_v2``'s tuple: plain softmax top-k, no groups,
        no scale."""
        return (int(self.num_experts_per_tok), bool(self.norm_topk_prob),
                1, 1, int(self.first_held_expert), 1.0)


def qwen3_next_tiny(**kw):
    """Test / rehearsal config: hidden 64, 2 periods (6 linear layers and 2
    full), 4 query heads on 2 KV heads of 16 with 8 rotated, 4 linear value
    heads on 2 key heads of 8 and 16, a router over 8 experts of width 32 of
    which the first 4 are held, 2 a token, a shared expert of 48, vocab 256."""
    defaults = dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        partial_rotary_factor=0.5, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=16, num_experts=4, router_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=48, max_position_embeddings=128)
    defaults.update(kw)
    return Qwen3NextConfig(**defaults)


def _shapes(c):
    """``(full, linear, ffn)``: each ``(normal, zero-centred norms)`` name ->
    shape of ONE place of the period, ``[periods, ...]``; ``ffn`` is what
    every layer holds beside its mixer."""
    H, P, hd = c.hidden_size, c.num_kv_layers, c.head_dim
    nq, nkv = c.num_attention_heads * hd, c.num_key_value_heads * hd
    hv = c.linear_num_value_heads
    C, vd = c.conv_channels, hv * c.linear_value_head_dim
    E, I, Is = (c.num_experts, c.moe_intermediate_size,
                c.shared_expert_intermediate_size)
    full = (dict(wq=(P, H, 2 * nq), wk=(P, H, nkv), wv=(P, H, nkv),
                 wo=(P, nq, H)),
            dict(q_norm=(P, hd), k_norm=(P, hd)))
    linear = (dict(gdn_wqkv=(P, H, C), gdn_wz=(P, H, vd),
                   gdn_wab=(P, H, 2 * hv), gdn_wo=(P, vd, H)), {})
    ffn = (dict(router=(P, H, c.router_experts), w_gate=(P, E, H, I),
                w_up=(P, E, H, I), w_down=(P, E, I, H), ws_gate=(P, H, Is),
                ws_up=(P, H, Is), ws_down=(P, Is, H), ws_sgate=(P, H, 1)),
           dict(input_ln=(P, H), post_ln=(P, H)))
    return full, linear, ffn


class Qwen3NextForCausalLM(nn.Layer):
    """Decoder-only LM of Gated DeltaNet and gated full-attention layers,
    each with a routed FFN; parameters stacked by layer kind and place.
    ``forward(input_ids)`` returns logits; ``generate`` runs the serving
    engine, as ``LlamaForCausalLM.generate`` does."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = c = config
        full, linear, ffn = _shapes(c)
        dt = dtype_mod.to_jax_dtype(c.dtype)
        f32 = jnp.float32
        P, places = c.num_kv_layers, c.linear_per_period
        gate = (P, c.linear_num_value_heads)

        def draw(key, shape, std):
            # (a stack is drawn a layer at a time: models.deepseek_v2)
            if len(shape) >= 3:
                return jax.lax.map(
                    lambda k: draw(k, shape[1:], std),
                    jax.random.split(key, shape[0]))
            return (std * jax.random.normal(key, shape, f32)).astype(dt)

        def tree(key, *kinds):
            """One place of the period: matrices Normal(0, 0.02), the
            zero-centred norm weights Normal(0, 0.1)."""
            normal = {n: s for k in kinds for n, s in k[0].items()}
            norms = {n: s for k in kinds for n, s in k[1].items()}
            k_w, k_n = jax.random.split(key)
            out = {n: draw(k, s, 0.02) for k, (n, s) in zip(
                jax.random.split(k_w, len(normal)), sorted(normal.items()))}
            out.update({n: draw(k, s, 0.1) for k, (n, s) in zip(
                jax.random.split(k_n, len(norms)), sorted(norms.items()))})
            return out

        def linear_tree(key):
            k_t, k_c, k_a, k_dt = jax.random.split(key, 4)
            out = tree(k_t, linear, ffn)
            out["gdn_conv"] = jax.random.uniform(
                k_c, (P, c.linear_conv_kernel_dim, c.conv_channels), f32,
                -0.5, 0.5).astype(dt)
            out["gdn_o_norm"] = jnp.ones((P, c.linear_value_head_dim), dt)
            # the decay, FLA's initialisation, float32 whatever the dtype
            out["gdn_A_log"] = jnp.log(jax.random.uniform(
                k_a, gate, f32, 1e-6, 16.0))
            step = jnp.exp(jax.random.uniform(
                k_dt, gate, f32, math.log(0.001), math.log(0.1)))
            out["gdn_dt_bias"] = step + jnp.log(-jnp.expm1(-step))
            return out

        # every parameter in its own dtype, in ONE jitted call (as
        # ``models.olmoe``): never float32 first
        def build(key):
            k_e, k_h, k_n, k_f, *k_lin = jax.random.split(key, 4 + places)
            top = tree(k_f, full, ffn)
            top.update(
                embed_tokens=draw(k_e, (c.vocab_size, c.hidden_size), 0.02),
                lm_head=draw(k_h, (c.hidden_size, c.vocab_size), 0.02),
                final_norm=draw(k_n, (c.hidden_size,), 0.1))
            return top, tuple(linear_tree(k) for k in k_lin)

        top, lin = build_once(config, build)(_random.next_key())
        self._full_names = tuple(sorted(
            n for n in top if n not in ("embed_tokens", "lm_head",
                                        "final_norm")))
        for name, value in top.items():
            setattr(self, name, Parameter(value))
        for j, t in enumerate(lin):
            for name, value in t.items():
                setattr(self, f"linear{j}_{name}", Parameter(value))
        self._linear_names = tuple(sorted(lin[0]))
        from ..serving.routing_record import RoutingRecord
        self.routing_record = RoutingRecord()

    def decode_params(self):
        """``(params, tied)`` for the serving step programs: the raw-array
        tree whose keys choose the layer bodies (``linear_layers``: the
        periods of Gated DeltaNet layers; ``input_ln`` / ``post_ln``:
        pre-norm; ``router``, ``ws_gate``, ``ws_sgate``: a routed FFN beside a
        gated shared expert, in every layer; a ``wq`` twice the heads' width:
        the attention's output gate; a ``q_norm`` one head wide: the norm a
        head; ``norm_plus_one``: zero-centred norm weights)."""
        p = {n: getattr(self, n).value for n in self._full_names}
        p["linear_layers"] = tuple(
            {n: getattr(self, f"linear{j}_{n}").value
             for n in self._linear_names}
            for j in range(self.config.linear_per_period))
        p.update(embed=self.embed_tokens.value, lm_head=self.lm_head.value,
                 final_norm=self.final_norm.value,
                 norm_plus_one=jnp.ones((), jnp.float32))
        return p, False

    def forward(self, input_ids, return_router_picks=False):
        """Logits ``[B, S, V]``: the layers of whole-prompt prefill
        (``serving.decode._hybrid_prefill_layers``), a sequence at a time;
        with ``return_router_picks`` also the experts every position picked
        in every layer's FFN, ``[L, B, S, top_k]`` (the serving programs' own
        where they ran: ``DeepseekV2ForCausalLM.forward``)."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params, _ = self.decode_params()
        c = self.config
        logits, picks = _forward(
            params, ids, nh=c.num_attention_heads,
            nkv=c.num_key_value_heads, hd=c.head_dim,
            eps=float(c.rms_norm_eps), theta=float(c.rope_theta),
            rotary=c.rotary_dim, gdn=c.gdn, moe=c.routing,
            return_picks=bool(return_router_picks))
        if return_router_picks:
            served = self.served_router_picks(ids)
            if served is not None:
                picks = jnp.where(served >= 0, served, picks)
            return Tensor(logits), picks
        return Tensor(logits)

    served_router_picks = DeepseekV2ForCausalLM.served_router_picks
    num_params = DeepseekV2ForCausalLM.num_params

    # the engine's fused decode tail (decode_chunk > 1) has a layer body
    # of its own that was not taught these layers: one tick a step
    generate = functools.partialmethod(_llama_generate, _decode_chunk=1)


@functools.partial(jax.jit, static_argnames=(
    "nh", "nkv", "hd", "eps", "theta", "rotary", "gdn", "moe",
    "return_picks"))
def _forward(params, ids, *, nh, nkv, hd, eps, theta, rotary, gdn, moe,
             return_picks):
    """(logits [B, S, V], picked experts [L, B, S, top_k] or None)."""
    from ..serving.decode import _final_norm, _hybrid_prefill_layers
    lengths = jnp.full((1,), ids.shape[1], jnp.int32)

    def one_sequence(row):
        x = jnp.take(params["embed"], row[None], axis=0)
        x, _, _, _, stats = _hybrid_prefill_layers(
            params, x, lengths, nh=nh, nkv=nkv, hd=hd, eps=eps, gdn=gdn,
            theta=theta, rotary=rotary, moe=moe, return_picks=return_picks)
        x = _final_norm(params, x[0], eps)
        return (jnp.einsum("sh,hv->sv", x, params["lm_head"]),
                stats[1][:, 0] if return_picks else None)

    logits, picks = jax.lax.map(one_sequence, ids)
    return logits, (None if picks is None else jnp.moveaxis(picks, 0, 1))
