"""Nemotron-H (``model_type`` ``nemotron_h``; nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B): a decoder whose blocks are ONE mixer each, a Mamba-2 layer
(state-space duality, arXiv:2405.21060), a routed FFN or grouped-query
attention, by the letter of ``hybrid_override_pattern`` (``M`` / ``E`` /
``*``). Served, not trained.

Every block, ``x`` the residual stream: ``x = x + Mixer_l(RMSNorm(x;
norm_l))``. After the last block ``RMSNorm(x; norm_f)`` and the untied head.
No rotary embedding anywhere: the Mamba-2 layers carry the order.

- *``M``, Mamba-2* (a token ``t``, input ``u_t``): ``[z_t | xBC_t | dt_t] = u_t
  W_in`` (``H P``, ``H P + 2 G N`` and ``H`` wide: ``H`` heads of ``P``
  channels, ``G`` groups, state size ``N``); ``xBC'_t = silu(b_conv + sum_j
  w_conv[j] * xBC_{t-3+j})`` a channel (causal, zeros before the sequence);
  ``[x_t | B_t | C_t] = xBC'_t``, head ``h`` using group ``h // (H / G)``;
  ``Delta_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)`` a scalar a head;
  the state ``S`` (``[P, N]`` a head, float32, zero at a sequence's start):
  ``S = exp(Delta_t A) S + Delta_t x_t (x) B_t``, ``y_t = S C_t + D x_t``
  (``kernels.ssd``); the gate BEFORE the norm, the norm by group: ``y_t =
  RMSNorm_grouped(y_t * silu(z_t); w_norm)`` over ``G`` groups of ``H P / G``
  channels; output ``y_t W_out``. ``W_in`` is stored as two matrices, ``[z |
  xBC]`` and the ``H`` columns of ``dt`` (whose product leaves in float32: it
  is an exponent's scale).
- *``*``, attention*: ``q, k, v = u W_q, u W_k, u W_v``, heads of ``head_dim``,
  causal softmax attention at ``head_dim^-0.5``, ``W_o``. No bias, no rotary
  embedding. (``W_q`` is stored by output feature, ``[heads x head_dim,
  hidden]``: ``serving.decode._mixer_qkv``.)
- *``E``, routed FFN*: ``s = sigmoid(u W_r)`` in float32; the ``top_k`` largest
  of ``s + b`` (``b`` the selection bias) are picked, a picked expert's weight
  is ``routed_scaling_factor * s_e / sum_picked s``; an expert is two
  matrices, ``relu(u W_up)^2 W_down`` (``W_up`` stored by output unit, ``[I,
  hidden]``: ``kernels.moe_ffn``); plus one shared expert of the same form
  every token runs. ``n_routed_experts`` is what THIS
  chip holds, ids ``first_held_expert .. + n_routed_experts`` of a router
  ``router_experts`` wide; what the absent experts would add is left out.

The pattern is read as UNITS of ``M``, optionally ``*``, then ``E`` (the
published 52 blocks are 23 units, six of them with an attention block): the
step programs scan the units, an attention block under a ``lax.cond`` on the
unit's place in the KV pool (``attn_at``, -1: none). What the cache holds of a
sequence: a row a token in each ATTENTION block's pool layer, and a Mamba-2
block's state and its convolution's last 3 inputs in the store by slot.

Parameters are stacked by kind, built in their dtype by one jitted call from
the seed: Normal(0, 0.02), norm weights 1, the selection bias Normal(0, 0.01)
(float32); Mamba-2's own (float32 but the convolution): the depthwise
convolution's weight ``U(-conv^-0.5, conv^-0.5)``, its bias 0, ``A_log = log
U(1, 16)``, ``D = 1``, ``dt_bias = softplus^-1(max(exp(U(log time_step_min,
log time_step_max)), time_step_floor))``.
"""
from __future__ import annotations

import functools
import math
import re
from dataclasses import dataclass
from typing import NamedTuple

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

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


class Ssd(NamedTuple):
    """A Mamba-2 layer's static numbers for the step programs
    (``config.ssd``): heads, a head's channels, groups, the state size, the
    convolution's width, which implementation of the recurrence runs
    (``decode_attention``) and the packed size of the engine's decode-only
    step program, whose spans are one token each (0: no such program)."""
    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int
    kernel: str
    decode_rows: int = 0


@dataclass
class NemotronHConfig:
    """The source's keys by the source's names, plus ``router_experts`` (the
    router's published width) and ``first_held_expert`` where
    ``n_routed_experts`` is a chip's share. ``dtype`` and
    ``decode_attention`` as ``LlamaConfig``."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128       # the published kernel's block: read by nothing
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_routed_experts: int = 128
    router_experts: int | None = None
    first_held_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    decode_attention: str = "pallas"
    dtype: str = "float32"

    rope_theta = None           # nothing is rotated

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers \
                or not re.fullmatch(r"(M\*?E)+", pattern):
            raise ValueError(
                "NemotronHConfig: hybrid_override_pattern is "
                f"num_hidden_layers ({self.num_hidden_layers}) letters in "
                "units of M, optionally *, then E (the step programs scan "
                f"the units), got {pattern!r}")
        if self.n_group != 1 or self.topk_group != 1 \
                or self.n_shared_experts != 1 or not (
                    0 <= self.first_held_expert
                    <= self.router_experts - self.n_routed_experts):
            raise ValueError(
                f"NemotronHConfig: n_group, topk_group and n_shared_experts "
                f"are 1, and the held experts {self.first_held_expert}..+"
                f"{self.n_routed_experts} must lie inside the router's "
                f"{self.router_experts}")
        if self.mamba_num_heads % self.n_groups \
                or self.num_attention_heads % self.num_key_value_heads \
                or self.tie_word_embeddings:
            raise ValueError(
                "NemotronHConfig: the Mamba-2 heads are whole groups, the "
                "query heads whole groups of the KV heads, and the head is "
                "untied")

    @property
    def rms_norm_eps(self):
        return self.layer_norm_epsilon

    @property
    def unit_attention(self):
        """A unit's place among the attention blocks (its layer of the KV
        pool), -1 for a unit without one."""
        out, n = [], 0
        for unit in re.findall(r"M\*?E", self.hybrid_override_pattern):
            out.append(n if "*" in unit else -1)
            n += "*" in unit
        return out

    @property
    def num_units(self):
        return len(self.unit_attention)

    @property
    def num_kv_layers(self):
        """Blocks that hold a row a token: the attention blocks."""
        return self.hybrid_override_pattern.count("*")

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self):
        """``[x | B | C]``: what the convolution runs over."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def ssd(self):
        return Ssd(int(self.mamba_num_heads), int(self.mamba_head_dim),
                   int(self.n_groups), int(self.ssm_state_size),
                   int(self.conv_kernel), self.decode_attention)

    @property
    def routing(self):
        """``models.deepseek_v2``'s tuple; the sigmoid rule is chosen by the
        tree's ``router_bias``."""
        return (int(self.num_experts_per_tok), bool(self.norm_topk_prob),
                int(self.n_group), int(self.topk_group),
                int(self.first_held_expert),
                float(self.routed_scaling_factor))


def nemotron_h_tiny(**kw):
    """Test / rehearsal config: hidden 64, the pattern ``MEM*EME`` (3 units,
    one with attention), Mamba-2 of 4 heads of 8 in 2 groups with a state of
    16, 4 / 2 attention heads of 16, a router over 8 experts of width 32 of
    which the first 4 are held, 2 a token, a shared expert of 48, vocab 256."""
    defaults = dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=7,
        hybrid_override_pattern="MEM*EME", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
        mamba_head_dim=8, n_groups=2, ssm_state_size=16, n_routed_experts=4,
        router_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=48, max_position_embeddings=128)
    defaults.update(kw)
    return NemotronHConfig(**defaults)


def _shapes(c):
    """``{stack: (normal, ones)}`` shapes of the three stacks: the Mamba-2
    blocks', the routed FFNs' (a unit each) and the attention blocks'."""
    hid, U, A = c.hidden_size, c.num_units, c.num_kv_layers
    nq, nkv = (n * c.head_dim for n in (c.num_attention_heads,
                                        c.num_key_value_heads))
    E, I, Is = (c.n_routed_experts, c.moe_intermediate_size,
                c.moe_shared_expert_intermediate_size)
    return dict(
        ssd=(dict(ssd_in=(U, hid, c.d_inner + c.conv_channels),
                  ssd_dt=(U, hid, c.mamba_num_heads),
                  ssd_out=(U, c.d_inner, hid)),
             dict(ln=(U, hid), ssd_norm=(U, c.d_inner))),
        moe=(dict(router=(U, hid, c.router_experts),
                  w_up=(U, E, I, hid), w_down=(U, E, I, hid),
                  ws_up=(U, hid, Is), ws_down=(U, Is, hid)),
             dict(moe_ln=(U, hid))),
        attn=(dict(wq=(A, nq, hid), wk=(A, hid, nkv), wv=(A, hid, nkv),
                   wo=(A, nq, hid)),
              dict(ln=(A, hid))))


class NemotronHForCausalLM(nn.Layer):
    """One-mixer-a-block LM, parameters stacked by kind. ``forward(input_ids)``
    returns logits; ``generate`` runs the serving engine, as
    ``LlamaForCausalLM.generate`` does."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = c = config
        shapes = _shapes(c)
        dt = dtype_mod.to_jax_dtype(c.dtype)
        f32 = jnp.float32
        U, H = c.num_units, c.mamba_num_heads

        def draw(key, shape, std):
            # (a stack is drawn a layer at a time: models.deepseek_v2)
            if len(shape) >= 3:
                return jax.lax.map(
                    lambda k: draw(k, shape[1:], std),
                    jax.random.split(key, shape[0]))
            return (std * jax.random.normal(key, shape, f32)).astype(dt)

        # every parameter in its own dtype, in ONE jitted call (as
        # ``models.olmoe``): never float32 first
        def build(key):
            k_e, k_h, k_b, k_c, k_a, k_t, *ks = jax.random.split(
                key, 6 + len(shapes))
            out = {}
            for k, (stack, (normal, ones)) in zip(ks, sorted(shapes.items())):
                keys = jax.random.split(k, len(normal))
                out[stack] = {n: draw(kk, s, 0.02) for kk, (n, s)
                              in zip(keys, sorted(normal.items()))}
                out[stack].update({n: jnp.ones(s, dt)
                                   for n, s in ones.items()})
            out["moe"]["router_bias"] = 0.01 * jax.random.normal(
                k_b, (U, c.router_experts), f32)
            bound = c.conv_kernel ** -0.5
            step = jnp.maximum(jnp.exp(jax.random.uniform(
                k_t, (U, H), f32, math.log(c.time_step_min),
                math.log(c.time_step_max))), c.time_step_floor)
            out["ssd"].update(
                ssd_conv=jax.random.uniform(
                    k_c, (U, c.conv_kernel, c.conv_channels), f32, -bound,
                    bound).astype(dt),
                ssd_conv_b=jnp.zeros((U, c.conv_channels), dt),
                ssd_A_log=jnp.log(jax.random.uniform(k_a, (U, H), f32, 1.0,
                                                     16.0)),
                ssd_D=jnp.ones((U, H), f32),
                ssd_dt_b=step + jnp.log(-jnp.expm1(-step)))
            embed = draw(k_e, (c.vocab_size, c.hidden_size), 0.02)
            head = draw(k_h, (c.hidden_size, c.vocab_size), 0.02)
            return embed, head, out

        embed, head, trees = build_once(config, build)(_random.next_key())
        self.embed_tokens = Parameter(embed)
        self.lm_head = Parameter(head)
        self.final_norm = Parameter(jnp.ones((c.hidden_size,), dt))
        self._names = {}
        for stack, tree in trees.items():
            self._names[stack] = tuple(sorted(tree))
            for name, value in tree.items():
                setattr(self, f"{stack}_{name}", Parameter(value))
        from ..serving.routing_record import RoutingRecord
        self.routing_record = RoutingRecord()

    def _tree(self, stack):
        return {n: getattr(self, f"{stack}_{n}").value
                for n in self._names[stack]}

    def decode_params(self):
        """``(params, tied)`` for the serving step programs: the routed FFNs'
        entries under their plain names ``[units, ...]`` (``router`` marks a
        routed model for the engine), the Mamba-2 blocks' under
        ``ssd_layers``, whose key chooses the forward
        (``serving.decode._mixer_span_forward``), the attention blocks' under
        ``attn_layers`` ``[attention blocks, ...]`` and ``attn_at``, a unit's
        place among them (-1: none)."""
        return dict(
            embed=self.embed_tokens.value, lm_head=self.lm_head.value,
            final_norm=self.final_norm.value, **self._tree("moe"),
            ssd_layers=self._tree("ssd"), attn_layers=self._tree("attn"),
            attn_at=jnp.asarray(self.config.unit_attention, jnp.int32)), False

    def forward(self, input_ids, return_router_picks=False):
        """Logits ``[B, S, V]``: the layers of whole-prompt prefill
        (``serving.decode._mixer_prefill_layers``), a sequence at a time;
        with ``return_router_picks`` also the experts every position picked
        in every routed FFN, ``[units, B, S, top_k]`` (the serving programs'
        own where they ran: ``DeepseekV2ForCausalLM.forward``)."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params, _ = self.decode_params()
        c = self.config
        logits, picks = _forward(
            params, ids, nh=c.num_attention_heads,
            nkv=c.num_key_value_heads, hd=c.head_dim,
            eps=float(c.rms_norm_eps), ssd=c.ssd, moe=c.routing,
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
    # of its own that was not taught these blocks: one tick a step
    generate = functools.partialmethod(_llama_generate, _decode_chunk=1)


@functools.partial(jax.jit, static_argnames=(
    "nh", "nkv", "hd", "eps", "ssd", "moe", "return_picks"))
def _forward(params, ids, *, nh, nkv, hd, eps, ssd, moe, return_picks):
    """(logits [B, S, V], picked experts [units, B, S, top_k] or None)."""
    from ..serving.decode import _mixer_prefill_layers, _rms
    S = ids.shape[1]
    lengths = jnp.full((1,), S, jnp.int32)

    def one_sequence(row):
        x = jnp.take(params["embed"], row[None], axis=0)
        x, _, _, _, stats = _mixer_prefill_layers(
            params, x, lengths, nh=nh, nkv=nkv, hd=hd, eps=eps, ssd=ssd,
            moe=moe, return_picks=return_picks)
        x = _rms(x[0], params["final_norm"], eps)
        return (jnp.einsum("sh,hv->sv", x, params["lm_head"]),
                stats[1][:, 0] if return_picks else None)

    logits, picks = jax.lax.map(one_sequence, ids)
    return logits, (None if picks is None else jnp.moveaxis(picks, 0, 1))
