"""GLM-5.2 (``model_type`` ``glm_moe_dsa``; zai-org/GLM-5.2): DeepSeek-V3's
block (latent attention with a query down-projection; leading dense layers,
then a shared expert plus routed experts under a sigmoid router with a
selection bias) with DeepSeek-V3.2's learned sparse attention, an indexer in
some layers only. Served, not trained. Built on ``models.deepseek_v2``'s
pieces; what that file says of the latent attention, the two stacks of layers,
a chip's share of the experts and the routing record holds here and is not
repeated.

What differs, per layer (``h`` the normalised input, ``c_q`` the query's
normalised down-projection):

- **the selected set** ``S_t``. ``indexer_types[l]`` is ``"full"`` or
  ``"shared"``. A ``full`` layer has an indexer: ``q^I_t = c_q_t W^I_qb`` as
  ``index_n_heads`` heads of ``index_head_dim``, ``k^I_s = LayerNorm(h_s
  W^I_k)`` (weight, bias; one head), the first ``qk_rope_head_dim`` values of
  both rotated by the layer's RoPE, ``w_t = h_t W^I_w * heads^-0.5 *
  dim^-0.5``; ``I[t, s] = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)`` for ``s <=
  t`` in float32, and ``S_t`` is the ``min(index_topk, t + 1)`` positions
  with the largest ``I[t, :]``. A ``shared`` layer has no indexer and takes
  the nearest ``full`` layer's set before it, unchanged (the first layer is
  ``full``). Attention is the latent one over ``s`` in ``S_t`` only.
- what a cache holds of a token: the latent row, every layer, and ``k^I``,
  a ``full`` layer: two paged caches under one block table
  (``serving.block_manager``: the pool's V side).
- **the router**: ``s = sigmoid(g W_r)``, the ``num_experts_per_tok`` largest
  of ``s + b`` (``router_bias``, DeepSeek-V3's ``e_score_correction_bias``),
  weights the unbiased ``s`` of the picked, divided by their sum over all
  picks and times ``routed_scaling_factor`` (``kernels.moe_ffn``).
  ``n_group`` is 1: DeepSeek-V3's group rule under a bias (the sum of a
  group's two best) is not implemented, and the config refuses it.
- RoPE is plain (``rope_type`` default, no YaRN), pairs half-split as
  ``models.deepseek_v2`` (the published checkpoint's are interleaved: with
  random weights a relabelling of columns, of ``W^I_qb`` / ``W^I_k`` too).

Not here: the multi-token-prediction layer (``num_nextn_predict_layers`` must
be 0) and the published inference kernel's Hadamard rotation and FP8
quantisation of the index vectors (an orthogonal rotation leaves every ``q .
k``; the quantisation is that kernel's storage choice).

Weights are Normal(0, 0.02), norm weights 1, LayerNorm bias 0, ``router_bias``
Normal(0, 0.01) (so that it decides near-ties and a test can see it), built in
their dtype by one jitted call.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core import random as _random
from ..core import dtype as dtype_mod
from ..core.tensor import Tensor
from ..nn.layer import Parameter
from .deepseek_v2 import (_ATTN, _FFN, _SHARED, DeepseekV2ForCausalLM, Mla,
                          _param_shapes, rope_tables)
from .llama import _rms, build_once
from .llama import generate as _llama_generate

_INDEXER = ("idx_wq_b", "idx_wk", "idx_k_ln_w", "idx_k_ln_b", "idx_w")


class Dsa(NamedTuple):
    """Sparse attention's static numbers for the step programs
    (``config.dsa``): the indexer's ``heads`` of ``dim``, of which the first
    ``rope`` values are rotated, the set's size ``topk``, the index key's
    LayerNorm ``eps``, and the ``layers`` that have an indexer."""
    heads: int
    dim: int
    rope: int
    topk: int
    eps: float
    layers: int


def published_indexer_types(layers, offset=3, freq=4):
    """The published list's rule (``index_skip_topk_offset`` 3,
    ``index_topk_freq`` 4): the first ``offset`` layers are ``full``, then
    every ``freq``-th, ``offset + freq - 1, offset + 2 freq - 1, ..``."""
    return ["full" if l < offset or (l - offset) % freq == freq - 1
            else "shared" for l in range(layers)]


@dataclass
class GlmMoeDsaConfig:
    """The source's keys by the source's names, plus ``router_experts`` and
    ``first_held_expert`` (``models.deepseek_v2``). ``indexer_types`` is the
    list of the depth held (None: the published rule)."""
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 256
    router_experts: int | None = None
    first_held_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_types: list | None = None
    index_layer_norm_eps: float = 1e-6
    num_nextn_predict_layers: int = 0
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    rope_parameters: dict = field(default_factory=lambda: dict(
        rope_theta=8000000, rope_type="default"))
    tie_word_embeddings: bool = False
    decode_attention: str = "pallas"
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        if self.indexer_types is None:
            self.indexer_types = published_indexer_types(
                self.num_hidden_layers)
        self.indexer_types = list(self.indexer_types)
        if self.moe_layer_freq != 1 or not (
                0 <= self.first_k_dense_replace < self.num_hidden_layers):
            raise ValueError(
                "GlmMoeDsaConfig: the layers are first_k_dense_replace "
                "dense ones, then expert layers (moe_layer_freq 1), at "
                "least one of them")
        if self.n_group != 1 or self.topk_group != 1 or not (
                0 <= self.first_held_expert
                <= self.router_experts - self.n_routed_experts):
            raise ValueError(
                f"GlmMoeDsaConfig: n_group and topk_group are 1 (the group "
                f"rule of a router with a bias is not implemented), and the "
                f"held experts {self.first_held_expert}..+"
                f"{self.n_routed_experts} must lie inside the router's "
                f"{self.router_experts}")
        if len(self.indexer_types) != self.num_hidden_layers \
                or set(self.indexer_types) - {"full", "shared"} \
                or self.indexer_types[0] != "full":
            raise ValueError(
                "GlmMoeDsaConfig: indexer_types is one of 'full' / 'shared' "
                "a layer, and the first layer, which has no layer before it "
                "to borrow a selection from, is 'full'")
        if self.num_nextn_predict_layers or (
                self.rope_parameters.get("rope_type", "default") != "default"
        ) or self.index_head_dim <= self.qk_rope_head_dim:
            raise ValueError(
                "GlmMoeDsaConfig: no multi-token-prediction layer is held "
                "(num_nextn_predict_layers 0), RoPE is plain, and an index "
                "head is wider than its rotated part (qk_rope_head_dim)")

    @property
    def rope_theta(self):
        return float(self.rope_parameters["rope_theta"])

    @property
    def head_dim(self):
        """Width of a query / key head: the rope-free and the rope part."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def mla(self):
        return Mla(self.kv_lora_rank, self.qk_nope_head_dim,
                   self.qk_rope_head_dim, self.v_head_dim,
                   float(self.head_dim ** -0.5), None)

    @property
    def dsa(self):
        return Dsa(int(self.index_n_heads), int(self.index_head_dim),
                   int(self.qk_rope_head_dim), int(self.index_topk),
                   float(self.index_layer_norm_eps),
                   self.indexer_types.count("full"))

    @property
    def routing(self):
        """``models.deepseek_v2``'s tuple; the sigmoid rule is chosen by the
        tree's ``router_bias``."""
        return (int(self.num_experts_per_tok), bool(self.norm_topk_prob),
                int(self.n_group), int(self.topk_group),
                int(self.first_held_expert),
                float(self.routed_scaling_factor))

    def indexer_places(self):
        """(dense stack's, expert stack's) ``(idx_layer, idx_slot)`` int32
        arrays: a layer's place in the index-key pool (-1: it borrows) and
        its indexer's place in its stack's weights."""
        out, pool_layer, first = [], 0, 0
        for n in (self.first_k_dense_replace,
                  self.num_hidden_layers - self.first_k_dense_replace):
            layer, slot, held = [], [], 0
            for kind in self.indexer_types[first:first + n]:
                full = kind == "full"
                layer.append(pool_layer if full else -1)
                slot.append(held if full else max(held - 1, 0))
                pool_layer, held = pool_layer + full, held + full
            out.append((np.asarray(layer, np.int32),
                        np.asarray(slot, np.int32)))
            first += n
        return out


def glm_moe_dsa_tiny(**kw):
    """Test / rehearsal config: hidden 64, 4 heads (nope 16, rope 8, v 16),
    latent 32 / 48, 1 dense + 4 expert layers ``[full, shared, shared, full,
    shared]``, an indexer of 4 heads of 16 that selects 8, a router over 8
    experts of width 32 of which the first 4 are held, 2 a token, vocab 256."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=4, router_experts=8,
        n_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
        index_n_heads=4, index_head_dim=16, index_topk=8,
        indexer_types=["full", "shared", "shared", "full", "shared"],
        max_position_embeddings=128,
        rope_parameters=dict(rope_theta=10000, rope_type="default"))
    defaults.update(kw)
    return GlmMoeDsaConfig(**defaults)


def _shapes(c):
    """(normal, ones, zeros, bias) shapes: ``models.deepseek_v2``'s and the
    indexers' (stacked over the layers of a stack that have one), and the
    routers' selection bias."""
    normal, ones = _param_shapes(c)
    zeros = {}
    hi, d = c.index_n_heads, c.index_head_dim
    for prefix, (layer, _) in zip(("dense_", ""), c.indexer_places()):
        n = int((layer >= 0).sum())
        if not n:
            continue
        normal.update({
            prefix + "idx_wq_b": (n, c.q_lora_rank, hi * d),
            prefix + "idx_wk": (n, c.hidden_size, d),
            prefix + "idx_w": (n, c.hidden_size, hi)})
        ones[prefix + "idx_k_ln_w"] = (n, d)
        zeros[prefix + "idx_k_ln_b"] = (n, d)
    bias = {"router_bias": (normal["router"][0], c.router_experts)}
    return normal, ones, zeros, bias


class GlmMoeDsaForCausalLM(nn.Layer):
    """Decoder-only LM with latent attention over a learned selection and a
    shared + routed FFN under a sigmoid router, parameters stacked over
    layers. ``forward(input_ids)`` returns logits; ``generate`` runs the
    serving engine."""

    def __init__(self, config: GlmMoeDsaConfig):
        super().__init__()
        self.config = config
        normal, ones, zeros, bias = _shapes(config)
        dt = dtype_mod.to_jax_dtype(config.dtype)

        def draw(key, shape, std):
            # (a stack is drawn a layer at a time: models.deepseek_v2)
            if len(shape) >= 3:
                return jax.lax.map(
                    lambda k: draw(k, shape[1:], std),
                    jax.random.split(key, shape[0]))
            return (std * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dt)

        def build(key):
            drawn = sorted(normal.items()) + sorted(bias.items())
            keys = jax.random.split(key, len(drawn))
            out = {n: draw(k, s, 0.01 if n in bias else 0.02)
                   for k, (n, s) in zip(keys, drawn)}
            out.update({n: jnp.ones(s, dt) for n, s in ones.items()})
            out.update({n: jnp.zeros(s, dt) for n, s in zeros.items()})
            return out

        built = build_once(config, build)(_random.next_key())
        for name, value in built.items():
            setattr(self, name, Parameter(value))
        if config.tie_word_embeddings:
            self.lm_head = None
        from ..serving.routing_record import RoutingRecord
        self.routing_record = RoutingRecord()

    def decode_params(self):
        """``models.deepseek_v2``'s tree plus, a stack, ``idx_layer`` /
        ``idx_slot`` (a layer's places: ``GlmMoeDsaConfig.indexer_places``),
        the indexers' weights where the stack has any, and the expert
        stack's ``router_bias``."""
        dense_at, expert_at = self.config.indexer_places()

        def stack(prefix, names, places):
            t = {n: getattr(self, prefix + n).value for n in names}
            t["idx_layer"], t["idx_slot"] = (jnp.asarray(a) for a in places)
            if hasattr(self, prefix + "idx_wk"):
                t.update({n: getattr(self, prefix + n).value
                          for n in _INDEXER})
            return t

        p = stack("", _ATTN + _FFN + _SHARED + ("router", "router_bias"),
                  expert_at)
        p["final_norm"] = self.final_norm.value
        if self.config.first_k_dense_replace:
            p["dense_layers"] = stack("dense_", _ATTN + _FFN, dense_at)
        p["embed"] = self.embed_tokens.value
        p["lm_head"] = (self.embed_tokens.value if self.lm_head is None
                        else self.lm_head.value)
        return p, self.lm_head is None

    def forward(self, input_ids, return_router_picks=False):
        """As ``DeepseekV2ForCausalLM.forward``: logits ``[B, S, V]`` and,
        with ``return_router_picks``, the experts every position picked in
        every expert layer (the serving programs' own where they ran)."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params, tied = self.decode_params()
        c = self.config
        logits, picks = _forward(
            params, ids, nh=c.num_attention_heads, eps=float(c.rms_norm_eps),
            theta=c.rope_theta, tied=tied, mla=c.mla, moe=c.routing,
            dsa=c.dsa, return_picks=bool(return_router_picks))
        if return_router_picks:
            served = self.served_router_picks(ids)
            if served is not None:
                picks = jnp.where(served >= 0, served, picks)
            return Tensor(logits), picks
        return Tensor(logits)

    served_router_picks = DeepseekV2ForCausalLM.served_router_picks
    num_params = DeepseekV2ForCausalLM.num_params

    # (one tick a step: models.deepseek_v2)
    generate = functools.partialmethod(_llama_generate, _decode_chunk=1)


@functools.partial(jax.jit, static_argnames=(
    "nh", "eps", "theta", "tied", "mla", "moe", "dsa", "return_picks"))
def _forward(params, ids, *, nh, eps, theta, tied, mla, moe, dsa,
             return_picks=False):
    """Plain whole-sequence forward, one sequence at a time, the serving
    programs' layer body in the expanded form with no cache
    (``serving.decode.sequence_attend_selected``): (logits [B, S, V], picked
    experts [L_expert, B, S, top_k] or None)."""
    from ..serving.decode import (_apply_rope, _decoder_layer, _indexers,
                                  _layer_stacks, sequence_attend_selected)
    S = ids.shape[1]
    sin, cos = rope_tables(S, mla.rope, theta, None)
    head = params["lm_head"].T if tied else params["lm_head"]

    def rope(x):
        return _apply_rope(x, sin, cos)

    def one_sequence(row):
        x = jnp.take(params["embed"], row[None], axis=0)
        sel, picks = jnp.zeros((1, S, S), bool), None
        for (_, keys, stack, experts), indexer in zip(
                _layer_stacks(params), _indexers(params)):
            routed = return_picks and experts is not None

            def layer(carry, lp):
                h, sel = carry
                lw = dict(zip(keys, lp))
                h, kv, stats = _decoder_layer(
                    h, lw, nh=nh, nkv=nh, hd=mla.nope + mla.rope, eps=eps,
                    rope=rope, attend=sequence_attend_selected(
                        lw, indexer, sel, rope, mla=mla, dsa=dsa),
                    mla=mla, moe=moe, experts=experts, return_picks=routed)
                return (h, kv[2]), (stats[1][0] if routed else None)

            (x, sel), p = jax.lax.scan(layer, (x, sel), stack)
            picks = p if experts is not None else picks
        x = _rms(x[0], params["final_norm"], eps)
        return jnp.einsum("sh,hv->sv", x, head), picks

    logits, picks = jax.lax.map(one_sequence, ids)
    return logits, (None if picks is None else jnp.moveaxis(picks, 0, 1))
