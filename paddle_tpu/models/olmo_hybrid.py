"""Olmo Hybrid (``model_type`` ``olmo_hybrid``; allenai/Olmo-Hybrid-7B): a
decoder whose layers come in periods of linear-attention (Gated DeltaNet,
arXiv:2412.06464) layers and then one full-attention layer, each sub-layer's
OUTPUT normalised before the residual add. Served, not trained.

Both kinds of layer, ``x`` the residual stream, no bias:

    x = x + RMSNorm(Mixer(x); attn_out_ln)
    x = x + RMSNorm(W_down(silu(x W_gate) * (x W_up)); ffn_out_ln)

Full-attention mixer: ``q, k, v = x W_q, x W_k, x W_v``; ``q = RMSNorm(q;
q_norm)``, ``k = RMSNorm(k; k_norm)`` over the whole projection; heads of
``head_dim``; NO rotary embedding (``rope_parameters.rope_theta`` is null in
the published config and is read as written); causal softmax attention;
``W_o``. The cache holds ``k`` after its norm and ``v``.

Gated DeltaNet mixer, a token ``t``, a head of ``linear_num_value_heads``:

- ``u_t = x_t [W_q | W_k | W_v]``; a depthwise causal convolution of width
  ``linear_conv_kernel_dim`` and SiLU a channel, rows before the sequence's
  start zero; split into ``q_t, k_t`` (``linear_key_head_dim`` a head) and
  ``v_t`` (``linear_value_head_dim``);
- ``q_t = q_t / sqrt(|q_t|^2 + 1e-6) * dk^-0.5``, ``k_t`` likewise without
  the scale;
- ``beta_t = sigmoid(x_t W_b)``, doubled with ``linear_allow_neg_eigval``;
  ``g_t = -exp(A_log) * softplus(x_t W_a + dt_bias)`` in float32;
- the state ``S`` (``dk x dv`` a head, float32, zero at a sequence's start):
  ``S = exp(g_t) S``; ``r = v_t - S^T k_t``; ``S = S + k_t (beta_t r)^T``;
  ``o_t = S^T q_t`` (``kernels.gated_delta_rule``);
- ``y_t = RMSNorm(o_t; o_norm) * silu(x_t W_z)`` a head (one weight vector
  for all heads); ``concat_heads(y_t) W_o``.

What the cache holds of a sequence a linear layer: ``S`` and the convolution's
last ``width - 1`` inputs. Nothing grows with the length.

Parameters are stacked by layer KIND and built in their dtype by one jitted
call from the seed: the full layers' under the plain names ``[periods, ...]``,
the linear layers' under ``linear_layers``, one tree ``[periods, ...]`` for each
place in the period (``W_q | W_k | W_v`` one matrix ``gdn_wqkv``, ``W_a | W_b``
one ``gdn_wab``).
Normal(0, 0.02), norm weights 1, and for the decay FLA's own initialisation
(float32): ``A_log = log U(0, 16)``, ``dt_bias = softplus^-1(exp(U(log 0.001,
log 0.1)))``. The layer bodies are the serving programs' own
(``serving.decode._decoder_layer`` / ``_gdn_layer``): both are ONE body, a
mixer and then an FFN (``serving.decode._mixer_ffn_layer``), which this tree
shares with ``models.qwen3_next``'s; what the tree holds (``attn_out_ln`` /
``ffn_out_ln``, dense ``w_gate`` / ``w_up`` / ``w_down``, whole-projection
``q_norm``) chooses this model's variant of it.
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

LINEAR, FULL = "linear_attention", "full_attention"


class Gdn(NamedTuple):
    """A hybrid model's static numbers for the step programs
    (``config.gdn``): a linear layer's (value) heads, a head's key and value
    widths, the convolution's width, whether ``beta`` is doubled, and which
    implementation of the delta rule runs (``decode_attention``); then what
    only some hybrids have: ``key_heads``, where q and k come at fewer heads
    than v (0: as many; value head ``h`` reads key head ``h // (heads /
    key_heads)``), and the packed size of the engine's decode-only step
    program, whose spans are one token each (0: no such program; the engine
    sets it, as it does the Mamba stores')."""
    heads: int
    dk: int
    dv: int
    conv: int
    neg_eigval: bool
    kernel: str
    key_heads: int = 0
    decode_rows: int = 0


def _period():
    return [LINEAR] * 3 + [FULL]


@dataclass
class OlmoHybridConfig:
    """The source's keys by the source's names. ``dtype`` and
    ``decode_attention`` as ``LlamaConfig``."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    layer_types: list = field(default_factory=lambda: _period() * 8)
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float | None = None
    tie_word_embeddings: bool = False
    decode_attention: str = "pallas"
    dtype: str = "float32"

    def __post_init__(self):
        types = list(self.layer_types)
        per = types.index(FULL) + 1 if FULL in types else 0
        if per < 2 or len(types) != self.num_hidden_layers \
                or len(types) % per \
                or types != ([LINEAR] * (per - 1) + [FULL]) * (len(types)
                                                               // per):
            raise ValueError(
                f"OlmoHybridConfig: layer_types must be num_hidden_layers "
                f"({self.num_hidden_layers}) entries in whole periods of "
                f"linear_attention layers and then one full_attention "
                f"layer, got {types}")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError(
                "OlmoHybridConfig: a linear layer's key heads and value "
                "heads are one count here (no grouped values)")
        if self.rope_theta is not None:
            raise ValueError(
                "OlmoHybridConfig: the full layers rotate nothing "
                "(rope_parameters.rope_theta is null)")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def linear_per_period(self):
        return list(self.layer_types).index(FULL)

    @property
    def num_kv_layers(self):
        """Layers that hold keys and values: the full-attention ones."""
        return self.num_hidden_layers // (self.linear_per_period + 1)

    @property
    def num_linear_layers(self):
        return self.num_hidden_layers - self.num_kv_layers

    @property
    def gdn(self):
        return Gdn(self.linear_num_value_heads, self.linear_key_head_dim,
                   self.linear_value_head_dim, self.linear_conv_kernel_dim,
                   bool(self.linear_allow_neg_eigval),
                   self.decode_attention)

    @property
    def conv_channels(self):
        return self.linear_num_key_heads * self.linear_key_head_dim * 2 \
            + self.linear_num_value_heads * self.linear_value_head_dim


def olmo_hybrid_tiny(**kw):
    """Test / rehearsal config: hidden 64, 4 heads of 16, 2 periods (6
    linear layers and 2 full), linear heads of 8 and 16, vocab 256."""
    defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    num_hidden_layers=8, num_attention_heads=4,
                    num_key_value_heads=4, layer_types=_period() * 2,
                    linear_num_key_heads=4, linear_num_value_heads=4,
                    linear_key_head_dim=8, linear_value_head_dim=16,
                    max_position_embeddings=128)
    defaults.update(kw)
    return OlmoHybridConfig(**defaults)


def _param_shapes(c):
    """``(normal, ones, linear normal, linear ones)`` name -> shape; the
    linear ones are ONE place of the period's, ``[periods, ...]``."""
    H, I, V = c.hidden_size, c.intermediate_size, c.vocab_size
    P = c.num_kv_layers
    nq = c.num_attention_heads * c.head_dim
    nkv = c.num_key_value_heads * c.head_dim
    hl = c.linear_num_value_heads
    C, vd = c.conv_channels, hl * c.linear_value_head_dim
    normal = dict(embed_tokens=(V, H), wq=(P, H, nq), wk=(P, H, nkv),
                  wv=(P, H, nkv), wo=(P, nq, H), w_gate=(P, H, I),
                  w_up=(P, H, I), w_down=(P, I, H))
    ones = dict(q_norm=(P, nq), k_norm=(P, nkv), attn_out_ln=(P, H),
                ffn_out_ln=(P, H), final_norm=(H,))
    if not c.tie_word_embeddings:
        normal["lm_head"] = (H, V)
    lin = dict(gdn_wqkv=(P, H, C), gdn_wz=(P, H, vd),
               gdn_wab=(P, H, 2 * hl),
               gdn_conv=(P, c.linear_conv_kernel_dim, C),
               gdn_wo=(P, vd, H), w_gate=(P, H, I), w_up=(P, H, I),
               w_down=(P, I, H))
    lin_ones = dict(gdn_o_norm=(P, c.linear_value_head_dim),
                    attn_out_ln=(P, H), ffn_out_ln=(P, H))
    return normal, ones, lin, lin_ones


class OlmoHybridForCausalLM(nn.Layer):
    """Decoder-only LM of linear-attention and full-attention layers,
    parameters stacked by layer kind. ``forward(input_ids)`` returns logits;
    ``generate`` runs the serving engine, as ``LlamaForCausalLM.generate``
    does."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        normal, ones, lin, lin_ones = _param_shapes(config)
        dt = dtype_mod.to_jax_dtype(config.dtype)
        gate = (config.num_kv_layers, config.linear_num_value_heads)
        places = config.linear_per_period

        # every parameter in its own dtype, in ONE jitted call (as
        # ``models.olmoe``): never float32 first
        def build(key):
            def draw(key, shapes):
                keys = jax.random.split(key, len(shapes))
                return {n: (0.02 * jax.random.normal(k, s, jnp.float32)
                            ).astype(dt)
                        for k, (n, s) in zip(keys, sorted(shapes.items()))}

            def linear(key):
                k_w, k_a, k_dt = jax.random.split(key, 3)
                out = draw(k_w, lin)
                out.update({n: jnp.ones(s, dt) for n, s in lin_ones.items()})
                # the decay, FLA's initialisation, float32 whatever the dtype
                out["gdn_A_log"] = jnp.log(jax.random.uniform(
                    k_a, gate, jnp.float32, 1e-6, 16.0))
                step = jnp.exp(jax.random.uniform(
                    k_dt, gate, jnp.float32, math.log(0.001), math.log(0.1)))
                out["gdn_dt_bias"] = step + jnp.log(-jnp.expm1(-step))
                return out

            k_full, *k_lin = jax.random.split(key, 1 + places)
            out = draw(k_full, normal)
            out.update({n: jnp.ones(s, dt) for n, s in ones.items()})
            return out, tuple(linear(k) for k in k_lin)

        full, linear = build_once(config, build)(_random.next_key())
        for name, value in full.items():
            setattr(self, name, Parameter(value))
        for j, tree in enumerate(linear):
            for name, value in tree.items():
                setattr(self, f"linear{j}_{name}", Parameter(value))
        self._linear_names = tuple(sorted(linear[0]))
        if config.tie_word_embeddings:
            self.lm_head = None

    def decode_params(self):
        """``(params, tied)`` for the serving step programs: the raw-array
        tree whose keys choose the layer bodies (``linear_layers``: the
        periods of Gated DeltaNet layers; ``attn_out_ln``: the norm on each
        sub-layer's output; ``q_norm`` / ``k_norm``: the QK-norm)."""
        names = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "w_gate",
                 "w_up", "w_down", "attn_out_ln", "ffn_out_ln",
                 "final_norm")
        p = {n: getattr(self, n).value for n in names}
        p["linear_layers"] = tuple(
            {n: getattr(self, f"linear{j}_{n}").value
             for n in self._linear_names}
            for j in range(self.config.linear_per_period))
        p["embed"] = self.embed_tokens.value
        p["lm_head"] = (self.embed_tokens.value if self.lm_head is None
                        else self.lm_head.value)
        return p, self.lm_head is None

    def forward(self, input_ids):
        """Logits ``[B, S, V]``: the layers of whole-prompt prefill
        (``serving.decode._hybrid_prefill_layers``) over full-length rows."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params, tied = self.decode_params()
        c = self.config
        return Tensor(_hybrid_forward(
            params, ids, nh=c.num_attention_heads,
            nkv=c.num_key_value_heads, hd=c.head_dim,
            eps=float(c.rms_norm_eps), tied=tied, gdn=c.gdn))

    def num_params(self):
        import numpy as np
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # the engine's fused decode tail (decode_chunk > 1) has a layer body
    # of its own that was not taught these layers: one tick a step
    generate = functools.partialmethod(_llama_generate, _decode_chunk=1)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "hd", "eps",
                                             "tied", "gdn"))
def _hybrid_forward(params, ids, *, nh, nkv, hd, eps, tied, gdn):
    from ..serving.decode import _hybrid_prefill_layers
    x = jnp.take(params["embed"], ids, axis=0)
    lengths = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
    x, *_ = _hybrid_prefill_layers(params, x, lengths, nh=nh, nkv=nkv, hd=hd,
                                   eps=eps, gdn=gdn)
    x = _rms(x, params["final_norm"], eps)
    head = params["lm_head"].T if tied else params["lm_head"]
    return jnp.einsum("bsh,hv->bsv", x, head)
