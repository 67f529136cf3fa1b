"""Jamba (``model_type`` ``jamba``; ai21labs/AI21-Jamba2-3B): Mamba-1 layers
with one grouped-query attention layer a PERIOD (arXiv:2403.19887), a dense
SwiGLU after every mixer. Served, not trained.

``L`` layers (28) in periods of ``attn_layer_period`` (14). With ``x`` the
residual stream, RMSNorm with a weight and no bias, every layer ``l``:

    x = x + Mixer_l(RMSNorm(x; input_layernorm))
    x = x + W_down (silu(x' W_gate) * (x' W_up)),  x' = RMSNorm(x; pre_ff_layernorm)

then ``RMSNorm(x; final_layernorm)`` and the tied head. No rotary or other
positional term anywhere: the Mamba layers carry the order. ``Mixer_l`` is
attention where ``l % attn_layer_period == attn_layer_offset`` (layers 7 and
21), else Mamba. ``num_experts`` is 1: every layer's FFN is the dense MLP and
``expert_layer_period`` / ``expert_layer_offset`` select nothing.

- *Mamba* (Mamba-1, a token ``t``, input ``h_t``): ``[a_t | z_t] = h_t W_in``;
  ``c_t = silu(b_conv + sum_j w_conv[j] * a_{t-3+j})`` (depthwise, causal,
  zeros before the sequence); ``[r_t | B_t | C_t] = c_t W_x``; the family's
  three inner norms ``r_t = RMSNorm(r_t; dt_layernorm)``, ``B_t = RMSNorm(B_t;
  b_layernorm)``, ``C_t = RMSNorm(C_t; c_layernorm)``; ``dt_t = softplus(r_t
  W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(dt_t A) * S_{t-1} + B_t (dt_t
  * c_t)``, ``S_{-1} = 0``; ``y_t = C_t . S_t + D * c_t``; output ``(y_t *
  silu(z_t)) W_out``. State, ``dt``, ``A``, the inner norms and the
  recurrence in float32 (``kernels.selective_scan``; the state is stored
  ``[d_state, d_inner]``).
- *Attention*: ``q = h W_q`` (heads of ``head_dim``), ``k = h W_k``, ``v = h
  W_v`` (``num_key_value_heads`` heads), causal softmax of ``q k^T *
  head_dim^-0.5``, ``W_o``. No bias. The cache holds ``k``, ``v`` as projected.

What the cache holds of a sequence: a row a token in each ATTENTION layer's
pool layer (its count among them), and a Mamba layer's state and its
convolution's last 3 inputs in the store by slot (its count among the Mamba
layers).

Parameters are stacked by a layer's PLACE in the period, ``[periods, ...]``
each (``serving.decode``: a scan over the periods then cuts ONE layer's
weights out of each array): ``mamba_layers = (the places before the attention
layer, the places after it)``, ``attn_layers`` the attention layer's. Built in
their dtype by one jitted call from the seed: Normal(0, 0.02), norm weights 1
(the inner ones too); Mamba's own as ``models.phi4_flash``: the depthwise
convolution's weight ``U(-d_conv^-0.5, d_conv^-0.5)``, its bias 0, ``A_log =
log(1..d_state)`` a channel, ``D = 1``, ``b_dt = softplus^-1(exp(U(log 0.001,
log 0.1)))`` (float32).
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
from .llama import build_once
from .llama import generate as _llama_generate
from .phi4_flash import Phi4FlashForCausalLM, Ssm


@dataclass
class JambaConfig:
    """The source's keys by the source's names, plus ``head_dim`` (hidden /
    heads where the source gives none). ``dtype`` and ``decode_attention`` as
    ``LlamaConfig``."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    head_dim: int | None = None
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    decode_attention: str = "pallas"
    dtype: str = "float32"

    rope_theta = None           # nothing is rotated

    def __post_init__(self):
        P, off = self.attn_layer_period, self.attn_layer_offset
        if self.num_hidden_layers % P or not 0 <= off < P or P < 2:
            raise ValueError(
                "JambaConfig: num_hidden_layers is whole periods of "
                "attn_layer_period >= 2 layers with the attention layer at "
                f"attn_layer_offset inside it (the step programs scan the "
                f"periods), got {self.num_hidden_layers} layers, period {P}, "
                f"offset {off}")
        if self.num_experts != 1 or self.num_experts_per_tok != 1:
            raise ValueError(
                "JambaConfig: every FFN is the dense MLP (num_experts 1), "
                f"got {self.num_experts} experts")
        if not (self.mamba_conv_bias and not self.mamba_proj_bias
                and self.tie_word_embeddings):
            raise ValueError(
                "JambaConfig: the convolution has a bias, the projections "
                "none, and the head is the embedding")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                "JambaConfig: the query heads are whole groups of the KV "
                "heads")
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def num_periods(self):
        return self.num_hidden_layers // self.attn_layer_period

    @property
    def num_kv_layers(self):
        """Layers that hold a row a token: the attention layers."""
        return self.num_periods

    @property
    def num_ssm_layers(self):
        return self.num_hidden_layers - self.num_periods

    @property
    def ssm(self):
        """``models.phi4_flash.Ssm``: no window layer, no ring."""
        return Ssm(self.mamba_d_conv, 0, 0, self.decode_attention)


def jamba_tiny(**kw):
    """Test / rehearsal config: hidden 80, 8 layers in periods of 4 with the
    attention layer at 2 (two Mamba layers before it, one after), 5 query
    heads of 16 on 1 KV head, Mamba of 160 channels, dt_rank 5, vocab 256."""
    defaults = dict(vocab_size=256, hidden_size=80, intermediate_size=96,
                    num_hidden_layers=8, num_attention_heads=5,
                    num_key_value_heads=1, attn_layer_period=4,
                    attn_layer_offset=2, max_position_embeddings=128)
    defaults.update(kw)
    return JambaConfig(**defaults)


def _shapes(c):
    """name -> shape of one layer of each kind, as ``(normal, ones)`` a
    kind; the MLP and the two norms are both kinds'."""
    H, I, C = c.hidden_size, c.intermediate_size, c.d_inner
    N, rank = c.mamba_d_state, c.mamba_dt_rank
    nq, nkv = (n * c.head_dim for n in (c.num_attention_heads,
                                        c.num_key_value_heads))
    mlp = dict(w_gate=(H, I), w_up=(H, I), w_down=(I, H))
    norms = dict(ln1=(H,), ln2=(H,))
    return dict(
        mamba=(dict(mlp, ssm_in=(H, 2 * C), ssm_conv=(c.mamba_d_conv, C),
                    ssm_x=(C, rank + 2 * N), ssm_dt=(rank, C),
                    ssm_out=(C, H)),
               dict(norms, ssm_dt_ln=(rank,), ssm_b_ln=(N,), ssm_c_ln=(N,))),
        attn=(dict(mlp, wq=(H, nq), wk=(H, nkv), wv=(H, nkv), wo=(nq, H)),
              norms))


class JambaForCausalLM(nn.Layer):
    """Periods of Mamba layers around one attention layer, parameters stacked
    by a layer's place in the period. ``forward(input_ids)`` returns logits;
    ``generate`` runs the serving engine, as ``LlamaForCausalLM.generate``
    does."""

    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = c = config
        shapes = _shapes(c)
        dt = dtype_mod.to_jax_dtype(c.dtype)
        f32 = jnp.float32
        C, N, periods = c.d_inner, c.mamba_d_state, c.num_periods
        off = c.attn_layer_offset
        # a place of the period: its prefix and its kind
        self._places = tuple(
            ("at", "attn") if j == off else (f"m{j}", "mamba")
            for j in range(c.attn_layer_period))

        # every parameter in its own dtype, in ONE jitted call (as
        # ``models.olmoe``): never float32 first
        def build(key):
            def one(key, kind):
                lead = (periods,)
                normal, ones = shapes[kind]
                k_w, k_dt, k_conv = jax.random.split(key, 3)
                keys = jax.random.split(k_w, len(normal))
                out = {n: (0.02 * jax.random.normal(k, lead + s, f32)
                           ).astype(dt)
                       for k, (n, s) in zip(keys, sorted(normal.items()))}
                out.update({n: jnp.ones(lead + s, dt)
                            for n, s in ones.items()})
                if kind == "mamba":     # Mamba's own
                    bound = c.mamba_d_conv ** -0.5
                    out["ssm_conv"] = jax.random.uniform(
                        k_conv, out["ssm_conv"].shape, f32, -bound,
                        bound).astype(dt)
                    out["ssm_conv_b"] = jnp.zeros(lead + (C,), dt)
                    out["ssm_A_log"] = jnp.broadcast_to(
                        jnp.log(jnp.arange(1, N + 1, dtype=f32))[:, None],
                        lead + (N, C))
                    out["ssm_D"] = jnp.ones(lead + (C,), f32)
                    step = jnp.exp(jax.random.uniform(
                        k_dt, lead + (C,), f32, math.log(0.001),
                        math.log(0.1)))
                    out["ssm_dt_b"] = step + jnp.log(-jnp.expm1(-step))
                return out

            k_e, *ks = jax.random.split(key, 1 + len(self._places))
            embed = (0.02 * jax.random.normal(
                k_e, (c.vocab_size, c.hidden_size), f32)).astype(dt)
            return embed, [one(k, kind)
                           for k, (_, kind) in zip(ks, self._places)]

        embed, trees = build_once(config, build)(_random.next_key())
        self.embed_tokens = Parameter(embed)
        self.final_norm = Parameter(jnp.ones((c.hidden_size,), dt))
        self._names = {}
        for (prefix, _), tree in zip(self._places, trees):
            self._names[prefix] = tuple(sorted(tree))
            for name, value in tree.items():
                setattr(self, f"{prefix}_{name}", Parameter(value))
        self.lm_head = None

    def _tree(self, prefix):
        return {n: getattr(self, f"{prefix}_{n}").value
                for n in self._names[prefix]}

    def decode_params(self):
        """``(params, tied)`` for the serving step programs: the raw-array
        tree whose key ``mamba_layers`` chooses the forward
        (``serving.decode._jamba_span_forward``): the Mamba layers' trees of
        the places before the attention layer and of those after it, each
        ``[periods, ...]``, and the attention layer's under ``attn_layers``."""
        off = self.config.attn_layer_offset
        mamba = [self._tree(p) for p, kind in self._places if kind == "mamba"]
        return dict(
            embed=self.embed_tokens.value, lm_head=self.embed_tokens.value,
            final_norm=self.final_norm.value,
            mamba_layers=(tuple(mamba[:off]), tuple(mamba[off:])),
            attn_layers=self._tree("at")), True

    def forward(self, input_ids):
        """Logits ``[B, S, V]``: the layers of whole-prompt prefill
        (``serving.decode._jamba_prefill_layers``) over full-length rows."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params, _ = self.decode_params()
        c = self.config
        return Tensor(_forward(
            params, ids, nh=c.num_attention_heads,
            nkv=c.num_key_value_heads, hd=c.head_dim,
            eps=float(c.rms_norm_eps), ssm=c.ssm))

    num_params = Phi4FlashForCausalLM.num_params

    # the engine's fused decode tail (decode_chunk > 1) has a layer body
    # of its own that was not taught these layers: one tick a step
    generate = functools.partialmethod(_llama_generate, _decode_chunk=1)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "hd", "eps",
                                             "ssm"))
def _forward(params, ids, *, nh, nkv, hd, eps, ssm):
    from ..serving.decode import _jamba_prefill_layers, _rms
    x = jnp.take(params["embed"], ids, axis=0)
    lengths = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
    x, _, _, _ = _jamba_prefill_layers(
        params, x, lengths, nh=nh, nkv=nkv, hd=hd, eps=eps, ssm=ssm,
        narrow=False)
    return jnp.einsum("bsh,vh->bsv", _rms(x, params["final_norm"], eps),
                      params["embed"])
