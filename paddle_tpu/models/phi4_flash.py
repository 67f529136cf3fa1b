"""Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``; microsoft/
Phi-4-mini-flash-reasoning): the SambaY decoder-hybrid-decoder of
arXiv:2507.06607 with differential attention (arXiv:2410.05258). Served, not
trained.

``L`` layers (32) in three runs. With ``x`` the residual stream, every layer:

    x = x + Mixer_l(LayerNorm(x; ln1))
    x = x + W_down (silu(x' W_gate) * (x' W_up)),  x' = LayerNorm(x; ln2)

(LayerNorm with weight and bias; ``W_1 = [W_gate | W_up]``, the gate the first
half, stored as two matrices). After the last layer ``LayerNorm(x; final)`` and
the tied head. No rotary embedding anywhere: the Mamba layers carry the order.

The mixer of layer ``l``: the SELF-DECODER, ``l < L/2`` (0..15): even ``l``
Mamba, odd ``l`` differential attention inside a window of ``sliding_window``
keys; the two MIDDLE layers: ``L/2`` (16) the Mamba layer whose scan output is
the memory ``m``, ``L/2 + 1`` (17) differential attention over everything
before, whose keys and values are THE cache; the CROSS-DECODER, ``l >= L/2 +
2`` (18..31): even ``l`` a Gated Memory Unit over ``m``, odd ``l`` differential
cross-attention over layer 17's keys and values.

- *Mamba* (Mamba-1, a token ``t``, input ``h_t``): ``[a_t | z_t] = h_t W_in``;
  ``c_t = silu(b_conv + sum_j w_conv[j] * a_{t-3+j})`` (depthwise, causal,
  zeros before the sequence); ``[r_t | B_t | C_t] = c_t W_x``; ``dt_t =
  softplus(r_t W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(dt_t A) * S_{t-1}
  + B_t (dt_t * c_t)``, ``S_{-1} = 0``; ``y_t = C_t . S_t + D * c_t``; output
  ``(y_t * silu(z_t)) W_out``. The middle Mamba layer also hands on ``m_t =
  y_t``, BEFORE the gate. State, ``dt``, ``A`` and the recurrence in float32
  (``kernels.selective_scan``; the state is stored ``[d_state, d_inner]``).
- *Differential attention*: ``[q | k | v] = h W_qkv + b``; heads of
  ``head_dim`` taken in adjacent PAIRS: differential head ``n`` has ``q1 =
  q[2n]``, ``q2 = q[2n+1]`` and the KV pair ``p = n // 2``: ``k1 = k[2p]``,
  ``k2 = k[2p+1]``, ``V = [v[2p] | v[2p+1]]``. ``P_i = softmax(q_i k_i^T *
  head_dim^-0.5)``, causal (and inside the window); ``lambda = exp(lq1 . lk1)
  - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``;
  ``o_n = (1 - lambda_init) RMSNorm((P1 - lambda P2) V; subln)``; output
  ``concat_n(o_n) W_o + b_o``. The cache holds ``k`` and ``v`` as projected.
- *Cross-attention*: ``q = h W_q + b`` only; ``k``, ``v`` are layer 17's.
- *GMU*: ``(m_t * silu(h_t W_1)) W_2``, ``m_t`` the same token's.

What the cache holds of a sequence: ONE layer of keys and values (layer 17's,
read by eight layers), the last ``sliding_window`` keys and values of every
window layer (a ring of blocks a slot, written and walked through
``serving.decode.ring_coords`` / ``ring_as_pool``, which ``models.
mimo_v2_flash``'s window layers share), and a Mamba layer's state and the
convolution's last 3 inputs.
The cross-decoder holds nothing, and its output is used at the LAST token of
a span only: the serving programs run it at one row a slot
(``serving.decode._sambay_span_forward``).

Parameters are stacked by layer kind and run: ``self_layers = (Mamba [L/4,
...], attention [L/4, ...])``, ``mid_layers = (Mamba, attention)`` unstacked,
``cross_layers = (GMU [L/4 - 1, ...], cross-attention [L/4 - 1, ...])``, built
in their dtype by one jitted call from the seed: Normal(0, 0.02), norm weights
1, biases 0; Mamba's own: the depthwise convolution's weight ``U(-d_conv^-0.5,
d_conv^-0.5)`` (a depthwise ``Conv1d``'s default, which Mamba leaves as it
is: its fan-in is ``d_conv``, so at Normal(0, 0.02) it would have a gain of
0.04 and the memory ``m`` would be of the order of a hundredth), ``A_log =
log(1..d_state)`` a channel, ``D = 1``, ``b_dt = softplus^-1(exp(U(log 0.001,
log 0.1)))`` (float32); the differential transformer's: the four ``lambda``
vectors Normal(0, 0.1) (float32).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as _random
from ..core import dtype as dtype_mod
from ..core.tensor import Tensor
from ..nn.layer import Parameter
from .llama import build_once
from .llama import generate as _llama_generate


class Ssm(NamedTuple):
    """The static numbers of the Mamba and window layers for the step
    programs (``config.ssm``): the convolution's width, the window, the rows
    of a window layer's ring a slot (0: a forward that keeps none; the engine
    sets it from its own geometry), which implementation of the scan and of
    the attention runs (``decode_attention``) and the packed size of the
    engine's decode-only step program, whose spans are one token each (0: no
    such program)."""
    conv: int
    window: int
    ring_rows: int
    kernel: str
    decode_rows: int = 0


@dataclass
class Phi4FlashConfig:
    """The source's keys by the source's names; the Mamba sizes are the
    family's (Mamba-1 as Samba uses it), which the source does not state.
    ``dtype`` and ``decode_attention`` as ``LlamaConfig``."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None
    decode_attention: str = "pallas"
    dtype: str = "float32"

    rope_theta = None           # nothing is rotated

    def __post_init__(self):
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8:
            raise ValueError(
                "Phi4FlashConfig: num_hidden_layers must be a multiple of 4, "
                "at least 8 (half of them the self-decoder's pairs, two "
                f"middle layers, the rest the cross-decoder's pairs), got "
                f"{self.num_hidden_layers}")
        if self.mb_per_layer != 2:
            raise ValueError(
                "Phi4FlashConfig: every second layer is a Mamba layer "
                f"(mb_per_layer 2), got {self.mb_per_layer}")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 \
                or (self.num_attention_heads // 2) % (
                    self.num_key_value_heads // 2):
            raise ValueError(
                "Phi4FlashConfig: differential attention pairs adjacent "
                "heads: even head counts, the query pairs a multiple of the "
                "KV pairs")
        if not self.tie_word_embeddings:
            raise ValueError("Phi4FlashConfig: the head is the embedding")
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def kernel_head_dim(self):
        """What the ragged kernel sees as a head: a KV pair."""
        return 2 * self.head_dim

    @property
    def rms_norm_eps(self):
        return self.layer_norm_eps

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def num_self_pairs(self):
        return self.num_hidden_layers // 4

    @property
    def num_kv_layers(self):
        """Layers that hold a row a token: the middle full layer."""
        return 1

    @property
    def num_window_layers(self):
        return self.num_self_pairs

    @property
    def num_ssm_layers(self):
        return self.num_self_pairs + 1

    @property
    def ssm(self):
        return Ssm(self.mamba_d_conv, self.sliding_window, 0,
                   self.decode_attention)


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def phi4_flash_tiny(**kw):
    """Test / rehearsal config: hidden 64, 4 / 2 heads of 16, 8 layers (2
    self pairs, the 2 middle layers, 1 cross pair), window 16, vocab 256."""
    defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    num_hidden_layers=8, num_attention_heads=4,
                    num_key_value_heads=2, sliding_window=16,
                    max_position_embeddings=128)
    defaults.update(kw)
    return Phi4FlashConfig(**defaults)


def _shapes(c):
    """name -> shape of one layer of each kind, as ``(normal, zeros, ones)``
    a kind; the MLP and the two norms are every kind's."""
    H, I, C = c.hidden_size, c.intermediate_size, c.d_inner
    N, rank, hd = c.mamba_d_state, c.mamba_dt_rank, c.head_dim
    nq, nkv = c.num_attention_heads * hd, c.num_key_value_heads * hd
    block = (dict(w_gate=(H, I), w_up=(H, I), w_down=(I, H)),
             dict(ln1_b=(H,), ln2_b=(H,)), dict(ln1_w=(H,), ln2_w=(H,)))

    def kind(normal, zeros=(), ones=()):
        return tuple({**b, **dict(extra)}
                     for b, extra in zip(block, (normal, zeros, ones)))

    attn = dict(wo=(nq, H))
    return dict(
        mamba=kind(dict(ssm_in=(H, 2 * C), ssm_conv=(c.mamba_d_conv, C),
                        ssm_x=(C, rank + 2 * N), ssm_dt=(rank, C),
                        ssm_out=(C, H)), dict(ssm_conv_b=(C,))),
        attn=kind(dict(wqkv=(H, nq + 2 * nkv), **attn),
                  dict(bqkv=(nq + 2 * nkv,), bo=(H,)),
                  dict(subln=(2 * hd,))),
        cross=kind(dict(wq=(H, nq), **attn), dict(bq=(nq,), bo=(H,)),
                   dict(subln=(2 * hd,))),
        gmu=kind(dict(gmu_in=(H, C), gmu_out=(C, H))))


class Phi4FlashForCausalLM(nn.Layer):
    """Decoder-hybrid-decoder LM, parameters stacked by layer kind and run.
    ``forward(input_ids)`` returns logits; ``generate`` runs the serving
    engine, as ``LlamaForCausalLM.generate`` does."""

    #: (prefix, kind, which layers) of the six stacks
    def _stacks(self):
        c = self.config
        P, L = c.num_self_pairs, c.num_hidden_layers
        return (("sm", "mamba", list(range(0, 2 * P, 2))),
                ("sa", "attn", list(range(1, 2 * P, 2))),
                ("mm", "mamba", 2 * P), ("ma", "attn", 2 * P + 1),
                ("cg", "gmu", list(range(2 * P + 2, L, 2))),
                ("ca", "cross", list(range(2 * P + 3, L, 2))))

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = c = config
        shapes = _shapes(c)
        dt = dtype_mod.to_jax_dtype(c.dtype)
        f32 = jnp.float32
        C, N, hd = c.d_inner, c.mamba_d_state, c.head_dim
        stacks = self._stacks()

        # every parameter in its own dtype, in ONE jitted call (as
        # ``models.olmoe``): never float32 first
        def build(key):
            def one(key, kind, layers):
                lead = () if isinstance(layers, int) else (len(layers),)
                normal, zeros, ones = shapes[kind]
                k_w, k_dt, k_lam, k_conv = jax.random.split(key, 4)
                keys = jax.random.split(k_w, len(normal))
                out = {n: (0.02 * jax.random.normal(k, lead + s, f32)
                           ).astype(dt)
                       for k, (n, s) in zip(keys, sorted(normal.items()))}
                out.update({n: jnp.zeros(lead + s, dt)
                            for n, s in zeros.items()})
                out.update({n: jnp.ones(lead + s, dt)
                            for n, s in ones.items()})
                if kind == "mamba":     # Mamba's own
                    bound = c.mamba_d_conv ** -0.5
                    out["ssm_conv"] = jax.random.uniform(
                        k_conv, out["ssm_conv"].shape, f32, -bound,
                        bound).astype(dt)
                    out["ssm_A_log"] = jnp.broadcast_to(
                        jnp.log(jnp.arange(1, N + 1, dtype=f32))[:, None],
                        lead + (N, C))
                    out["ssm_D"] = jnp.ones(lead + (C,), f32)
                    step = jnp.exp(jax.random.uniform(
                        k_dt, lead + (C,), f32, math.log(0.001),
                        math.log(0.1)))
                    out["ssm_dt_b"] = step + jnp.log(-jnp.expm1(-step))
                if kind in ("attn", "cross"):
                    out["lam"] = 0.1 * jax.random.normal(
                        k_lam, lead + (4, hd), f32)
                    out["lambda_init"] = jnp.asarray(
                        lambda_init(layers) if isinstance(layers, int)
                        else [lambda_init(l) for l in layers], f32)
                return out

            k_e, *ks = jax.random.split(key, 1 + len(stacks))
            embed = (0.02 * jax.random.normal(
                k_e, (c.vocab_size, c.hidden_size), f32)).astype(dt)
            return embed, [one(k, kind, layers)
                           for k, (_, kind, layers) in zip(ks, stacks)]

        embed, trees = build_once(config, build)(_random.next_key())
        self.embed_tokens = Parameter(embed)
        self.final_norm = Parameter(jnp.ones((c.hidden_size,), dt))
        self.final_norm_b = Parameter(jnp.zeros((c.hidden_size,), dt))
        self._names = {}
        for (prefix, _, _), tree in zip(stacks, trees):
            self._names[prefix] = tuple(sorted(tree))
            for name, value in tree.items():
                setattr(self, f"{prefix}_{name}", Parameter(value))
        self.lm_head = None

    def _tree(self, prefix):
        return {n: getattr(self, f"{prefix}_{n}").value
                for n in self._names[prefix]}

    def decode_params(self):
        """``(params, tied)`` for the serving step programs: the raw-array
        tree whose key ``self_layers`` chooses the forward
        (``serving.decode._sambay_span_forward``)."""
        return dict(
            embed=self.embed_tokens.value, lm_head=self.embed_tokens.value,
            final_norm=self.final_norm.value,
            final_norm_b=self.final_norm_b.value,
            self_layers=(self._tree("sm"), self._tree("sa")),
            mid_layers=(self._tree("mm"), self._tree("ma")),
            cross_layers=(self._tree("cg"), self._tree("ca"))), True

    def forward(self, input_ids):
        """Logits ``[B, S, V]``: the layers of whole-prompt prefill
        (``serving.decode._sambay_prefill_layers``) over full-length rows,
        the cross-decoder on every token."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params, _ = self.decode_params()
        c = self.config
        return Tensor(_forward(
            params, ids, nh=c.num_attention_heads,
            nkv=c.num_key_value_heads, hd=c.head_dim,
            eps=float(c.layer_norm_eps), ssm=c.ssm))

    def num_params(self):
        import numpy as np
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # the engine's fused decode tail (decode_chunk > 1) has a layer body
    # of its own that was not taught these layers: one tick a step
    generate = functools.partialmethod(_llama_generate, _decode_chunk=1)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "hd", "eps",
                                             "ssm"))
def _forward(params, ids, *, nh, nkv, hd, eps, ssm):
    from ..serving.decode import _final_norm, _sambay_prefill_layers
    x = jnp.take(params["embed"], ids, axis=0)
    lengths = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
    x, _, _, _ = _sambay_prefill_layers(
        params, x, lengths, nh=nh, nkv=nkv, hd=hd, eps=eps, ssm=ssm,
        narrow=False)
    return jnp.einsum("bsh,vh->bsv", _final_norm(params, x, eps),
                      params["embed"])
