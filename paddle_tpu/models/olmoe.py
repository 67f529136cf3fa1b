"""OLMoE (``model_type`` ``olmoe``; allenai/OLMoE-1B-7B-0125-Instruct): a
pre-norm decoder whose FFN is a dropless top-k mixture of experts and whose
attention normalises q and k before the rotary embedding.

Per layer, ``x`` the residual stream, no bias anywhere:

- ``h = RMSNorm(x; input_ln)``; ``q, k, v = h Wq, h Wk, h Wv``;
- QK-norm over the whole projection, before the heads are split:
  ``q = RMSNorm(q; q_norm)``, ``k = RMSNorm(k; k_norm)`` (``clip_qkv`` is
  null in the published config: no clamp);
- heads of ``head_dim``, RoPE (half-split layout), causal attention,
  ``x = x + attn Wo``;
- ``h = RMSNorm(x; post_ln)``; router probabilities ``softmax(h Wr)`` in
  float32; the ``num_experts_per_tok`` largest, used as they are
  (``norm_topk_prob`` false) or renormalised (true);
  ``x = x + sum_e p_e W_down,e (silu(h W_gate,e) * (h W_up,e))``.

Parameters are stacked over layers like ``LlamaForCausalLM``'s, so the
serving step programs scan them (``serving/decode.py``); the layer body is
the one the serving programs run (``decode._decoder_layer``), chosen by what
the parameter tree holds. The router's auxiliary losses exist only in
training and are not here: this model is served, not trained.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as _random
from ..core import dtype as dtype_mod
from ..core.tensor import Tensor
from ..nn.layer import Parameter
from .llama import _rms, _rope_tables, build_once
from .llama import generate as _llama_generate


@dataclass
class OlmoeConfig:
    """The source's keys by the source's names; ``intermediate_size`` is ONE
    expert's width. ``dtype`` and ``decode_attention`` as ``LlamaConfig``."""
    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = False
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    decode_attention: str = "pallas"
    dtype: str = "float32"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def olmoe_tiny(**kw):
    """Test / rehearsal config: hidden 64, 4 heads of 16, 2 layers, 8
    experts of width 32, 2 per token, vocab 256."""
    defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=4, num_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=128)
    defaults.update(kw)
    return OlmoeConfig(**defaults)


def _param_shapes(c):
    H, I, V, L = (c.hidden_size, c.intermediate_size, c.vocab_size,
                  c.num_hidden_layers)
    E, hd = c.num_experts, c.head_dim
    nq, nkv = c.num_attention_heads * hd, c.num_key_value_heads * hd
    normal = dict(embed_tokens=(V, H), wq=(L, H, nq), wk=(L, H, nkv),
                  wv=(L, H, nkv), wo=(L, nq, H), router=(L, H, E),
                  w_gate=(L, E, H, I), w_up=(L, E, H, I),
                  w_down=(L, E, I, H))
    ones = dict(q_norm=(L, nq), k_norm=(L, nkv), input_ln=(L, H),
                post_ln=(L, H), final_norm=(H,))
    if not c.tie_word_embeddings:
        normal["lm_head"] = (H, V)
    return normal, ones


class OlmoeForCausalLM(nn.Layer):
    """Decoder-only LM with a routed FFN, parameters stacked over layers.
    ``forward(input_ids)`` returns logits; ``generate`` runs the serving
    engine, as ``LlamaForCausalLM.generate`` does."""

    def __init__(self, config: OlmoeConfig):
        super().__init__()
        self.config = config
        normal, ones = _param_shapes(config)
        dt = dtype_mod.to_jax_dtype(config.dtype)

        # every parameter in its own dtype, in ONE jitted call: eager
        # float32 draws would put two 4 GiB temporaries beside a 2 GiB
        # expert matrix at the published widths
        def build(key):
            keys = jax.random.split(key, len(normal))
            out = {n: (0.02 * jax.random.normal(k, s, jnp.float32)).astype(dt)
                   for k, (n, s) in zip(keys, sorted(normal.items()))}
            out.update({n: jnp.ones(s, dt) for n, s in ones.items()})
            return out

        built = build_once(config, build)(_random.next_key())
        for name, value in built.items():
            setattr(self, name, Parameter(value))
        if config.tie_word_embeddings:
            self.lm_head = None

    def decode_params(self):
        """``(params, tied)`` for the serving step programs: the raw-array
        tree whose keys choose the layer body (``router``: the routed FFN;
        ``q_norm`` / ``k_norm``: the QK-norm)."""
        names = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "router",
                 "w_gate", "w_up", "w_down", "input_ln", "post_ln",
                 "final_norm")
        p = {n: getattr(self, n).value for n in names}
        p["embed"] = self.embed_tokens.value
        p["lm_head"] = (self.embed_tokens.value if self.lm_head is None
                        else self.lm_head.value)
        return p, self.lm_head is None

    def forward(self, input_ids, return_router_picks=False):
        """Logits ``[B, S, V]``; with ``return_router_picks`` also the
        experts every position picked in every layer, ``[L, B, S, top_k]``
        int32 (what a check of the routing against a reference reads)."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params, tied = self.decode_params()
        c = self.config
        logits, picks = _olmoe_forward(
            params, ids, nh=c.num_attention_heads,
            nkv=c.num_key_value_heads, hd=c.head_dim,
            eps=float(c.rms_norm_eps), theta=float(c.rope_theta),
            tied=tied, return_picks=bool(return_router_picks),
            moe=(int(c.num_experts_per_tok), bool(c.norm_topk_prob)))
        return (Tensor(logits), picks) if return_router_picks \
            else Tensor(logits)

    def num_params(self):
        import numpy as np
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # the engine's fused decode tail (decode_chunk > 1) has a layer body
    # of its own that was not taught this layer: one tick a step
    generate = functools.partialmethod(_llama_generate, _decode_chunk=1)


@functools.partial(jax.jit, static_argnames=(
    "nh", "nkv", "hd", "eps", "theta", "tied", "moe", "return_picks"))
def _olmoe_forward(params, ids, *, nh, nkv, hd, eps, theta, tied, moe,
                   return_picks=False):
    """Plain whole-sequence forward: (logits [B, S, V], picked experts
    [L, B, S, top_k] or None). The layer body is the serving programs'
    own, under plain causal attention."""
    from ..kernels.flash_attention import attention
    from ..serving.decode import _apply_rope, _decoder_layer, _layer_stack
    B, S = ids.shape
    sin, cos = _rope_tables(S, hd, theta)
    keys, stack, experts = _layer_stack(params)

    def layer(h, lp):
        h, _, stats = _decoder_layer(
            h, dict(zip(keys, lp)), nh=nh, nkv=nkv, hd=hd, eps=eps,
            rope=lambda x: _apply_rope(x, sin, cos),
            attend=lambda q, k, v: (attention(q, k, v, causal=True), None),
            moe=moe, experts=experts, return_picks=return_picks)
        return h, (stats[1] if return_picks else None)

    x = jnp.take(params["embed"], ids, axis=0)
    x, picks = jax.lax.scan(layer, x, stack)
    x = _rms(x, params["final_norm"], eps)
    head = params["lm_head"].T if tied else params["lm_head"]
    return jnp.einsum("bsh,hv->bsv", x, head), picks
