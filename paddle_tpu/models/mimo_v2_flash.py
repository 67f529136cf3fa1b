"""MiMo-V2-Flash (``model_type`` ``mimo_v2_flash``; XiaomiMiMo/MiMo-V2-Flash,
309B-A15B): a pre-norm decoder whose layers come in periods of WINDOW
attention layers (128 keys and a learned sink) and then one FULL attention
layer, after a leading dense layer; every other layer's FFN is routed over 256
experts by a sigmoid router. Keys are wider than values (192 | 128) and the two
kinds of layer have KV heads of their own count (4 | 8). Served, not trained.

Every layer, ``x`` the residual stream, no bias anywhere:

    x = x + Attn(N(x; ln1));   x = x + FFN(N(x; ln2))

``N(x; w) = x / sqrt(mean(x^2) + eps) * w`` in float32 (``layernorm_epsilon``):
before each mixer, before each FFN, before the head; none on ``q`` or ``k``.
``hybrid_layer_pattern[i]`` is 0 for a full layer and 1 for a window layer;
``moe_layer_freq[i]`` is 0 for a dense SwiGLU at ``intermediate_size`` and 1
for the routed FFN.

- *Full layer* (``num_attention_heads`` query heads on ``num_key_value_heads``
  KV heads, keys ``head_dim`` wide, values ``v_head_dim``): ``q = x W_q``, ``k
  = x W_k``, ``v = attention_value_scale * (x W_v)``; the FIRST ``int(head_dim
  * partial_rotary_factor)`` values of a head of ``q`` and ``k`` rotated
  (rotate-half inside them, ``rope_theta``), the rest left; ``s_ij = q_i . k_j
  * head_dim^-0.5`` for ``j <= i``; softmax; ``o = concat_h(p v) W_o`` with
  ``W_o`` reading ``heads x v_head_dim``. No sink
  (``add_full_attention_sink_bias`` false). The cache holds ``k`` after
  rotation and the scaled ``v``.
- *Window layer* (``swa_*``: 8 KV heads here against 4, the same widths,
  ``swa_rope_theta``): as the full layer with two changes. A query at position
  ``i`` sees keys ``i - sliding_window < j <= i``. And a learned SINK ``b_h``,
  one float a query head (``add_swa_attention_sink_bias``): ``p_ij = exp(s_ij)
  / (exp(b_h) + sum_j' exp(s_ij'))``, one more column of the softmax that has
  no value, so a row's weights sum to less than one. The cache holds the last
  ``sliding_window`` tokens only: a ring of the store by slot.
- *Routed FFN*: ``s = sigmoid(x W_r)`` in float32 over ``router_experts``; the
  ``num_experts_per_tok`` largest of ``s + c`` (``router_bias``, DeepSeek-V3's
  ``e_score_correction_bias``; ``topk_method`` noaux_tc, ``n_group`` 1);
  weights ``s_e / sum_picked s`` (``norm_topk_prob``), times
  ``routed_scaling_factor`` (null = 1); an expert is a SwiGLU at
  ``moe_intermediate_size``; no shared expert. ``n_routed_experts`` is what
  THIS chip holds, ids ``first_held_expert .. + n_routed_experts`` of the
  router's width; what the absent experts would add is left out
  (``kernels.moe_ffn``).

Not built: the three multi-token prediction layers the model card describes
(the published ``config.json`` has no key for them); serving without them is
the model's plain decoding. ``attention_chunk_size`` equals the window and
changes no equation.

Assumed where the published config is silent: the value scale multiplies ``v``
after its projection (attention is linear in ``v``: scaling the heads' output
before ``W_o`` is the same function); the rotated values are the first of a
head, half-split; the sink as written above.

What the program runs: the dense layers first (a prefix, all full attention),
then whole periods of ``n`` window layers and one full layer, every period
alike (``serving.decode._hybrid_scan``); a pattern that is not that is refused
when the configuration is made, with the reason. THE PUBLISHED MODEL AT ITS
FULL DEPTH IS NOT RUNNABLE YET: its 48 layers are ``[0, 1,1,1,1, 0,
(1,1,1,1,1, 0) x 7]`` (``PUBLISHED_PATTERN``, the configuration's default),
and the short run of four window layers after layer 0 would need a scan of
its own, which is not built; what runs is a cut made of the dense layer and
whole periods: the published layers 0 and 6-47 (``[0] + [1,1,1,1,1,0] * 7``),
or fewer periods.

Where a key is stored: a key head lies ``head_dim`` lanes wide in the pool and
in the rings, as it is (192: the ragged kernel takes a key and a value of
different widths, and Mosaic cuts a head's window at half a lane tile: no
padded store, the model's 2,560 B a token a full layer).

Parameters are stacked by layer KIND and place in the period, built in their
dtype by one jitted call from the seed, a layer of a stack at a time
(``models.qwen3_next``): the dense layers under ``dense_*`` ``[dense layers,
...]``, the full layers of the periods under the plain names ``[periods,
...]``, the window layers under ``window<j>_*``, one tree ``[periods, ...]`` a
place. Every matrix Normal(0, 0.02), norm weights 1, the selection bias
Normal(0, 0.01) (so that it decides near-ties), the sink Normal(4, 1): at these
weights a score's standard deviation is about 1.6 and a full window's
denominator some hundreds, so a sink at the published initial value would be
under a hundredth of it and no check could tell a dropped sink; at 4 it is a
tenth to a third. The sink and the bias are float32. The layer body is the
serving programs' own (``serving.decode._decoder_layer``), chosen by what the
tree holds.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
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

PUBLISHED_PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7


class Swa(NamedTuple):
    """The window layers' static numbers for the step programs
    (``config.swa``): the ``window``, their rotary base ``theta``, the value
    scale ``v_scale`` (both kinds of layer) and the rows of a window layer's
    ring a slot (0: a forward that keeps none; the engine sets it from its own
    geometry)."""
    window: int
    theta: float
    v_scale: float
    ring_rows: int = 0


@dataclass
class MiMoV2FlashConfig:
    """The source's keys by the source's names and with the source's values,
    plus ``router_experts`` (the router's published width) and
    ``first_held_expert`` where ``n_routed_experts`` is a chip's share.
    ``dtype`` and ``decode_attention`` as ``LlamaConfig``. The defaults ARE the
    published model, whose 48-layer ``hybrid_layer_pattern`` the program
    cannot run yet (module docstring): made as they stand they are refused
    with that reason, so a caller gives the layers it means
    (``PUBLISHED_PATTERN``'s whole periods, or a cut of them)."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 48
    hybrid_layer_pattern: list = field(
        default_factory=lambda: list(PUBLISHED_PATTERN))
    moe_layer_freq: list = field(default_factory=lambda: [0] + [1] * 47)
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    attention_bias: bool = False
    n_routed_experts: int = 256
    router_experts: int | None = None
    first_held_expert: int = 0
    n_shared_experts: int | None = None
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float | None = None
    max_position_embeddings: int = 262144
    layernorm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    decode_attention: str = "pallas"
    dtype: str = "float32"

    def __post_init__(self):
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        pat, freq = list(self.hybrid_layer_pattern), list(self.moe_layer_freq)
        L = self.num_hidden_layers
        if len(pat) != L or len(freq) != L:
            raise ValueError(
                f"MiMoV2FlashConfig: hybrid_layer_pattern ({len(pat)}) and "
                f"moe_layer_freq ({len(freq)}) have an entry a layer "
                f"({L})")
        nd = freq.index(1) if 1 in freq else L
        if nd < 1 or any(f != 1 for f in freq[nd:]) or any(pat[:nd]):
            raise ValueError(
                f"MiMoV2FlashConfig: the dense layers (moe_layer_freq 0) are "
                f"a prefix of at least one layer, all full attention "
                f"(hybrid_layer_pattern 0): the step programs run them "
                f"before the period scan; got moe_layer_freq {freq}, "
                f"hybrid_layer_pattern {pat[:nd]} over the dense ones")
        rest = pat[nd:]
        per = rest.index(0) + 1 if 0 in rest else 0
        if per < 2 or len(rest) % per \
                or rest != ([1] * (per - 1) + [0]) * (len(rest) // per):
            raise ValueError(
                f"MiMoV2FlashConfig: after the dense layers the step "
                f"programs scan whole periods of window layers and then ONE "
                f"full layer, every period alike (a window layer's keys "
                f"live in a ring, a full layer's in the pool); "
                f"hybrid_layer_pattern[{nd}:] = {rest} is not that"
                + (" (it starts with a full layer or has no window layer)"
                   if per == 1 else " (no period ends in a full layer)"
                   if not per else " (a period is shorter than the first, "
                   "or the last does not end in a full layer: the published "
                   "48 layers' short run of four window layers after layer "
                   "0 is not built)"))
        if self.swa_num_attention_heads != self.num_attention_heads \
                or self.swa_head_dim != self.head_dim \
                or self.swa_v_head_dim != self.v_head_dim \
                or self.num_attention_heads % self.num_key_value_heads \
                or self.num_attention_heads % self.swa_num_key_value_heads:
            raise ValueError(
                "MiMoV2FlashConfig: the two kinds of layer share the query "
                "heads and both head widths (what differs is the KV heads), "
                "and the query heads are whole groups of either's KV heads")
        if not self.add_swa_attention_sink_bias \
                or self.add_full_attention_sink_bias or self.attention_bias:
            raise ValueError(
                "MiMoV2FlashConfig: the window layers have a sink, the full "
                "layers none, and no projection has a bias")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc" \
                or self.n_group != 1 or self.topk_group != 1 \
                or self.n_shared_experts or self.tie_word_embeddings \
                or not (0 <= self.first_held_expert
                        <= self.router_experts - self.n_routed_experts):
            raise ValueError(
                f"MiMoV2FlashConfig: the router is the sigmoid one with a "
                f"selection bias and no group limit, beside no shared "
                f"expert, the head is untied, and the held experts "
                f"{self.first_held_expert}..+{self.n_routed_experts} must "
                f"lie inside the router's {self.router_experts}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"MiMoV2FlashConfig: int(head_dim x partial_rotary_factor) "
                f"({self.rotary_dim}) is an even part of a head")

    @property
    def rms_norm_eps(self):
        return self.layernorm_epsilon

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def num_dense_layers(self):
        return list(self.moe_layer_freq).index(1)

    @property
    def window_per_period(self):
        return list(self.hybrid_layer_pattern)[
            self.num_dense_layers:].index(0)

    @property
    def num_periods(self):
        return (self.num_hidden_layers - self.num_dense_layers) \
            // (self.window_per_period + 1)

    @property
    def num_kv_layers(self):
        """Layers whose keys and values lie in the pool: the full ones."""
        return self.num_dense_layers + self.num_periods

    @property
    def num_window_layers(self):
        return self.num_hidden_layers - self.num_kv_layers

    @property
    def swa(self):
        return Swa(int(self.sliding_window), float(self.swa_rope_theta),
                   float(self.attention_value_scale))

    @property
    def routing(self):
        """``models.deepseek_v2``'s tuple; the sigmoid rule is chosen by the
        tree's ``router_bias``."""
        return (int(self.num_experts_per_tok), bool(self.norm_topk_prob),
                1, 1, int(self.first_held_expert),
                float(self.routed_scaling_factor or 1.0))


def mimo_v2_flash_tiny(**kw):
    """Test / rehearsal config: hidden 64, a dense layer and 2 periods of (2
    window layers, 1 full), 8 query heads of 24 | 16 (8 rotated) on 2 KV heads
    in a full layer and 4 in a window layer, a window of 16, a router over 8
    experts of width 32 of which the first 4 are held, 2 a token, vocab
    256."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=7, hybrid_layer_pattern=[0, 1, 1, 0, 1, 1, 0],
        moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], num_attention_heads=8,
        num_key_value_heads=2, head_dim=24, v_head_dim=16,
        swa_num_attention_heads=8, swa_num_key_value_heads=4,
        swa_head_dim=24, swa_v_head_dim=16, sliding_window=16,
        n_routed_experts=4, router_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, max_position_embeddings=128)
    defaults.update(kw)
    return MiMoV2FlashConfig(**defaults)


def _shapes(c, n, nkv, dense):
    """``(normal, ones, bias)``: name -> shape of ``n`` stacked layers with
    ``nkv`` KV heads and a dense or a routed FFN."""
    H, hd, vd, nh = (c.hidden_size, c.head_dim, c.v_head_dim,
                     c.num_attention_heads)
    normal = dict(wq=(n, H, nh * hd), wk=(n, H, nkv * hd),
                  wv=(n, H, nkv * vd), wo=(n, nh * vd, H))
    bias = {}
    if dense:
        I = c.intermediate_size
        normal.update(w_gate=(n, H, I), w_up=(n, H, I), w_down=(n, I, H))
    else:
        E, I = c.n_routed_experts, c.moe_intermediate_size
        normal.update(router=(n, H, c.router_experts), w_gate=(n, E, H, I),
                      w_up=(n, E, H, I), w_down=(n, E, I, H))
        bias["router_bias"] = (n, c.router_experts)
    return normal, dict(input_ln=(n, H), post_ln=(n, H)), bias


class MiMoV2FlashForCausalLM(nn.Layer):
    """Decoder-only LM of window-attention layers with a sink around one
    full-attention layer a period, behind a leading dense layer; parameters
    stacked by layer kind and place. ``forward(input_ids)`` returns logits;
    ``generate`` runs the serving engine, as ``LlamaForCausalLM.generate``
    does."""

    def __init__(self, config: MiMoV2FlashConfig):
        super().__init__()
        self.config = c = config
        dt = dtype_mod.to_jax_dtype(c.dtype)
        f32 = jnp.float32
        P, places = c.num_periods, c.window_per_period

        def draw(key, shape, std):
            # (a stack is drawn a layer at a time: models.deepseek_v2)
            if len(shape) >= 3:
                return jax.lax.map(
                    lambda k: draw(k, shape[1:], std),
                    jax.random.split(key, shape[0]))
            return (std * jax.random.normal(key, shape, f32)).astype(dt)

        def tree(key, n, nkv, dense, sink=False):
            normal, ones, bias = _shapes(c, n, nkv, dense)
            k_w, k_b, k_s = jax.random.split(key, 3)
            out = {name: draw(k, s, 0.02) for k, (name, s) in zip(
                jax.random.split(k_w, len(normal)), sorted(normal.items()))}
            out.update({name: jnp.ones(s, dt) for name, s in ones.items()})
            out.update({name: 0.01 * jax.random.normal(k_b, s, f32)
                        for name, s in bias.items()})
            if sink:
                out["sink"] = 4.0 + jax.random.normal(
                    k_s, (n, c.num_attention_heads), f32)
            return out

        # every parameter in its own dtype, in ONE jitted call (as
        # ``models.olmoe``): never float32 first
        def build(key):
            k_e, k_h, k_d, k_f, *k_win = jax.random.split(key, 4 + places)
            top = dict(
                embed_tokens=draw(k_e, (c.vocab_size, c.hidden_size), 0.02),
                lm_head=draw(k_h, (c.hidden_size, c.vocab_size), 0.02),
                final_norm=jnp.ones((c.hidden_size,), dt))
            return (top,
                    tree(k_d, c.num_dense_layers, c.num_key_value_heads,
                         True),
                    tree(k_f, P, c.num_key_value_heads, False),
                    tuple(tree(k, P, c.swa_num_key_value_heads, False,
                               sink=True) for k in k_win))

        top, dense, full, win = build_once(config, build)(_random.next_key())
        for name, value in top.items():
            setattr(self, name, Parameter(value))
        for prefix, t in [("dense_", dense), ("", full)] + [
                (f"window{j}_", t) for j, t in enumerate(win)]:
            for name, value in t.items():
                setattr(self, prefix + name, Parameter(value))
        self._names = {"dense_": tuple(sorted(dense)), "": tuple(sorted(full)),
                       "window": tuple(sorted(win[0]))}
        from ..serving.routing_record import RoutingRecord
        self.routing_record = RoutingRecord()

    def _tree(self, prefix, names):
        return {n: getattr(self, prefix + n).value for n in names}

    def decode_params(self):
        """``(params, tied)`` for the serving step programs: the raw-array
        tree whose keys choose the layer bodies (``window_layers``: the
        periods of window layers, each with its ``sink``; ``dense_layers``:
        the leading full-attention layers with a dense SwiGLU; ``router`` and
        ``router_bias``: the sigmoid-routed FFN of every other layer; a
        ``wk`` / ``wv`` of another width than the full layers': a window
        layer's own KV heads, a value narrower than a key)."""
        p = self._tree("", self._names[""])
        p["dense_layers"] = self._tree("dense_", self._names["dense_"])
        p["window_layers"] = tuple(
            self._tree(f"window{j}_", self._names["window"])
            for j in range(self.config.window_per_period))
        p.update(embed=self.embed_tokens.value, lm_head=self.lm_head.value,
                 final_norm=self.final_norm.value)
        return p, False

    def forward(self, input_ids, return_router_picks=False):
        """Logits ``[B, S, V]``: the layers of whole-prompt prefill
        (``serving.decode._swa_prefill_layers``), a sequence at a time and
        an FFN ``FFN_ROWS`` positions at a time (a benchmark's check calls
        this beside a resident engine: at 12 k tokens a routed FFN's
        temporaries over the whole sequence are 2.2 GiB); with
        ``return_router_picks`` also the experts every position picked in
        every routed layer's FFN, ``[L_routed, B, S, top_k]`` (the serving
        programs' own where they ran: ``DeepseekV2ForCausalLM.forward``)."""
        ids = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params, _ = self.decode_params()
        c = self.config
        logits, picks = _forward(
            params, ids, nh=c.num_attention_heads,
            nkv=c.num_key_value_heads, hd=c.head_dim,
            eps=float(c.rms_norm_eps), theta=float(c.rope_theta),
            rotary=c.rotary_dim, swa=c.swa, moe=c.routing,
            return_picks=bool(return_router_picks), ffn_rows=FFN_ROWS)
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


#: positions a block of ``forward``'s FFNs (``decode._mixer_ffn_layer``)
FFN_ROWS = 1024


@functools.partial(jax.jit, static_argnames=(
    "nh", "nkv", "hd", "eps", "theta", "rotary", "swa", "moe",
    "return_picks", "ffn_rows"))
def _forward(params, ids, *, nh, nkv, hd, eps, theta, rotary, swa, moe,
             return_picks, ffn_rows=None):
    """(logits [B, S, V], picked experts [L_routed, B, S, top_k] or None)."""
    from ..serving.decode import _final_norm, _swa_prefill_layers
    lengths = jnp.full((1,), ids.shape[1], jnp.int32)

    def one_sequence(row):
        x = jnp.take(params["embed"], row[None], axis=0)
        x, _, _, _, stats = _swa_prefill_layers(
            params, x, lengths, nh=nh, nkv=nkv, hd=hd, eps=eps, swa=swa,
            theta=theta, rotary=rotary, moe=moe, return_picks=return_picks,
            ffn_rows=ffn_rows)
        x = _final_norm(params, x[0], eps)
        return (jnp.einsum("sh,hv->sv", x, params["lm_head"]),
                stats[1][:, 0] if return_picks else None)

    logits, picks = jax.lax.map(one_sequence, ids)
    return logits, (None if picks is None else jnp.moveaxis(picks, 0, 1))
