"""paddle.quantization — QAT/PTQ (reference: ``python/paddle/quantization/``
— QuantConfig + QAT.quantize (fake-quant insertion) + PTQ.quantize
(observers) + convert).

TPU-native: fake-quant is a pure jnp op with a straight-through-estimator
custom VJP — it fuses into the surrounding XLA program (no special kernels;
int8 inference on TPU is a matter of emitting int8 dots, which `convert`
models by baking quantized-dequantized weights). Observers are functional
state on the layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..ops._op import tensor_op

__all__ = ["QuantConfig", "QAT", "PTQ", "AbsmaxObserver", "quanted_linear",
           "fake_quant", "FakeQuanterWithAbsMaxObserver", "QuantedLinear",
           "quantize_weight_int8", "convert_weights_int8",
           "quantize_collective_int8", "quantized_psum_int8",
           "collective_wire_bytes"]


def quantize_weight_int8(w, reduce_axis, bits=8):
    """Symmetric per-channel int8 weight-only quantization — THE shared
    machinery behind :class:`ConvertedLinear` and the serving engine's
    ``quantize_weights=True`` decode path
    (``serving/decode.quantize_decode_params``, README "Quantized
    serving").

    ``w`` is the raw weight array; ``reduce_axis`` names the
    contraction axis of the matmul the weight feeds (the "in" dim), so
    each OUTPUT channel gets its own absmax scale — per-channel, not
    per-tensor, because one outlier channel must not flatten every
    other channel's resolution. Returns ``(q int8, scale f32)`` with
    ``scale`` keeping the reduced axis as size 1 (broadcasts straight
    back against ``q`` for the dequant ``q * scale``). Symmetric range
    [-127, 127]: -128 is never emitted so ``|q * scale| <= absmax``
    exactly. All-zero channels carry scale 0 and dequantize to exact
    zeros (the quantize guard divides by a tiny floor instead).
    ``bits < 8`` narrows the grid inside the same int8 storage (the
    PTQ 4-bit convert path); ``bits > 8`` cannot fit int8 and raises.
    """
    if not 2 <= int(bits) <= 8:
        raise ValueError(
            f"int8 storage holds 2..8-bit symmetric grids, got "
            f"bits={bits}")
    qmax = float(2 ** (int(bits) - 1) - 1)
    w = jnp.asarray(w)
    scale = (jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axis,
                     keepdims=True) / qmax)
    q = jnp.clip(jnp.round(w.astype(jnp.float32)
                           / jnp.maximum(scale, 1e-30)),
                 -qmax, qmax).astype(jnp.int8)
    return q, scale


# ------------------------------------------- quantized all-reduce (EQuARX)
def quantize_collective_int8(x):
    """Symmetric per-row int8 quantization of a collective payload —
    THE wire format of the serving stack's ``collective_dtype="int8"``
    tensor-parallel all-reduce (README "Tensor-parallel serving",
    EQuARX / PAPERS.md). Each row (absmax over the LAST axis) gets its
    own fp32 scale, so one outlier activation cannot flatten a whole
    chunk's resolution; all-zero rows carry scale 0 and dequantize to
    exact zeros. Returns ``(q int8, scale f32 [..., 1])``."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(xf / jnp.maximum(scale, 1e-30)),
                 -127, 127).astype(jnp.int8)
    return q, scale


def quantized_psum_int8(x, axis_name, tp):
    """EQuARX-style block-quantized all-reduce over mesh axis
    ``axis_name`` (size ``tp``): both communication phases move int8
    payloads plus per-row fp32 scales instead of full-precision
    activations, cutting wire bytes ~``itemsize / (1 + tp·4/H)``-fold
    (~3.5–3.9x for fp32 at serving hidden sizes).

    Phase 1 (reduce-scatter): split the partial sum into ``tp`` chunks
    along the last axis, quantize, ``all_to_all`` so shard ``i``
    receives every shard's quantized chunk ``i``, dequantize and sum
    in fp32 — a FIXED summation order, so the result (and therefore
    the token stream) is deterministic and identical on every shard.
    Phase 2 (all-gather): requantize the reduced chunk, ``all_gather``,
    dequantize and reassemble. The double quantization is a quality
    price: greedy streams may diverge from the fp wire (not measured on
    the chip).

    Requires the last axis divisible by ``tp`` (the engine validates
    ``hidden_size % tp == 0`` at build). Shapes/dtype are preserved."""
    shp = x.shape
    hidden = shp[-1]
    chunk = hidden // tp
    xc = jnp.moveaxis(x.reshape(shp[:-1] + (tp, chunk)), -2, 0)
    q, s = quantize_collective_int8(xc)            # [tp, ..., chunk]
    q2 = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    s2 = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0)
    red = jnp.sum(q2.astype(jnp.float32) * s2, axis=0)   # [..., chunk]
    qr, sr = quantize_collective_int8(red)
    qg = jax.lax.all_gather(qr, axis_name, axis=0)       # [tp, ..., chunk]
    sg = jax.lax.all_gather(sr, axis_name, axis=0)
    full = jnp.moveaxis(qg.astype(jnp.float32) * sg, 0, -2)
    return full.reshape(shp).astype(x.dtype)


def collective_wire_bytes(rows, hidden, tp, collective_dtype,
                          fp_itemsize=4):
    """EXACT per-device wire bytes of ONE per-layer all-reduce of a
    ``[rows, hidden]`` activation on a ``tp``-way mesh — the counter
    model behind ``serving_collective_bytes_total{dtype}`` (README
    "Tensor-parallel serving") and the bench's >=3x acceptance gate.

    Both dtypes are priced on the same ring reduce-scatter +
    all-gather schedule (each phase moves ``(tp-1)/tp`` of the payload
    per device), so the fp-vs-int8 ratio isolates the WIRE FORMAT:

    - ``"fp"``: payload = ``rows · hidden · fp_itemsize``;
    - ``"int8"``: payload = ``rows · hidden`` int8 bytes plus one fp32
      scale per (row, chunk) — ``rows · tp`` scales per phase — the
      exact layout :func:`quantized_psum_int8` moves.

    Deterministic, shape-derived, no measurement noise. Returns 0 for
    ``tp <= 1`` (no mesh, no wire)."""
    if tp <= 1:
        return 0
    if collective_dtype == "int8":
        payload = rows * hidden + rows * tp * 4
    else:
        payload = rows * hidden * fp_itemsize
    return int(2 * payload * (tp - 1) // tp)


# ------------------------------------------------------------- fake quant
@jax.custom_vjp
def _fake_quant_ste(x, scale, bits):
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(scale, 1e-8) / qmax
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax) * s


def _fq_fwd(x, scale, bits):
    return _fake_quant_ste(x, scale, bits), (x, scale, bits)


def _fq_bwd(res, g):
    x, scale, bits = res
    qmax = 2.0 ** (bits - 1) - 1
    lim = jnp.maximum(scale, 1e-8)
    # straight-through inside the clip range, zero outside
    pass_thru = (jnp.abs(x) <= lim).astype(g.dtype)
    return g * pass_thru, jnp.zeros_like(scale), None


_fake_quant_ste.defvjp(_fq_fwd, _fq_bwd)


@tensor_op
def fake_quant(x, scale, bits=8):
    """Quantize-dequantize with STE gradient (reference
    FakeQuanterWithAbsMaxObserver forward)."""
    return _fake_quant_ste(x, jnp.asarray(scale, jnp.float32), int(bits))


class FakeQuanterWithAbsMaxObserver(nn.Layer):
    """Activation quanter: tracks a running absmax, fake-quants with STE
    (reference ``paddle.quantization.quanters.FakeQuanterWithAbsMaxObserver``)."""

    def __init__(self, moving_rate=0.9, bit_length=8, dtype="float32",
                 name=None):
        super().__init__()
        self.moving_rate = float(moving_rate)
        self.bits = int(bit_length)
        self.register_buffer("scale", Tensor(jnp.ones((), jnp.float32)))
        self._seen = False

    def forward(self, x):
        cur = jnp.max(jnp.abs(x.value)).astype(jnp.float32)
        if self.training:
            m = self.moving_rate
            prev = self.scale.value
            new = jnp.where(jnp.asarray(self._seen), m * prev + (1 - m) * cur,
                            cur)
            self.scale.set_value(new)
            self._seen = True
        return fake_quant(x, self.scale.value, self.bits)


class QuantedLinear(nn.Layer):
    """Linear with fake-quanted weight + activation (QAT execution form)."""

    def __init__(self, linear: nn.Linear, q_config):
        super().__init__()
        self.weight = linear.weight
        self.bias = linear.bias
        self.weight_bits = q_config.weight_bits
        self.act_quanter = (FakeQuanterWithAbsMaxObserver(
            bit_length=q_config.activation_bits)
            if q_config.activation_bits else None)

    def forward(self, x):
        if self.act_quanter is not None:
            x = self.act_quanter(x)
        w = self.weight
        wq = fake_quant(w, jnp.max(jnp.abs(w.value)), self.weight_bits)
        from ..nn import functional as F
        return F.linear(x, wq, self.bias)


# ------------------------------------------------------------- observers
class AbsmaxObserver(nn.Layer):
    """PTQ observer: records absmax over calibration batches (reference
    ``paddle.quantization.observers.AbsmaxObserver``)."""

    def __init__(self, quant_bits=8):
        super().__init__()
        self.bits = int(quant_bits)
        self.register_buffer("scale", Tensor(jnp.zeros((), jnp.float32)))

    def forward(self, x):
        cur = jnp.max(jnp.abs(x.value)).astype(jnp.float32)
        self.scale.set_value(jnp.maximum(self.scale.value, cur))
        return x


class ObservedLinear(nn.Layer):
    def __init__(self, linear: nn.Linear, q_config):
        super().__init__()
        self.weight = linear.weight
        self.bias = linear.bias
        self.observer = AbsmaxObserver(q_config.activation_bits or 8)
        self.weight_bits = q_config.weight_bits

    def forward(self, x):
        x = self.observer(x)
        from ..nn import functional as F
        return F.linear(x, self.weight, self.bias)


class ConvertedLinear(nn.Layer):
    """Inference form: weights stored int8 + scale, dequantized on the fly
    (on TPU the int8 weight halves HBM traffic; XLA emits the dequant as a
    fused convert on the way into the MXU).

    Scales are PER OUTPUT CHANNEL and computed ONCE here, at convert
    time (``quantize_weight_int8``) — the forward only applies them.
    Per-tensor absmax let a single outlier channel flatten every other
    channel's resolution, and deriving scales inside ``__call__`` both
    re-paid the reduction on every step and made the quantization grid
    drift with whatever dtype autocast handed in. ``w_scale`` has shape
    ``[1, out_features]`` (paddle's ``[in, out]`` weight layout)."""

    def __init__(self, weight, bias, weight_bits=8):
        super().__init__()
        q, scale = quantize_weight_int8(weight.value, reduce_axis=0,
                                        bits=weight_bits)
        self.register_buffer("qweight", Tensor(q))
        self.register_buffer("w_scale", Tensor(scale))
        self.bias = bias

    def forward(self, x):
        # dequantize to the INPUT's dtype, not hard-coded fp32: under
        # amp.auto_cast(dtype="bfloat16") a bf16 activation must meet a
        # bf16 weight or the matmul silently promotes back to fp32
        # (breaking the int8 + autocast composition); integer inputs
        # (never valid for linear anyway) fall back to fp32
        dt = x.value.dtype
        if not jnp.issubdtype(dt, jnp.floating):
            dt = jnp.float32
        w = (self.qweight.value.astype(dt)
             * self.w_scale.value.astype(dt))
        b = self.bias
        if b is not None and b.dtype != dt:
            b = Tensor(b.value.astype(dt))  # fp32 bias would re-promote
        from ..nn import functional as F
        return F.linear(x, Tensor(w), b)


# ------------------------------------------------------------- config/API
class QuantConfig:
    """Reference ``paddle.quantization.QuantConfig`` (subset): global
    weight/activation quanter settings."""

    def __init__(self, activation=None, weight=None, weight_bits=8,
                 activation_bits=8):
        self.activation = activation
        self.weight = weight
        self.weight_bits = int(weight_bits)
        self.activation_bits = int(activation_bits) if activation_bits else 0
        self._types = (nn.Linear,)

    def add_type_config(self, layer_types, activation=None, weight=None):
        if not isinstance(layer_types, (list, tuple)):
            layer_types = [layer_types]
        self._types = tuple(layer_types)
        return self


def _swap_matching(model, match_fn, factory):
    """Replace sublayers where match_fn(child); skips subtrees of already-
    replaced layers (their old child paths no longer resolve)."""
    replaced = []
    for name, _ in list(model.named_sublayers()):
        if any(name.startswith(r + ".") for r in replaced):
            continue
        parent = model
        parts = name.split(".")
        for p in parts[:-1]:
            parent = getattr(parent, p)
        leaf = parts[-1]
        child = getattr(parent, leaf)
        if match_fn(child):
            setattr(parent, leaf, factory(child))
            replaced.append(name)
    return model


def _swap_layers(model, cfg, factory):
    return _swap_matching(
        model,
        lambda child: isinstance(child, nn.Linear) and not isinstance(
            child, (QuantedLinear, ObservedLinear, ConvertedLinear)),
        lambda child: factory(child, cfg))


class QAT:
    """Quantization-aware training driver (reference ``paddle.quantization.QAT``)."""

    def __init__(self, q_config: QuantConfig):
        self.cfg = q_config

    def quantize(self, model, inplace=False):
        return _swap_layers(model, self.cfg,
                            lambda lin, cfg: QuantedLinear(lin, cfg))

    def convert(self, model, inplace=False):
        return _swap_layers(
            model, self.cfg,
            lambda lin, cfg: lin)  # QuantedLinear already executes quantized


class PTQ:
    """Post-training quantization driver (reference ``paddle.quantization.PTQ``)."""

    def __init__(self, q_config: QuantConfig):
        self.cfg = q_config

    def quantize(self, model, inplace=False):
        return _swap_layers(model, self.cfg,
                            lambda lin, cfg: ObservedLinear(lin, cfg))

    def convert(self, model, inplace=False):
        return _swap_matching(
            model,
            lambda child: isinstance(child, ObservedLinear),
            lambda child: ConvertedLinear(child.weight, child.bias,
                                          self.cfg.weight_bits))


def convert_weights_int8(model):
    """One-call weight-only int8 conversion (no observers, no
    calibration): swap every ``nn.Linear`` for a
    :class:`ConvertedLinear` with per-channel scales baked at convert
    time. IDEMPOTENT: already-converted layers (and QAT/observed ones)
    are skipped, so ``convert_weights_int8(convert_weights_int8(m))``
    is a no-op — the second pass finds nothing to swap and never
    re-quantizes an int8 weight (which would double the quantization
    error). The serving engine's ``quantize_weights=True`` knob is the
    raw-array twin of this layer-level surface."""
    return _swap_layers(
        model, None, lambda lin, _cfg: ConvertedLinear(lin.weight,
                                                       lin.bias))


def quanted_linear(x, weight, bias=None, w_bits=8, a_scale=None, a_bits=8):
    """Functional QAT linear."""
    if a_scale is not None:
        x = fake_quant(x, a_scale, a_bits)
    wq = fake_quant(weight, jnp.max(jnp.abs(weight.value)), w_bits)
    from ..nn import functional as F
    return F.linear(x, wq, bias)
