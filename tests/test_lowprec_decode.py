"""End-to-end low-precision decode (README "Quantized serving",
ISSUE 19): fp8 KV with dequant-free attention + the int8x8
(``quantize_activations``) projection path. The load-bearing
properties, PR-13 discipline throughout:

- **Measured divergence, not assumed zero**: fp8 and a8 streams are
  compared token-for-token against the fp32 baseline and the agreement
  asserted as a measured bound; replays are byte-identical.
- **Per-block scales, constant by construction**: the fp8 pool's scale
  planes are ``[L, nb, Hkv]`` ones — e4m3's exponent is the per-value
  scale — so a cached token costs strictly fewer bytes than int8's
  per-row layout and a block's bytes never depend on which program
  wrote it (restore()/replay byte-identity).
- **Compile discipline**: ``decode_compilations() == 1`` inclusive of
  the ``kv8f``/``a8`` variant geometry, with fp/int8/fp8/w8/a8 engines
  sharing ONE jit cache (the tags key their traces apart) and the
  default path byte-identical before/after.
- **Composition** (``tests/test_lowprec_composition.py``, a file of its
  own so that no file is the floor under the suite's wall, ROADMAP D6:
  its programs are other programs than these): fp8/a8 ride multi-tick,
  spec-verify, TP and the host tier with streams byte-identical to their
  own tick-at-a-time quantized baselines.
"""
import numpy as np
import pytest

from paddle_tpu.serving.kv_cache import (FP8_MAX, quantize_kv_rows,
                                         quantize_kv_rows_fp8)

import serving_support
from serving_support import (CHUNK, engine as _engine,
                             match_fraction as _match_fraction,
                             mixed_reqs as _reqs, run as _run)


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA: nkv=2 < nh=4


# -------------------------------------------- rows: roundtrip properties
class TestRoundtripProperties:
    """Randomized quantize/dequantize roundtrip bounds across int8 AND
    fp8 rows — the error model each write rule promises, checked over
    many magnitude regimes, never a single lucky draw."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_int8_rows_bounded_by_half_scale(self, seed):
        rng = np.random.RandomState(seed)
        x = rng.randn(4, 6, 3, 16).astype(np.float32) * \
            rng.uniform(1e-3, 100.0, (4, 6, 3, 1)).astype(np.float32)
        q, s = quantize_kv_rows(x)
        q, s = np.asarray(q), np.asarray(s)
        deq = q.astype(np.float32) * s[..., None]
        assert np.all(np.abs(deq - x) <= s[..., None] / 2 + 1e-7)
        assert np.abs(q).max() <= 127

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_fp8_rows_bounded_by_e4m3_relative_step(self, seed):
        """e4m3 round-to-nearest: relative error <= 2^-4 for normals,
        absolute error <= 2^-10 in the subnormal range — with NO scale
        (the per-block planes are the constant 1.0 by design)."""
        rng = np.random.RandomState(seed)
        x = rng.randn(4, 6, 3, 16).astype(np.float32) * \
            rng.uniform(1e-3, 64.0, (4, 6, 3, 1)).astype(np.float32)
        f8 = np.asarray(quantize_kv_rows_fp8(x))
        assert f8.dtype == np.dtype("float8_e4m3fn")
        deq = f8.astype(np.float32)
        bound = np.maximum(np.abs(x) * 2.0 ** -4, 2.0 ** -10)
        assert np.all(np.abs(deq - x) <= bound + 1e-7)
        assert np.all(np.isfinite(deq))

    def test_fp8_saturates_instead_of_nan(self):
        x = np.array([[-1e6, -FP8_MAX, 0.0, FP8_MAX, 1e6]],
                     np.float32)
        deq = np.asarray(quantize_kv_rows_fp8(x)).astype(np.float32)
        np.testing.assert_array_equal(
            deq, [[-FP8_MAX, -FP8_MAX, 0.0, FP8_MAX, FP8_MAX]])

    def test_fp8_zero_rows_exact_and_sign_preserving(self):
        deq = np.asarray(quantize_kv_rows_fp8(
            np.zeros((2, 4, 3, 8), np.float32))).astype(np.float32)
        assert np.all(deq == 0.0)


# --------------------------------------------------------------- streams
class TestStreams:
    def test_fp8_greedy_divergence_measured_and_bounded(self, model):
        base = _run(_engine(model), _reqs())
        f8 = _run(_engine(model, kv_dtype="fp8"), _reqs())
        assert [len(s) for s in f8] == [len(s) for s in base]
        frac = _match_fraction(base, f8)
        assert frac >= 0.75, f"fp8 greedy matched-prefix fraction {frac}"

    @pytest.mark.slow  # sampled duplicate of the greedy bound above
    def test_fp8_sampled_divergence_measured_and_bounded(self, model):
        base = _run(_engine(model), _reqs(sampled=True))
        f8 = _run(_engine(model, kv_dtype="fp8"), _reqs(sampled=True))
        frac = _match_fraction(base, f8)
        assert frac >= 0.75, f"fp8 sampled matched-prefix fraction {frac}"

    def test_a8_divergence_measured_and_bounded(self, model):
        base = _run(_engine(model), _reqs())
        a8 = _run(_engine(model, quantize_weights=True,
                          quantize_activations=True), _reqs())
        frac = _match_fraction(base, a8)
        assert frac >= 0.5, f"a8 matched-prefix fraction {frac}"

    @pytest.mark.parametrize(
        "sampled", [False, pytest.param(True, marks=pytest.mark.slow)])
    def test_fp8_and_a8_deterministic_across_replays(self, model,
                                                     sampled):
        for kw in (dict(kv_dtype="fp8"),
                   dict(quantize_weights=True,
                        quantize_activations=True),
                   dict(kv_dtype="fp8", quantize_weights=True,
                        quantize_activations=True)):
            a = _run(_engine(model, **kw), _reqs(sampled))
            b = _run(_engine(model, **kw), _reqs(sampled))
            assert a == b, kw

    def test_default_path_unchanged_by_lowprec_siblings(self, model):
        before = _run(_engine(model), _reqs())
        _run(_engine(model, kv_dtype="fp8", quantize_weights=True,
                     quantize_activations=True), _reqs())
        after = _run(_engine(model), _reqs())
        assert before == after


# --------------------------------------------------- compile discipline
class TestCompileDiscipline:
    @pytest.mark.slow  # 9 s four-engine matrix duplicate: the tag-keying
    # test below asserts compile-once for fp/fp8/a8 by default (870s cap)
    def test_compile_once_inclusive_of_kv8f_and_a8(self, model):
        engines = {
            "fp": _engine(model),
            "fp8": _engine(model, kv_dtype="fp8"),
            "a8": _engine(model, quantize_weights=True,
                          quantize_activations=True),
            "all": _engine(model, kv_dtype="fp8", quantize_weights=True,
                           quantize_activations=True),
        }
        for eng in engines.values():
            _run(eng, _reqs())
            _run(eng, _reqs(sampled=True))
        for name, eng in engines.items():
            assert eng.decode_compilations() == 1, name
        pre = {n: e.prefill_compilations() for n, e in engines.items()}
        for eng in engines.values():
            _run(eng, _reqs())
        assert {n: e.prefill_compilations()
                for n, e in engines.items()} == pre

    def test_kv8f_and_a8_tags_key_programs_apart(self, model):
        # on the shared cache: one dict holds all three variants' programs
        # (and whatever the module's other tests built), keyed apart
        fp = _engine(model)
        f8 = _engine(model, kv_dtype="fp8")
        a8 = _engine(model, quantize_weights=True,
                     quantize_activations=True)
        for e in (fp, f8, a8):
            _run(e, _reqs(n_reqs=1))
        keys = set(fp._jit)
        attn = model.config.decode_attention
        # a program a packed size: chunk-carrying steps, decode-only steps
        for rows in (2 + CHUNK, 8):
            assert ("ragged", 2, 2 + CHUNK, rows, 1, attn) in keys
            assert ("ragged", 2, 2 + CHUNK, rows, 1, attn, "kv8f") in keys
            assert ("ragged", 2, 2 + CHUNK, rows, 1, attn, "w8",
                    "a8") in keys
        assert fp.decode_compilations() == 2
        assert f8.decode_compilations() == 2
        assert a8.decode_compilations() == 2
