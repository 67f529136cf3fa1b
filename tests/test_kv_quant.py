"""Int8 block-quantized KV serving + int8 weight-only decode (README
"Quantized serving", ISSUE 14). The load-bearing properties:

- **Measured divergence, not assumed zero**: quantized streams are
  compared token-for-token against the fp32 baseline — greedy AND
  seeded-sampled — and the agreement is asserted as a measured bound.
- **Scales ride the blocks**: the per-row-per-head scale planes are
  indexed by physical block id, so trie donation, zero-copy hits,
  speculative truncation, preemption and restore() all carry them with
  NO dedicated bookkeeping — pinned by scale-plane identity and exact
  ``num_free`` restoration.
- **Compile discipline**: ``decode_compilations() == 1`` inclusive of
  the quantized geometry, with fp32/int8/weight-quantized engines
  sharing ONE jit cache (the variant tags key their traces apart).
- **Transparency of the step machinery** and the blocks' lifecycle
  (``tests/test_kv_quant_lifecycle.py``, a file of its own so that no
  file is the floor under the suite's wall, ROADMAP D6: its programs are
  other programs than these): speculative decode and multi-tick decode on
  int8 KV are byte-identical to their own tick-at-a-time quantized
  baselines; the chaos fault matrix loses nothing and replays
  deterministically.
"""
import numpy as np
import pytest

from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.faults import FaultPlan
from paddle_tpu.serving.kv_cache import PagedKVCache, quantize_kv_rows
from paddle_tpu.serving.server.gateway import ServingGateway

import serving_support
from serving_support import (BS, CHUNK, clone as _clone, engine as _engine,
                             match_fraction as _match_fraction,
                             mixed_reqs as _reqs, prompt as _prompt,
                             run as _run)


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA: nkv=2 < nh=4


# ------------------------------------------------------------ unit: rows
class TestQuantizeRows:
    def test_roundtrip_error_bounded_per_row_head(self):
        rng = np.random.RandomState(0)
        x = rng.randn(5, 7, 3, 16).astype(np.float32) * \
            rng.uniform(0.1, 10.0, (5, 7, 3, 1)).astype(np.float32)
        q, s = quantize_kv_rows(x)
        q, s = np.asarray(q), np.asarray(s)
        assert q.dtype == np.int8 and s.dtype == np.float32
        assert s.shape == x.shape[:-1]
        deq = q.astype(np.float32) * s[..., None]
        # symmetric round-to-nearest: error <= scale/2 per element,
        # and |dequant| never exceeds the row-head absmax
        assert np.all(np.abs(deq - x) <= s[..., None] / 2 + 1e-7)
        assert np.all(np.abs(deq) <= np.abs(x).max(-1, keepdims=True)
                      + 1e-7)
        assert np.abs(q).max() <= 127

    def test_zero_rows_quantize_to_exact_zero(self):
        q, s = quantize_kv_rows(np.zeros((2, 4, 3, 8), np.float32))
        assert np.all(np.asarray(q) == 0) and np.all(np.asarray(s) == 0)
        assert np.all(np.asarray(q).astype(np.float32)
                      * np.asarray(s)[..., None] == 0)


# ------------------------------------------------------- pool accounting
class TestPoolBytes:
    def test_occupancy_bytes_exact_and_ratio(self, model):
        base = _engine(model)
        q = _engine(model, kv_dtype="int8")
        c = model.config
        L, Hkv, D = (c.num_hidden_layers, c.num_key_value_heads,
                     c.head_dim)
        nb = q.cache.pool.num_blocks
        ob = q.cache.occupancy_bytes()
        assert ob["capacity_kv"] == 2 * L * nb * BS * Hkv * D      # int8
        assert ob["capacity_scales"] == 2 * L * nb * BS * Hkv * 4  # fp32
        ob0 = base.cache.occupancy_bytes()
        assert ob0["capacity_scales"] == 0
        assert ob0["capacity_kv"] == 2 * L * base.cache.pool.num_blocks \
            * BS * Hkv * D * 4                                     # fp32
        # per-token marginal cost: fp32 4D bytes vs int8 D + 4 bytes
        ratio = ob0["per_token"] / ob["per_token"]
        assert ratio == pytest.approx(4 * D / (D + 4))
        assert ratio >= 1.8               # the density headline's floor

    def test_write_prefill_quantizes_on_write(self, model):
        c = model.config
        cache = PagedKVCache(c.num_hidden_layers, 2, 64,
                             c.num_key_value_heads, c.head_dim,
                             block_size=BS, kv_dtype="int8")
        rng = np.random.RandomState(3)
        L, Hkv, D = (c.num_hidden_layers, c.num_key_value_heads,
                     c.head_dim)
        pk = rng.randn(L, 16, Hkv, D).astype(np.float32)
        pv = rng.randn(L, 16, Hkv, D).astype(np.float32)
        slot = cache.alloc()
        cache.write_prefill(slot, pk, pv, 11)
        assert cache.pool.k.dtype == np.int8
        want_q, want_s = quantize_kv_rows(pk)
        blocks = cache.slot_block_ids(slot)
        got_q = np.asarray(cache.pool.k)[:, blocks].reshape(L, -1, Hkv, D)
        got_s = np.asarray(cache.pool.k_scale)[:, blocks].reshape(
            L, -1, Hkv)
        # rows [0, 11) landed quantized with their scales; padding rows
        # past prompt_len dropped (block 2 of the 16-row buffer was
        # never allocated). Tolerances: the jitted writer's fused
        # reduction may differ from the eager recompute by float
        # epsilon, which can flip a round-to-nearest tie by one step.
        np.testing.assert_allclose(got_s[:, :11],
                                   np.asarray(want_s)[:, :11],
                                   rtol=1e-5)
        assert np.abs(got_q[:, :11].astype(np.int32)
                      - np.asarray(want_q)[:, :11]).max() <= 1

    def test_pool_cache_kv_dtype_mismatch_raises(self, model):
        from paddle_tpu.serving.block_manager import BlockManager
        c = model.config
        pool = BlockManager(c.num_hidden_layers, 16, BS,
                            c.num_key_value_heads, c.head_dim)
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedKVCache(c.num_hidden_layers, 2, 64,
                         c.num_key_value_heads, c.head_dim,
                         block_size=BS, pool=pool, kv_dtype="int8")


# ----------------------------------------------------------- validation
class TestValidation:
    def test_bad_kv_dtype_rejected(self, model):
        with pytest.raises(ValueError, match="kv_dtype"):
            _engine(model, kv_dtype="int4")


# -------------------------------------------------------------- streams
class TestStreams:
    def test_greedy_divergence_measured_and_bounded(self, model):
        base = _run(_engine(model), _reqs())
        quant = _run(_engine(model, kv_dtype="int8"), _reqs())
        assert [len(s) for s in quant] == [len(s) for s in base]
        frac = _match_fraction(base, quant)
        # MEASURED agreement, not assumed identity: per-token int8 KV
        # holds the greedy argmax walk on this model/trace (frac is
        # 1.0 here today; the bound leaves room for platform jitter
        # while still catching a real quantization regression)
        assert frac >= 0.75, f"greedy matched-prefix fraction {frac}"

    def test_sampled_divergence_measured_and_bounded(self, model):
        base = _run(_engine(model), _reqs(sampled=True))
        quant = _run(_engine(model, kv_dtype="int8"),
                     _reqs(sampled=True))
        frac = _match_fraction(base, quant)
        assert frac >= 0.75, f"sampled matched-prefix fraction {frac}"

    def test_int8_streams_deterministic_across_replays(self, model):
        for sampled in (False, True):
            a = _run(_engine(model, kv_dtype="int8"), _reqs(sampled))
            b = _run(_engine(model, kv_dtype="int8"), _reqs(sampled))
            assert a == b

    def test_default_kv_dtype_unchanged_by_quantized_sibling(self, model):
        """The default path must stay byte-identical with quantized
        engines sharing the SAME jit cache dict — the quantized trace
        keys apart instead of perturbing the baseline programs."""
        before = _run(_engine(model), _reqs())
        _run(_engine(model, kv_dtype="int8", quantize_weights=True),
             _reqs())
        after = _run(_engine(model), _reqs())
        assert before == after


# --------------------------------------------------- compile discipline
class TestCompileDiscipline:
    @pytest.mark.slow  # 6 s four-engine matrix duplicate: test_lowprec_decode
    # TestCompileDiscipline keys fp/kv8f/w8+a8 apart by default (870s cap)
    def test_compile_once_inclusive_of_quantized_geometry(self, model):
        # all four engines share one POOL geometry (no trie), so one
        # cache, and the pin isolates exactly the quantization variants
        engines = {
            "fp": _engine(model),
            "int8": _engine(model, kv_dtype="int8"),
            "w8": _engine(model, quantize_weights=True),
            "both": _engine(model, kv_dtype="int8",
                            quantize_weights=True),
        }
        for eng in engines.values():
            _run(eng, _reqs())
            _run(eng, _reqs(sampled=True))
        for name, eng in engines.items():
            assert eng.decode_compilations() == 1, name
        # second wave re-traces nothing: the prefill compile set is
        # closed per variant
        pre = {n: e.prefill_compilations() for n, e in engines.items()}
        for eng in engines.values():
            _run(eng, _reqs())
        assert {n: e.prefill_compilations()
                for n, e in engines.items()} == pre

    def test_variant_tags_key_programs_apart(self, model):
        # on the shared cache, beside whatever the module's tests built
        fp = _engine(model)
        q8 = _engine(model, kv_dtype="int8", quantize_weights=True)
        # a short prompt (under the chunk) takes the COLD prefill path
        short = [GenerationRequest(prompt=_prompt(9, 10),
                                   max_new_tokens=2)]
        _run(fp, _reqs(n_reqs=1)), _run(fp, short)
        _run(q8, _reqs(n_reqs=1)), _run(q8, short)
        keys = set(fp._jit)
        attn = model.config.decode_attention
        # a program a packed size: chunk-carrying steps, decode-only steps
        for rows in (2 + CHUNK, 8):
            assert ("ragged", 2, 2 + CHUNK, rows, 1, attn) in keys
            assert ("ragged", 2, 2 + CHUNK, rows, 1, attn, "kv8",
                    "w8") in keys
        assert ("prefill",) in keys and ("prefill", "w8") in keys
        # each engine counts ONLY its own variant
        assert fp.decode_compilations() == 2
        assert q8.decode_compilations() == 2


# ------------------------------------------------------- weight-only w8
class TestWeightOnly:
    def test_streams_deterministic_and_close_to_fp(self, model):
        base = _run(_engine(model), _reqs())
        a = _run(_engine(model, quantize_weights=True), _reqs())
        b = _run(_engine(model, quantize_weights=True), _reqs())
        assert a == b                       # deterministic
        frac = _match_fraction(base, a)
        assert frac >= 0.5, f"w8 matched-prefix fraction {frac}"

    def test_converted_params_cached_on_model(self, model):
        e1 = _engine(model, quantize_weights=True)
        e2 = _engine(model, quantize_weights=True)
        assert e1._params is e2._params     # converted ONCE per model
        q, s = e1._params["wq"]
        assert np.asarray(q).dtype == np.int8
        assert s.shape[1] == 1              # per-channel, axis-1 reduced

    def test_rebuild_shares_qparams_and_jit(self, model):
        want = _run(_engine(model, quantize_weights=True), _reqs())

        def factory():
            return _engine(model, quantize_weights=True)
        plan = FaultPlan().at_step(3, "fatal")
        gw = ServingGateway(factory(), engine_factory=factory,
                            fault_hook=plan, start=False, max_queue=16)
        streams = [gw.submit(_clone(r)) for r in _reqs()]
        gw.start()
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == want
        assert gw.restarts == 1
        assert gw.engine.decode_compilations() == 2
        gw.shutdown(drain=True, timeout=30)
