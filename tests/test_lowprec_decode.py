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
- **Composition**: fp8/a8 ride multi-tick, spec-verify, TP and the
  host tier with streams byte-identical to their own tick-at-a-time
  quantized baselines.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (ContinuousBatchingEngine,
                                GenerationRequest)
from paddle_tpu.serving.fleet import EngineFleet
from paddle_tpu.serving.kv_cache import (FP8_MAX, quantize_kv_rows,
                                         quantize_kv_rows_fp8)

BS = 8      # block size
CHUNK = 16  # 2 blocks per chunk


@pytest.fixture(scope="module")
def model():
    paddle.seed(33)
    return LlamaForCausalLM(llama_tiny())  # GQA: nkv=2 < nh=4


def _engine(model, **kw):
    kw.setdefault("jit_cache", model.__dict__.setdefault("_serving_jit", {}))
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("decode_chunk", 1)
    kw.setdefault("prefix_block_size", BS)
    kw.setdefault("prefill_chunk", CHUNK)
    return ContinuousBatchingEngine(model, **kw)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, 256, (n,)).astype(np.int32)


def _reqs(sampled=False, n_reqs=4, max_new=8):
    sys_p = [_prompt(100 + i, 24) for i in range(2)]
    out = []
    for i in range(n_reqs):
        tail = np.tile(_prompt(i, 4), 3).astype(np.int32)
        kw = dict(max_new_tokens=max_new)
        if sampled:
            kw.update(temperature=0.8, top_k=20, seed=500 + i)
        out.append(GenerationRequest(
            prompt=np.concatenate([sys_p[i % 2], tail]), **kw))
    return out


def _clone(r):
    return GenerationRequest(prompt=r.prompt,
                             max_new_tokens=r.max_new_tokens,
                             temperature=r.temperature, top_k=r.top_k,
                             seed=r.seed, eos_token_id=r.eos_token_id)


def _run(eng, reqs):
    return [list(o) for o in eng.generate([_clone(r) for r in reqs])]


def _match_fraction(a, b):
    fracs = []
    for x, y in zip(a, b):
        m = 0
        for t, u in zip(x, y):
            if t != u:
                break
            m += 1
        fracs.append(m / max(len(x), 1))
    return sum(fracs) / len(fracs)


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


# --------------------------------------------------- pool byte accounting
class TestFp8PoolBytes:
    def test_per_block_planes_and_strictly_cheaper_tokens(self, model):
        i8 = _engine(model, kv_dtype="int8")
        f8 = _engine(model, kv_dtype="fp8")
        c = model.config
        L, Hkv, D = (c.num_hidden_layers, c.num_key_value_heads,
                     c.head_dim)
        pool = f8.cache.pool
        assert pool.k.dtype == np.dtype("float8_e4m3fn")
        # per-BLOCK planes, initialized to the constant 1.0
        assert pool.k_scale.shape == (L, pool.num_blocks, Hkv)
        assert np.all(np.asarray(pool.k_scale) == 1.0)
        ob8, obf = (i8.cache.occupancy_bytes(),
                    f8.cache.occupancy_bytes())
        # identical data bytes (1 byte/elem both), block_size x fewer
        # scale bytes — so fp8's cached token is STRICTLY cheaper
        nb = f8.cache.pool.num_blocks
        assert obf["capacity_scales"] == 2 * L * nb * Hkv * 4
        assert obf["per_token"] == 2 * L * Hkv * (D + 4 / BS)
        assert obf["per_token"] < ob8["per_token"]

    def test_write_prefill_saturating_cast_scales_untouched(self, model):
        from paddle_tpu.serving.kv_cache import PagedKVCache
        c = model.config
        cache = PagedKVCache(c.num_hidden_layers, 2, 64,
                             c.num_key_value_heads, c.head_dim,
                             block_size=BS, kv_dtype="fp8")
        rng = np.random.RandomState(3)
        L, Hkv, D = (c.num_hidden_layers, c.num_key_value_heads,
                     c.head_dim)
        pk = rng.randn(L, 16, Hkv, D).astype(np.float32) * 100.0
        pv = rng.randn(L, 16, Hkv, D).astype(np.float32)
        slot = cache.alloc()
        cache.write_prefill(slot, pk, pv, 11)
        blocks = cache.slot_block_ids(slot)
        got = np.asarray(cache.pool.k)[:, blocks].reshape(L, -1, Hkv, D)
        want = np.asarray(quantize_kv_rows_fp8(pk))
        np.testing.assert_array_equal(
            got[:, :11].astype(np.float32),
            want[:, :11].astype(np.float32))
        # the scale planes were never written: constant 1.0 planes are
        # what makes restore()-by-recompute byte-identical on fp8
        assert np.all(np.asarray(cache.pool.k_scale) == 1.0)
        assert np.all(np.asarray(cache.pool.v_scale) == 1.0)


# ------------------------------------------------------------ validation
class TestValidation:
    def test_a8_requires_weight_quant(self, model):
        with pytest.raises(ValueError, match="quantize_weights"):
            _engine(model, quantize_activations=True)

    def test_shared_pool_mode_mismatch_raises(self, model):
        """An int8-pool trie adopted by an fp8 engine is a geometry
        error at build, not an opaque XLA failure at first hit."""
        int8 = _engine(model, kv_dtype="int8", prefix_cache=True)
        with pytest.raises(ValueError, match="kv_dtype"):
            _engine(model, kv_dtype="fp8",
                    prefix_cache=int8.prefix_cache)


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
        jit = {}
        engines = {
            "fp": _engine(model, jit_cache=jit),
            "fp8": _engine(model, kv_dtype="fp8", jit_cache=jit),
            "a8": _engine(model, quantize_weights=True,
                          quantize_activations=True, jit_cache=jit),
            "all": _engine(model, kv_dtype="fp8", quantize_weights=True,
                           quantize_activations=True, jit_cache=jit),
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
        jit = {}
        fp = _engine(model, jit_cache=jit)
        f8 = _engine(model, kv_dtype="fp8", jit_cache=jit)
        a8 = _engine(model, quantize_weights=True,
                     quantize_activations=True, jit_cache=jit)
        for e in (fp, f8, a8):
            _run(e, _reqs(n_reqs=1))
        keys = set(jit)
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


# ------------------------------------------------------------ composition
class TestComposition:
    """fp8/a8 x the step machinery: every combination's streams are
    byte-identical to its own tick-at-a-time low-precision baseline."""

    @pytest.mark.parametrize(
        "sampled", [False, pytest.param(True, marks=pytest.mark.slow)])
    def test_spec_decode_byte_identical_on_fp8(self, model, sampled):
        base = _run(_engine(model, kv_dtype="fp8"), _reqs(sampled))
        spec = _run(_engine(model, kv_dtype="fp8", spec_decode=True,
                            spec_k=3), _reqs(sampled))
        assert spec == base

    @pytest.mark.parametrize(
        "sampled", [False, pytest.param(True, marks=pytest.mark.slow)])
    def test_multitick_byte_identical_on_fp8(self, model, sampled):
        base = _run(_engine(model, kv_dtype="fp8"), _reqs(sampled))
        mt = _run(_engine(model, kv_dtype="fp8", decode_ticks=4),
                  _reqs(sampled))
        assert mt == base

    def test_spec_and_multitick_byte_identical_on_a8(self, model):
        kw = dict(quantize_weights=True, quantize_activations=True)
        base = _run(_engine(model, **kw), _reqs())
        spec = _run(_engine(model, spec_decode=True, spec_k=3, **kw),
                    _reqs())
        mt = _run(_engine(model, decode_ticks=4, **kw), _reqs())
        assert spec == base and mt == base

    @pytest.mark.parametrize("kw", [
        dict(kv_dtype="fp8"),
        dict(quantize_weights=True, quantize_activations=True),
    ], ids=["fp8", "a8"])
    def test_tp2_byte_identical_to_single_chip(self, model, kw):
        base = _run(_engine(model, **kw), _reqs())
        tp = _run(_engine(model, tp=2, **kw), _reqs())
        assert tp == base

    def test_preempt_restore_byte_identical_on_fp8(self, model):
        from paddle_tpu.serving.faults import FaultPlan
        want = _run(_engine(model, kv_dtype="fp8", prefix_cache=True),
                    _reqs())
        eng = _engine(model, kv_dtype="fp8", prefix_cache=True)
        FaultPlan().at_step(3, "pool").install(eng)
        got = _run(eng, _reqs())
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["restores"] >= 1
        assert got == want


# ------------------------------------------------- tier + fleet lifecycle
#: two 2-block system-prompt families: under a 2-block trie budget,
#: alternating them thrashes — every switch spills, every return readmits
_FAMS = [np.random.RandomState(300 + f).randint(
    0, 256, (2 * BS,)).astype(np.int32) for f in range(2)]


def _fam_req(fam, tail_seed, **kw):
    tail = np.random.RandomState(tail_seed).randint(
        0, 256, (6,)).astype(np.int32)
    kw.setdefault("max_new_tokens", 6)
    return GenerationRequest(
        prompt=np.concatenate([_FAMS[fam], tail]), **kw)


def _serial(eng, reqs):
    return [eng.generate([_clone(r)])[0].tolist() for r in reqs]


class TestTierAndFleet:
    def test_fp8_tier_spill_readmit_byte_identical(self, model):
        """The fp8 pool's per-block planes spill and readmit alongside
        the e4m3 data (one tier entry, block-id-keyed like int8's) with
        streams byte-identical to the tier-off fp8 engine."""
        reqs = [_fam_req(f, 10 * f + i, **(
            dict(temperature=0.8, top_k=5, seed=700 + f) if i == 1
            else {}))
            for i in range(3) for f in (0, 1)]
        jit = {}  # private: count THIS geometry's programs, not the
        # fp8 mtick/spec siblings the module's shared cache holds
        off = _engine(model, kv_dtype="fp8", prefix_cache=True,
                      prefix_blocks=2, jit_cache=jit)
        want = _serial(off, reqs)
        eng = _engine(model, kv_dtype="fp8", prefix_cache=True,
                      prefix_blocks=2, host_tier_bytes=1 << 24,
                      jit_cache=jit)
        pc = eng.prefix_cache
        assert _serial(eng, reqs) == want
        assert pc.stats["spilled_blocks"] > 0
        assert pc.stats["readmitted_blocks"] > 0
        # a resident entry carries e4m3 data + the 2-D per-block planes
        with pc.tier._lock:
            bufs = next(iter(pc.tier._entries.values()))[0]
        assert set(bufs) == {"k", "v", "k_scale", "v_scale"}
        assert bufs["k"].dtype == np.dtype("float8_e4m3fn")
        assert bufs["k_scale"].dtype == np.float32
        assert bufs["k_scale"].shape[1] == 1      # [L, 1, Hkv]: 1 block
        assert np.all(bufs["k_scale"] == 1.0)
        assert eng.decode_compilations() == 2

    def test_fp8_fleet_migration_byte_identical(self, model):
        """Live migration off an fp8-pool replica: evict donates the
        quantized chain + PRNG snapshot, adopt restores by recompute on
        the sibling's fp8 pool — stream byte-identical to an unmigrated
        fp8 single-engine run."""
        import time
        req = GenerationRequest(prompt=_prompt(7, 12),
                                max_new_tokens=40)
        want = _run(_engine(model, kv_dtype="fp8"), [req])[0]
        fl = EngineFleet(model, replicas=2, router="least-loaded",
                         num_slots=2, max_seq_len=96,
                         prefix_block_size=BS, prefill_chunk=CHUNK,
                         kv_dtype="fp8", max_queue=8,
                         retry_backoff_s=0.0, start=True)
        try:
            st = fl.submit(_clone(req))
            deadline = time.monotonic() + 30
            while not (st.seq is not None and len(st.seq.tokens) >= 8):
                assert time.monotonic() < deadline
                time.sleep(0.005)
            fl.migrate(st, target=1)
            ids, reason = st.result()
            assert ids.tolist() == want and reason == "length"
            assert st.gateway is fl.replicas[1].gateway
            assert fl._m_migrated.value(cause="migration") == 1
        finally:
            fl.shutdown(drain=True, timeout=30)
