"""fp8 KV and int8x8 projections x the step machinery (README "Quantized
serving", ISSUE 19): the composition half of ``tests/test_lowprec_decode.py``,
in a file of its own because its programs (the speculative, multi-tick,
sharded, trie-pool and fleet variants) are other programs than that file's
streams and compile pins need, and lowering is paid a process (ROADMAP D6).

- **Composition**: fp8/a8 ride multi-tick, spec-verify, TP and the host
  tier with streams byte-identical to their own tick-at-a-time quantized
  baselines.
- **Lifecycle**: the fp8 pool's per-block planes spill and readmit with its
  data, and a live migration between fp8 replicas recomputes byte-identically.
- **Per-block scales, constant by construction**, and what an engine refuses
  at build: the pool's byte accounting and the validation of the switches
  (no program runs; they ride here so that this file, heavy and of few
  tests, is not among the last the scheduler hands out: it orders files by
  their number of tests).
"""
import numpy as np
import pytest

from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.fleet import EngineFleet
from paddle_tpu.serving.kv_cache import quantize_kv_rows_fp8

import serving_support
from serving_support import (BS, CHUNK, clone as _clone, engine as _engine,
                             mixed_reqs as _reqs, prompt as _prompt,
                             run as _run, wait_until)


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA: nkv=2 < nh=4


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
        # a 2-block trie is a pool of its own size, so programs of its own
        off = _engine(model, kv_dtype="fp8", prefix_cache=True,
                      prefix_blocks=2)
        want = _serial(off, reqs)
        eng = _engine(model, kv_dtype="fp8", prefix_cache=True,
                      prefix_blocks=2, host_tier_bytes=1 << 24)
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
            # the replica compiles its fp8 programs under this wait
            wait_until(lambda: st.seq is not None
                       and len(st.seq.tokens) >= 8, "8 tokens")
            fl.migrate(st, target=1)
            ids, reason = st.result()
            assert ids.tolist() == want and reason == "length"
            assert st.gateway is fl.replicas[1].gateway
            assert fl._m_migrated.value(cause="migration") == 1
        finally:
            fl.shutdown(drain=True, timeout=30)
