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
- **Transparency of the step machinery**: speculative decode and
  multi-tick decode on int8 KV are byte-identical to their own
  tick-at-a-time quantized baselines; the chaos fault matrix loses
  nothing and replays deterministically.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (ContinuousBatchingEngine,
                                GenerationRequest)
from paddle_tpu.serving.faults import FaultPlan
from paddle_tpu.serving.kv_cache import PagedKVCache, quantize_kv_rows
from paddle_tpu.serving.server.gateway import ServingGateway

from test_metrics_prom import parse_prometheus

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
    """Mixed trace: two shared system prompts with unique tails (trie
    traffic) + repetition so the n-gram drafter has something to hit."""
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
    """Mean matched-prefix fraction across paired streams — the
    measured (not assumed) divergence statistic the density bench
    banks."""
    fracs = []
    for x, y in zip(a, b):
        m = 0
        for t, u in zip(x, y):
            if t != u:
                break
            m += 1
        fracs.append(m / max(len(x), 1))
    return sum(fracs) / len(fracs)


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


# ---------------------------------------------- lifecycle carries scales
class TestLifecycleCarriesScales:
    def test_trie_hit_zero_copy_and_scale_plane_identity(self, model):
        eng = _engine(model, kv_dtype="int8", prefix_cache=True)
        p = _prompt(7, 32)                  # 4 whole blocks
        r = GenerationRequest(prompt=p, max_new_tokens=4)
        first = list(eng.generate([r])[0])
        matched = eng.prefix_cache.lookup(p)
        assert matched, "retirement should have donated the chain"
        blocks = [n.block_id for n in matched]
        ks_before = np.asarray(eng.cache.pool.k_scale)[:, blocks].copy()
        vs_before = np.asarray(eng.cache.pool.v_scale)[:, blocks].copy()
        second = list(eng.generate([GenerationRequest(
            prompt=p, max_new_tokens=4)])[0])
        assert eng.prefix_cache.stats["hits"] >= 1
        assert second == first              # hit ≡ cold, quantized
        # the donated blocks' scale planes were READ, never rewritten:
        # scale identity is what makes zero-copy hits exact on int8
        np.testing.assert_array_equal(
            np.asarray(eng.cache.pool.k_scale)[:, blocks], ks_before)
        np.testing.assert_array_equal(
            np.asarray(eng.cache.pool.v_scale)[:, blocks], vs_before)

    def test_spec_truncate_restores_num_free_exactly(self, model):
        eng = _engine(model, kv_dtype="int8", spec_decode=True,
                      spec_k=3)
        free0 = eng.cache.pool.num_free
        outs = _run(eng, _reqs())
        assert all(len(s) == 8 for s in outs)
        # every slot retired; with no trie, every draft-rejected and
        # private block went back to the heap exactly once
        assert eng.cache.pool.num_free == free0
        assert eng.cache.num_free == eng.num_slots

    def test_preempt_restore_byte_identical_on_int8(self, model):
        want = _run(_engine(model, kv_dtype="int8",
                            prefix_cache=True), _reqs())
        eng = _engine(model, kv_dtype="int8", prefix_cache=True)
        FaultPlan().at_step(3, "pool").install(eng)
        got = _run(eng, _reqs())
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["restores"] >= 1
        assert got == want

    def test_cancel_mid_decode_restores_pool(self, model):
        eng = _engine(model, kv_dtype="int8")
        free0 = eng.cache.pool.num_free
        seqs = [eng.submit(r) for r in _reqs(max_new=24)]
        for _ in range(3):
            eng.step()
        for s in seqs:
            if not s.done:
                eng.cancel(s)
        assert eng.cache.pool.num_free == free0
        assert eng.cache.num_free == eng.num_slots


# ------------------------------------------------------ chaos, int8 leg
class TestChaosInt8:
    def _factory(self, model, jit):
        def factory():
            return _engine(model, kv_dtype="int8", prefix_cache=True,
                           jit_cache=jit)
        return factory

    def test_fault_matrix_zero_lost_deterministic(self, model):
        # dedicated jit dict: the trie-backed pool is a different arg
        # SHAPE than the no-trie engines elsewhere in this module, and
        # pool-geometry-keyed caches must not collide under the
        # compile pin (jit-cache-per-pool-geometry rule)
        jit = {}
        want = _run(_engine(model, kv_dtype="int8", prefix_cache=True,
                            jit_cache=jit), _reqs())

        def chaos_once():
            plan = (FaultPlan().at_step(2, "transient")
                    .at_step(4, "pool").at_step(6, "fatal")
                    .at_step(8, "nan"))
            factory = self._factory(model, jit)
            gw = ServingGateway(factory(), engine_factory=factory,
                                fault_hook=plan, start=False,
                                max_queue=16)
            streams = [gw.submit(_clone(r)) for r in _reqs()]
            gw.start()
            outs = [st.result() for st in streams]
            kinds = [k for _, k in plan.log]
            comp = gw.engine.decode_compilations()
            gw.shutdown(drain=True, timeout=30)
            return ([ids.tolist() for ids, _ in outs],
                    [r for _, r in outs], kinds, comp)

        ids1, reasons1, kinds1, comp1 = chaos_once()
        ids2, reasons2, kinds2, comp2 = chaos_once()
        assert ids1 == want                 # 0 lost, byte-identical
        assert ids1 == ids2 and reasons1 == reasons2    # deterministic
        assert set(kinds1) >= {"transient", "pool", "fatal", "nan"}
        assert comp1 == 2 and comp2 == 2


# --------------------------------------------------- compile discipline
class TestCompileDiscipline:
    @pytest.mark.slow  # 6 s four-engine matrix duplicate: test_lowprec_decode
    # TestCompileDiscipline keys fp/kv8f/w8+a8 apart by default (870s cap)
    def test_compile_once_inclusive_of_quantized_geometry(self, model):
        # fresh dict: all four engines share one POOL geometry (no
        # trie), so the pin isolates exactly the quantization variants
        jit = {}
        engines = {
            "fp": _engine(model, jit_cache=jit),
            "int8": _engine(model, kv_dtype="int8", jit_cache=jit),
            "w8": _engine(model, quantize_weights=True, jit_cache=jit),
            "both": _engine(model, kv_dtype="int8",
                            quantize_weights=True, jit_cache=jit),
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
        jit = {}
        fp = _engine(model, jit_cache=jit)
        q8 = _engine(model, kv_dtype="int8", quantize_weights=True,
                     jit_cache=jit)
        # a short prompt (under the chunk) takes the COLD prefill path
        short = [GenerationRequest(prompt=_prompt(9, 10),
                                   max_new_tokens=2)]
        _run(fp, _reqs(n_reqs=1)), _run(fp, short)
        _run(q8, _reqs(n_reqs=1)), _run(q8, short)
        keys = set(jit)
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


# ----------------------------------------- spec + multi-tick, int8 pool
class TestSpecAndMultitickInt8:
    @pytest.mark.parametrize("sampled", [False, True])
    def test_spec_decode_byte_identical_to_int8_baseline(self, model,
                                                         sampled):
        base = _run(_engine(model, kv_dtype="int8"), _reqs(sampled))
        spec = _run(_engine(model, kv_dtype="int8", spec_decode=True,
                            spec_k=3), _reqs(sampled))
        assert spec == base

    @pytest.mark.parametrize("sampled", [False, True])
    def test_multitick_byte_identical_to_int8_baseline(self, model,
                                                       sampled):
        base = _run(_engine(model, kv_dtype="int8"), _reqs(sampled))
        mt = _run(_engine(model, kv_dtype="int8", decode_ticks=4),
                  _reqs(sampled))
        assert mt == base


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
        jit = model.__dict__.setdefault("_serving_jit", {})
        want = _run(_engine(model, quantize_weights=True,
                            jit_cache=jit), _reqs())

        def factory():
            return _engine(model, quantize_weights=True, jit_cache=jit)
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


# -------------------------------------------------------------- metrics
class TestQuantMetrics:
    def test_kv_pool_bytes_gauges_strict_parse(self, model):
        eng = _engine(model, kv_dtype="int8", prefix_cache=True)
        gw = ServingGateway(eng, start=False, max_queue=16)
        eng.submit(GenerationRequest(prompt=_prompt(1, 20),
                                     max_new_tokens=4))
        eng.step()                          # we are the driver thread
        fams = parse_prometheus(gw.registry.render())
        ob = eng.cache.occupancy_bytes()
        kv = fams["kv_pool_bytes"]["samples"]
        assert kv[("kv_pool_bytes", (("kind", "kv"),))] == ob["used_kv"]
        assert kv[("kv_pool_bytes",
                   (("kind", "scales"),))] == ob["used_scales"]
        assert ob["used_kv"] > 0 and ob["used_scales"] > 0
        # int8 data is exactly D bytes per fp32-scale's 4: the ratio
        # of the two gauges is D/4, dtype-awareness in one line
        assert ob["used_kv"] / ob["used_scales"] == \
            model.config.head_dim / 4
        per_tok = fams["serving_kv_bytes_per_token"]["samples"][
            ("serving_kv_bytes_per_token", ())]
        assert per_tok == ob["per_token"]
        gw.shutdown(drain=False, timeout=10)

    def test_profile_doc_reports_bytes_not_blocks(self, model):
        eng = _engine(model, kv_dtype="int8")
        gw = ServingGateway(eng, start=False, max_queue=16)
        eng.submit(GenerationRequest(prompt=_prompt(2, 20),
                                     max_new_tokens=4))
        eng.step()
        doc = gw.profile_doc()
        kvp = doc["kv_pool"]
        assert kvp["kv_dtype"] == "int8"
        per_block = (eng.cache.pool.block_nbytes
                     + eng.cache.pool.scale_block_nbytes)
        occ = eng.cache.occupancy()
        assert kvp["live_bytes"] == occ["live"] * per_block
        assert kvp["live_bytes"] > 0
        assert kvp["capacity_bytes"] == \
            eng.cache.pool.num_blocks * per_block
        assert kvp["bytes_per_token"] == \
            eng.cache.occupancy_bytes()["per_token"]
        gw.shutdown(drain=False, timeout=10)
