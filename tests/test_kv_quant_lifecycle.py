"""Int8 block-quantized KV through the blocks' lifecycle and the step
machinery (README "Quantized serving", ISSUE 14): the half of
``tests/test_kv_quant.py`` whose programs (the trie-backed int8 pool, the
speculative and the multi-tick step) are other programs than that file's
streams and compile pins need; lowering is paid a process (ROADMAP D6).

- **Scales ride the blocks**: the per-row-per-head scale planes are
  indexed by physical block id, so trie donation, zero-copy hits,
  speculative truncation, preemption and restore() all carry them with
  NO dedicated bookkeeping — pinned by scale-plane identity and exact
  ``num_free`` restoration.
- **Transparency of the step machinery**: speculative decode and
  multi-tick decode on int8 KV are byte-identical to their own
  tick-at-a-time quantized baselines; the chaos fault matrix loses
  nothing and replays deterministically.
"""
import numpy as np
import pytest

from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.faults import FaultPlan
from paddle_tpu.serving.server.gateway import ServingGateway

import serving_support
from serving_support import (clone as _clone, engine as _engine,
                             mixed_reqs as _reqs, prompt as _prompt,
                             run as _run)
from test_metrics_prom import parse_prometheus


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA: nkv=2 < nh=4


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
#: one fault plan a kind, then all four in one run: each a case of its own
_CHAOS = {
    "transient": [(2, "transient")],
    "pool": [(4, "pool")],
    "fatal": [(6, "fatal")],
    "nan": [(8, "nan")],
    "all": [(2, "transient"), (4, "pool"), (6, "fatal"), (8, "nan")],
}


class TestChaosInt8:
    @pytest.mark.parametrize("case", sorted(_CHAOS))
    def test_fault_matrix_zero_lost_deterministic(self, model, case):
        # the trie-backed pool is a different arg SHAPE than the no-trie
        # engines elsewhere in this module: the support module keys its
        # caches by pool geometry, so the compile pin holds on the shared one
        def factory():
            return _engine(model, kv_dtype="int8", prefix_cache=True)

        want = _run(factory(), _reqs())

        def chaos_once():
            plan = FaultPlan()
            for step, kind in _CHAOS[case]:
                plan.at_step(step, kind)
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
        assert set(kinds1) >= {kind for _, kind in _CHAOS[case]}
        assert comp1 == 2 and comp2 == 2


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
