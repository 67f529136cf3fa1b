"""Device-boundary cost observatory (profiler/cost.py + its threading
through the serving stack; README "Cost attribution & /debug/profile").

The properties under test, per the observability contract:

- the observatory itself: exact per-program call counts, abstract-shape
  byte accounting (host-resident args = h2d, declared host-fetched
  results = d2h, device-resident leaves never charged), compile-event
  deltas, phase attribution — all with no device sync;
- EXACTNESS: on real engine runs of the unified ragged and the
  speculative step, the observatory's
  dispatch totals equal independent counts taken at the engine's
  program accessors, and the per-kind split equals the engine's own
  stats — with token streams byte-identical to an uninstrumented run
  and ``decode_compilations() == 1``;
- determinism: a chaos+spec replay under ``VirtualClock`` exports a
  byte-identical accounting twice, monotonic across the engine
  rebuilds inside it, with zero compile events when warm;
- counter tracks: the engine emits ``ph:"C"`` dispatch/transfer/
  KV-occupancy samples onto the step timeline;
- the gateway surface: ``serving_dispatches_total{program}``,
  ``serving_transfer_bytes_total{direction}``,
  ``serving_dispatches_per_decoded_token`` on ``/metrics``; every
  engine-stat-derived counter monotonic across crash-recovery rebuilds
  (the ISSUE 11 fix); ``GET /debug/profile`` (aggregate + step-bounded
  window) and the ``/debug/requests`` cost columns over live HTTP;
- guard discipline: a static (ast) sweep asserting every tracer/cost
  recording site under ``paddle_tpu/serving/`` routes through the
  one-attribute ``_tr()``/``_co()`` guards;
- the profiler CLI accepts Chrome trace JSON files (the
  ``/debug/trace`` document) with ``--top``/``--json`` honored and
  exit 1 on unparseable input.
"""
import ast
import contextlib
import io
import json
import pathlib
import re
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental import pallas as pl

from paddle_tpu.profiler.cost import (CostObservatory, _CountedProgram,
                                      jaxpr_census)
from paddle_tpu.profiler.tracing import SpanTracer
from paddle_tpu.serving import FaultPlan, GenerationRequest, VirtualClock
from paddle_tpu.serving.server import ServingGateway, serve

import serving_support
from test_metrics_prom import parse_prometheus
from test_tracing import _chaos_run, _chaos_workload


def _count_accessor_launches(eng):
    """The count the observatory is pinned against: every device call
    site invokes its program accessor exactly once, so accessor calls ==
    program launches."""
    calls = {"n": 0}

    def wrap(orig):
        def f(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)
        return f

    for name in ("_prefill_fn", "_suffix_fn", "_ragged_fn", "_mtick_fn",
                 "_spec_fn"):
        setattr(eng, name, wrap(getattr(eng, name)))
    return calls


NUM_SLOTS, S_MAX = 2, 256


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=31)


def _engine(model, **kw):
    """The shared helper at this file's geometry: 256 positions and
    otherwise the ENGINE's own defaults, which is what the ``server``
    fixture's ``serve()`` builds and what test_tracing's chaos workload,
    replayed below, was sized for."""
    kw.setdefault("num_slots", NUM_SLOTS)
    kw.setdefault("max_seq_len", S_MAX)
    return serving_support.engine_as_given(model, **kw)


def _reqs(n=3, max_new=4, plen=8, long_prompt=False):
    rng = np.random.RandomState(7)
    out = []
    for i in range(n):
        kw = {}
        if i % 3 == 2:
            kw = dict(temperature=0.8, top_k=5, seed=100 + i)
        out.append(GenerationRequest(
            prompt=rng.randint(0, 256, (plen,)).astype(np.int32),
            max_new_tokens=max_new, **kw))
    if long_prompt:
        out.append(GenerationRequest(
            prompt=rng.randint(0, 256, (72,)).astype(np.int32),
            max_new_tokens=max_new))
    return out


# ------------------------------------------------------------------ unit
class TestCostObservatoryUnit:
    def test_byte_accounting_abstract_and_exact(self):
        co = CostObservatory(clock=VirtualClock())
        f = jax.jit(lambda a, b: (a + 1.0, jnp.sum(b)))
        w = co.wrap(("decode", 1), f, host_out=(1,))
        a = np.zeros((4, 8), np.float32)      # host arg: 128 bytes h2d
        b = jnp.zeros((2, 2), jnp.float32)    # device arg: never charged
        w(a, b)
        rec = co.programs["decode[1]"]
        assert rec["calls"] == 1
        assert rec["h2d_bytes"] == 128
        assert rec["d2h_bytes"] == 4          # the () f32 host_out leaf
        assert rec["compiles"] == 1           # first call traced
        w(a, b)
        assert rec["calls"] == 2 and rec["compiles"] == 1
        assert co.totals["dispatches"] == 2
        assert co.totals["h2d_bytes"] == 256
        assert co.kind_calls("decode") == 2
        assert co.kind_calls("ragged") == 0

    def test_phase_attribution(self):
        co = CostObservatory(clock=VirtualClock())
        w = co.wrap(("prefill",), jax.jit(lambda x: x), host_out=())
        co.set_phase("admit")
        w(np.zeros(2, np.float32))
        co.set_phase("launch")
        w(np.zeros(2, np.float32))
        w(np.zeros(2, np.float32))
        co.set_phase(None)
        assert co.phases["admit"]["dispatches"] == 1
        assert co.phases["launch"]["dispatches"] == 2

    def test_export_delta_and_snapshot(self):
        co = CostObservatory(clock=VirtualClock())
        w = co.wrap(("ragged", 2, 10, 1, "jnp"), jax.jit(lambda x: x),
                    host_out=())
        w(np.zeros(4, np.float32))
        base = co.snapshot_full()
        s0 = co.snapshot()
        w(np.zeros(4, np.float32))
        w(np.zeros(4, np.float32))
        assert co.delta(s0)["dispatches"] == 2
        doc = co.export(base=base)
        assert doc["totals"]["dispatches"] == 2
        (prog,) = doc["programs"]
        assert prog["program"] == "ragged[2,10,1,jnp]"
        assert prog["calls"] == 2 and prog["kind"] == "ragged"
        full = co.export()
        assert full["totals"]["dispatches"] == 3
        json.dumps(full)                       # JSON-serializable

    def test_disabled_handout_is_raw(self, model):
        eng = _engine(model)
        # no observatory / disabled observatory: the accessor hands out
        # the RAW jitted program — zero wrapper on the hot path
        assert not isinstance(eng._prefill_fn(), _CountedProgram)
        eng.cost = CostObservatory().disable()
        assert not isinstance(eng._prefill_fn(), _CountedProgram)
        eng.cost.enable()
        assert isinstance(eng._prefill_fn(), _CountedProgram)


# ----------------------------------------------------------- tier ledger
class TestTierLedger:
    """ISSUE 16 satellite: KV-tier traffic (spill d2h / readmit h2d /
    fleet peer transfer) gets its OWN ledger — mirroring the PR-15
    collectives rule — so cache-plane bytes never pollute the
    per-program h2d/d2h counts."""

    def test_record_tier_unit_and_separation(self):
        co = CostObservatory(clock=VirtualClock())
        co.record_tier("d2h", 2, 4096)
        co.record_tier("d2h", 1, 2048)
        co.record_tier("h2d", 1, 2048)
        assert co.tier_bytes("d2h") == 6144
        assert co.tier_bytes("h2d") == 2048
        assert co.tier_bytes("peer") == 0      # unseen: explicit zero
        assert co.tiers["d2h"] == {"blocks": 3, "bytes": 6144}
        # THE SEPARATE-LEDGER RULE: tier traffic never touches the
        # per-program transfer totals or the dispatch count
        assert co.totals["h2d_bytes"] == 0
        assert co.totals["d2h_bytes"] == 0
        assert co.totals["dispatches"] == 0

    def test_export_delta_and_snapshot_carry_tiers(self):
        co = CostObservatory(clock=VirtualClock())
        co.record_tier("d2h", 1, 100)
        base = co.snapshot_full()
        assert base["tiers"]["d2h"] == {"blocks": 1, "bytes": 100}
        co.record_tier("d2h", 2, 200)
        co.record_tier("peer", 1, 50)
        doc = co.export(base=base)
        assert doc["tiers"] == {"d2h": {"blocks": 2, "bytes": 200},
                                "peer": {"blocks": 1, "bytes": 50}}
        full = co.export()
        assert full["tiers"]["d2h"] == {"blocks": 3, "bytes": 300}
        json.dumps(full)                       # JSON-serializable

    def test_engine_tier_traffic_never_pollutes_program_baselines(
            self, model):
        """A thrashed tiered engine moves real spill/readmit bytes —
        and the per-program totals still equal exactly the sum over
        the program records, as if the tier did not exist."""
        fams = [np.random.RandomState(900 + f).randint(
            0, 256, (16,)).astype(np.int32) for f in range(2)]
        reqs = []
        for i in range(3):
            for f in range(2):
                tail = np.random.RandomState(10 * f + i).randint(
                    0, 256, (5,)).astype(np.int32)
                reqs.append(GenerationRequest(
                    prompt=np.concatenate([fams[f], tail]),
                    max_new_tokens=3))
        eng = _engine(model, decode_chunk=1, prefix_cache=True,
                      prefix_block_size=8, prefix_blocks=2,
                      host_tier_bytes=1 << 24)
        co = CostObservatory()
        eng.cost = co
        for r in reqs:     # serial: each publish thrashes the 2-block pool
            eng.generate([r])
        pc = eng.prefix_cache
        assert pc.stats["spilled_blocks"] > 0
        assert pc.stats["readmitted_blocks"] > 0
        assert co.tier_bytes("d2h") > 0 and co.tier_bytes("h2d") > 0
        # bytes moved match the ledger's own block count × block bytes
        assert co.tiers["d2h"]["bytes"] == \
            pc.stats["spilled_blocks"] * pc.pool.block_nbytes
        assert co.tiers["h2d"]["bytes"] == \
            pc.stats["readmitted_blocks"] * pc.pool.block_nbytes
        # separation: totals are exactly the per-program sums
        progs = list(co.programs.values())
        assert co.totals["h2d_bytes"] == \
            sum(rec["h2d_bytes"] for rec in progs)
        assert co.totals["d2h_bytes"] == \
            sum(rec["d2h_bytes"] for rec in progs)

    def test_gateway_tier_series_and_profile_doc(self, model):
        """``serving_tier_bytes_total{direction}`` scrapes from a
        tiered gateway (d2h/h2d > 0, peer an explicit 0 — all three
        series exist), the ``serving_prefix_*`` tier counters/gauges
        agree with the trie's stats, and ``/debug/profile`` carries
        the tiers section without touching per-program columns."""
        fams = [np.random.RandomState(910 + f).randint(
            0, 256, (16,)).astype(np.int32) for f in range(2)]
        eng = _engine(model, decode_chunk=1, prefix_cache=True,
                      prefix_block_size=8, prefix_blocks=2,
                      host_tier_bytes=1 << 24)
        gw = ServingGateway(eng, start=False)  # installs gw.cost on eng
        for i in range(3):
            for f in range(2):
                tail = np.random.RandomState(20 * f + i).randint(
                    0, 256, (5,)).astype(np.int32)
                eng.generate([GenerationRequest(
                    prompt=np.concatenate([fams[f], tail]),
                    max_new_tokens=3)])
        pc = eng.prefix_cache
        assert pc.stats["spilled_blocks"] > 0
        fams_p = parse_prometheus(gw.registry.render())

        def val(name, **labels):
            key = tuple(sorted(labels.items()))
            return fams_p[name]["samples"][(name, key)]

        assert val("serving_tier_bytes_total", direction="d2h") == \
            gw.cost.tier_bytes("d2h") > 0
        assert val("serving_tier_bytes_total", direction="h2d") == \
            gw.cost.tier_bytes("h2d") > 0
        assert val("serving_tier_bytes_total", direction="peer") == 0
        assert val("serving_prefix_spilled_blocks_total") == \
            pc.stats["spilled_blocks"]
        assert val("serving_prefix_tier_hits_total") == \
            pc.stats["tier_hits"] > 0
        assert val("serving_prefix_readmitted_blocks_total") == \
            pc.stats["readmitted_blocks"] > 0
        assert val("serving_prefix_tier_blocks") == pc.tier.num_blocks > 0
        assert val("serving_prefix_tier_bytes") == pc.tier.bytes_used > 0
        assert val("serving_prefix_tier_bytes_capacity") == 1 << 24
        assert val("serving_prefix_cached_blocks") == \
            pc.num_cached_blocks
        doc = gw.profile_doc()
        tiers = doc["tiers"]
        assert tiers["host_tier_bytes"] == 1 << 24
        assert tiers["tier_blocks"] == pc.tier.num_blocks
        assert tiers["per_direction"]["d2h"]["bytes"] == \
            gw.cost.tier_bytes("d2h")
        assert "bytes_per_decoded_token" in tiers["per_direction"]["d2h"]

    def test_tierless_gateway_scrapes_explicit_zeros(self, model):
        gw = ServingGateway(_engine(model, decode_chunk=1, prefix_cache=True,
                                    prefix_block_size=8), start=False)
        fams_p = parse_prometheus(gw.registry.render())
        s = fams_p["serving_tier_bytes_total"]["samples"]
        for tdir in ("d2h", "h2d", "peer"):
            assert s[("serving_tier_bytes_total",
                      (("direction", tdir),))] == 0
        assert fams_p["serving_prefix_tier_bytes_capacity"]["samples"][
            ("serving_prefix_tier_bytes_capacity", ())] == 0
        # same idiom as collectives on a tp=1 engine: the export key
        # exists, empty — no occupancy section is synthesized
        assert gw.profile_doc()["tiers"] == {}

    def test_tier_counters_monotonic_across_rebuild(self, model):
        """A crash-recovery rebuild starts a fresh trie AND a fresh
        host tier, zeroing their stats — the gateway banks the dead
        incarnation's tier counts (CARRIED_PREFIX_STATS) so the
        ``serving_prefix_*`` tier series stay monotonic."""
        fams = [np.random.RandomState(920 + f).randint(
            0, 256, (16,)).astype(np.int32) for f in range(2)]

        def factory():
            return _engine(model, decode_chunk=1, prefix_cache=True,
                           prefix_block_size=8, prefix_blocks=2,
                           host_tier_bytes=1 << 24)

        plan = FaultPlan().at_step(8, "fatal")
        gw = ServingGateway(factory(), engine_factory=factory,
                            fault_hook=plan, retry_backoff_s=0.0,
                            start=False)
        reqs = []
        for i in range(3):
            for f in range(2):
                tail = np.random.RandomState(30 * f + i).randint(
                    0, 256, (5,)).astype(np.int32)
                reqs.append(GenerationRequest(
                    prompt=np.concatenate([fams[f], tail]),
                    max_new_tokens=3))
        gw.start()
        for r in reqs:             # serial: publishes land in order
            gw.submit(r).result()
        assert gw.restarts >= 1
        # the dead incarnation spilled before dying, and its counts
        # were banked into the carried base at the rebuild
        pc_base = gw._counter_state[1]
        assert pc_base["spilled_blocks"] > 0
        total = gw._pc_stat("spilled_blocks")
        assert total == pc_base["spilled_blocks"] + \
            gw.engine.prefix_cache.stats["spilled_blocks"]
        fams_p = parse_prometheus(gw.registry.render())
        assert fams_p["serving_prefix_spilled_blocks_total"]["samples"][
            ("serving_prefix_spilled_blocks_total", ())] == total
        gw.shutdown(drain=True, timeout=30)


# ------------------------------------------------------------ exactness
class TestExactAccounting:
    CONFIGS = (
        ("ragged", dict(prefill_chunk=32, prefix_block_size=8,
                        headroom_mult=None)),
        ("spec", dict(prefill_chunk=32, prefix_block_size=8,
                      headroom_mult=None, spec_decode=True, spec_k=3)),
    )

    @pytest.mark.slow  # 15 s exact-count duplicate: test_launch_attribution_
    # per_request below keeps the default exact-accounting rep (870s cap)
    def test_counts_exact_streams_unchanged(self, model):
        reqs = _reqs(3, max_new=4, long_prompt=True)
        for name, cfg in self.CONFIGS:
            base_eng = _engine(model, decode_chunk=1, **cfg)
            base = [o.tolist() for o in base_eng.generate(reqs)]
            eng = _engine(model, decode_chunk=1, **cfg)
            co = CostObservatory()
            eng.cost = co
            accessor = _count_accessor_launches(eng)
            out = [o.tolist() for o in eng.generate(reqs)]
            # observing never changes a token
            assert out == base, name
            # dispatch count == independent program-accessor count
            assert co.totals["dispatches"] == accessor["n"], name
            assert co.totals["dispatches"] > 0
            # per-kind split == the engine's own stats
            if name == "ragged":
                assert co.kind_calls("ragged") == \
                    eng.stats["unified_steps"]
            else:
                assert co.kind_calls("spec") == eng.stats["spec_steps"]
            # compile-once survives the counting facade (raw fns stay
            # in the jit-cache), and the warm run retraced nothing
            assert eng.decode_compilations() == 1, name
            assert co.totals["compiles"] == 0, name
            # boundary bytes flowed both ways
            assert co.totals["h2d_bytes"] > 0
            assert co.totals["d2h_bytes"] > 0
            # every launch landed in a named phase
            assert None not in co.phases
            assert co.phases.keys() <= {"admit", "plan", "launch",
                                        "host-accept"}

    def test_launch_attribution_per_request(self, model):
        eng = _engine(model, decode_chunk=1, prefill_chunk=32,
                      prefix_block_size=8, headroom_mult=None)
        seqs = [eng.submit(r) for r in _reqs(2, max_new=4,
                                             long_prompt=True)]
        while eng.has_work():
            eng.step()
        for seq in seqs:
            # every request rode >= 1 prefill launch + >= 1 decode
            assert seq.launches >= 2
        # the chunked long prompt paid one launch per chunk too
        assert seqs[-1].launches >= 3


# -------------------------------------------------------- counter tracks
class TestCounterTracks:
    def test_step_timeline_counter_events(self, model):
        tr = SpanTracer().enable()
        eng = _engine(model, decode_chunk=1)
        eng.tracer = tr
        eng.cost = CostObservatory()
        eng.generate(_reqs(2, max_new=4))
        evs = tr.events()
        counters = [e for e in evs if e["ph"] == "C"]
        names = {e["name"] for e in counters}
        assert {"dispatches", "transfer_bytes", "kv_blocks",
                "block_table_fill"} <= names
        steps = [e for e in evs if e["name"] == "step"]
        # one sample per track per step
        for track in names:
            assert len([e for e in counters if e["name"] == track]) \
                == len(steps)
        disp = [e for e in counters if e["name"] == "dispatches"]
        assert sum(e["args"]["per_step"] for e in disp) == \
            eng.cost.totals["dispatches"]
        xfer = [e for e in counters if e["name"] == "transfer_bytes"]
        assert all({"h2d", "d2h"} <= set(e["args"]) for e in xfer)
        # what the pool counts in O(1): the live / trie split is a walk
        # of every table, taken at scrape rate (``occupancy``), never by
        # a traced step
        kv = [e for e in counters if e["name"] == "kv_blocks"]
        pool = eng.cache.pool
        assert kv[-1]["args"] == {"used": pool.num_used,
                                  "free": pool.num_free}
        occ = eng.cache.occupancy()
        assert set(occ) == {"live", "trie", "free"}
        assert occ["live"] + occ["trie"] == kv[-1]["args"]["used"]
        assert occ["free"] == kv[-1]["args"]["free"]

    def test_no_counters_without_cost_or_tracer(self, model):
        # tracer on, cost absent: spans yes, dispatch counters no
        tr = SpanTracer().enable()
        eng = _engine(model, decode_chunk=1)
        eng.tracer = tr
        eng.generate(_reqs(1, max_new=2))
        names = {e["name"] for e in tr.events() if e["ph"] == "C"}
        assert "dispatches" not in names
        assert "transfer_bytes" not in names
        # KV occupancy is tracer-only — it still rides along, as the
        # pool's own two counts
        assert "kv_blocks" in names
        assert all(set(e["args"]) == {"used", "free"}
                   for e in tr.events() if e["name"] == "kv_blocks")


# ----------------------------------------------------- chaos determinism
class TestChaosDeterminism:
    def test_cost_accounting_byte_identical_and_monotonic(self, model):
        reqs = _chaos_workload()
        # warm every program (recovery-path buckets included)
        _chaos_run(model, reqs, with_plan=True, trace=True)
        outs1, _, gw1, eng1, plan1 = _chaos_run(
            model, reqs, with_plan=True, trace=True)
        outs2, _, gw2, eng2, plan2 = _chaos_run(
            model, reqs, with_plan=True, trace=True)
        assert outs1 == outs2 and plan1.log == plan2.log
        # the accounting replays byte-identically under VirtualClock
        doc1 = json.dumps(gw1.profile_doc(), sort_keys=True)
        doc2 = json.dumps(gw2.profile_doc(), sort_keys=True)
        assert doc1 == doc2
        d = json.loads(doc1)
        assert d["totals"]["dispatches"] > 0
        assert d["totals"]["decoded_tokens"] > 0
        assert d["totals"]["dispatches_per_decoded_token"] > 0
        # the observatory survived >= 3 engine rebuilds monotonic (it
        # is gateway-owned), and the warm replay retraced NOTHING —
        # compile-once across rebuilds, now measured rather than
        # inferred
        assert gw1.restarts >= 3
        assert d["totals"]["compiles"] == 0
        assert eng1.decode_compilations() == 1
        # per-program calls sum to the total (no unattributed launches)
        assert sum(p["calls"] for p in d["programs"]) == \
            d["totals"]["dispatches"]


# ------------------------------------------------------- gateway surface
class TestGatewaySurface:
    def test_metrics_families_and_values(self):
        # programs of its own: the last assertion is that a COLD start's
        # compiles are counted. On the jnp attention path, the cheapest
        # step program there is to lower
        model = serving_support.model("llama", seed=31,
                                      decode_attention="jnp")
        gw = ServingGateway(_engine(model, decode_chunk=1, jit_cache={}),
                            start=False)
        streams = [gw.submit(r) for r in _reqs(3, max_new=4)]
        gw.start()
        for s in streams:
            s.result()
        fams = parse_prometheus(gw.registry.render())
        gw.shutdown(drain=True, timeout=30)
        disp = fams["serving_dispatches_total"]
        assert disp["type"] == "counter"
        by_kind = {lab[0][1]: v for (_, lab), v in
                   disp["samples"].items()}
        assert set(by_kind) == {"prefill", "suffix", "psuffix",
                                "decode", "pdecode", "ragged", "mtick",
                                "spec"}
        assert by_kind["ragged"] > 0          # the engine default path
        assert sum(by_kind.values()) == gw.cost.totals["dispatches"]
        xfer = {lab[0][1]: v for (_, lab), v in
                fams["serving_transfer_bytes_total"]["samples"].items()}
        assert xfer["h2d"] > 0 and xfer["d2h"] > 0
        g = fams["serving_dispatches_per_decoded_token"]
        assert g["type"] == "gauge"
        (val,) = g["samples"].values()
        assert val == pytest.approx(
            gw.cost.totals["dispatches"]
            / max(gw._stat("tokens_generated"), 1))
        assert fams["serving_program_compiles_total"]["samples"][
            ("serving_program_compiles_total", ())] >= 1  # cold start

    def test_shared_prefix_cache_not_double_counted(self, model):
        """An adopted SHARED PrefixCache rides into every rebuilt
        engine with its stats intact — the rebuild carry must not bank
        them too (that would double hits/misses per restart)."""
        seed_eng = _engine(model, decode_chunk=1, prefix_cache=True,
                           prefix_block_size=8)
        shared = seed_eng.prefix_cache

        def factory():
            return _engine(model, decode_chunk=1, prefix_cache=shared,
                           prefix_block_size=8)

        plan = FaultPlan().at_step(2, "fatal")
        gw = ServingGateway(factory(), engine_factory=factory,
                            fault_hook=plan, retry_backoff_s=0.0,
                            start=False)
        streams = [gw.submit(r) for r in _reqs(3, max_new=4)]
        gw.start()
        for s in streams:
            s.result()
        assert gw.restarts >= 1
        # the shared trie's own counts ARE the totals — no carry
        assert gw._pc_stat("misses") == shared.stats["misses"]
        assert gw._pc_stat("hits") == shared.stats["hits"]
        gw.shutdown(drain=True, timeout=30)

    def test_stat_counters_monotonic_across_rebuild(self, model):
        """ISSUE 11 satellite: engine ``stats`` reset on crash-recovery
        rebuild; every derived /metrics counter must carry a
        gateway-side base. A scrape thread samples the affected series
        THROUGH the fault matrix and each must be non-decreasing."""
        clk = VirtualClock()

        def factory():
            return _engine(model, decode_chunk=1, prefix_cache=True,
                           prefix_block_size=8, prefill_chunk=32,
                           spec_decode=True, spec_k=3, headroom_mult=None,
                           step_clock=clk)

        plan = (FaultPlan(clock=clk)
                .at_step(3, "fatal").at_step(7, "pool")
                .at_step(11, "fatal"))
        gw = ServingGateway(factory(), engine_factory=factory,
                            fault_hook=plan, clock=clk,
                            retry_backoff_s=0.0, max_restarts=16,
                            start=False)
        streams = [gw.submit(r) for r in _chaos_workload()]
        series = ("prefill_chunks", "prefill_tokens_saved",
                  "spec_proposed", "spec_accepted", "preemptions",
                  "tokens_generated")
        samples = []
        stop = threading.Event()

        def scrape():
            while not stop.is_set():
                samples.append(
                    {k: gw._stat(k) for k in series}
                    | {"pc_" + k: gw._pc_stat(k)
                       for k in ("hits", "misses", "evictions")}
                    | {"dispatches": gw.cost.totals["dispatches"]})
                time.sleep(0.002)

        t = threading.Thread(target=scrape)
        t.start()
        gw.start()
        for s in streams:
            ids, reason = s.result()
            assert reason in ("stop", "length")
        stop.set()
        t.join(10)
        assert gw.restarts >= 2
        # the fix itself: the dead incarnations' counts were banked
        assert gw._stat_base["tokens_generated"] > 0
        fams = parse_prometheus(gw.registry.render())
        assert fams["serving_prefill_chunks_total"]["samples"][
            ("serving_prefill_chunks_total", ())] == \
            gw._stat("prefill_chunks")
        gw.shutdown(drain=True, timeout=30)
        assert len(samples) >= 2
        for key in samples[0]:
            vals = [s[key] for s in samples]
            assert all(a <= b for a, b in zip(vals, vals[1:])), \
                f"{key} went backwards: {vals}"


# ------------------------------------------------------------- live HTTP
@pytest.fixture(scope="class")
def server(model):
    srv = serve(model, port=0, num_slots=NUM_SLOTS, max_seq_len=S_MAX,
                max_queue=8, model_name="cost-test")
    s = srv.gateway.submit(GenerationRequest(prompt=[1, 2, 3, 4],
                                             max_new_tokens=2))
    s.result()
    yield srv
    srv.shutdown(drain=False, timeout=30)


def _get(server, path, timeout=60):
    try:
        with urllib.request.urlopen(server.url + path,
                                    timeout=timeout) as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


class TestDebugProfileHTTP:
    def test_aggregate_profile(self, server):
        status, doc = _get(server, "/debug/profile")
        assert status == 200
        assert doc["window_steps"] is None
        t = doc["totals"]
        assert t["dispatches"] > 0 and t["decoded_tokens"] > 0
        assert t["dispatches_per_decoded_token"] > 0
        assert t["h2d_bytes_per_decoded_token"] > 0
        assert doc["programs"]
        for p in doc["programs"]:
            assert {"program", "kind", "calls", "h2d_bytes",
                    "d2h_bytes", "compiles", "wall_ewma_s",
                    "share_of_wall"} <= set(p)
        assert doc["phases"]
        assert abs(sum(p["share_of_wall"]
                       for p in doc["programs"]) - 1.0) < 0.01

    def test_step_bounded_window(self, server):
        stream = server.gateway.submit(GenerationRequest(
            prompt=[9, 10, 11, 12], max_new_tokens=96))
        status, doc = _get(server, "/debug/profile?steps=4&timeout_s=30")
        stream.result()
        assert status == 200
        # window_steps reports steps actually CAPTURED (== the ask
        # here; a timed-out window reports fewer + truncated flag)
        assert doc["window_steps"] == 4
        assert doc["window_steps_requested"] == 4
        assert doc["window_truncated"] is False
        # a 4-step window over a decoding request: exactly one unified
        # launch per captured step; the request's own prefill launch
        # rides along iff its admission landed inside the window
        (prog,) = [p for p in doc["programs"] if p["kind"] == "ragged"]
        assert prog["calls"] == 4
        assert 4 <= doc["totals"]["dispatches"] <= 5
        status, _ = _get(server, "/debug/profile?steps=bogus")
        assert status == 400

    def test_debug_requests_cost_columns(self, server):
        stream = server.gateway.submit(GenerationRequest(
            prompt=[5, 6, 7, 8], max_new_tokens=64))
        row = None
        for _ in range(200):
            status, doc = _get(server, "/debug/requests")
            assert status == 200
            rows = [r for r in doc["requests"] if r["id"] == stream.id]
            if rows and rows[0]["state"] == "running" \
                    and rows[0]["generated_tokens"] > 1:
                row = rows[0]
                break
            time.sleep(0.02)
        assert row is not None, "request never showed as running"
        assert row["launches"] >= 2        # prefill + >= 1 decode
        assert row["kv_bytes"] > 0
        bm = server.gateway.engine.cache.pool
        assert row["kv_bytes"] % bm.block_nbytes == 0
        stream.result()


# ------------------------------------------------------ guard discipline
RECORDING_METHODS = {"instant", "complete", "span", "counter", "wrap",
                     "set_phase"}
GUARD_RE = re.compile(r"=\s*self\._(tr|co)\(\)")
GUARD_NAMES = {"tr", "tracer", "co", "cost"}
SERVING_DIR = (pathlib.Path(__file__).resolve().parent.parent
               / "paddle_tpu" / "serving")


def _layer_body_source(src, fn_name):
    """The source of a scanned layer body by its function's name;
    ``_decoder_layer``'s is its own and ``_mixer_ffn_layer``'s, the FFN half
    it shares with the hybrid models' linear layers."""
    names = (fn_name, "_mixer_ffn_layer") if fn_name == "_decoder_layer" \
        else (fn_name,)
    return "".join(src.split(f"def {n}(")[1].split("\ndef ")[0]
                   for n in names)


class TestGuardDiscipline:
    """ISSUE 11 satellite: the ≤1%-disabled-overhead property holds
    only while every tracer/cost instrumentation site goes through the
    one-attribute guards (``_tr()``/``_co()``). This static sweep makes
    the discipline un-regressable as call sites accumulate."""

    def _violations(self):
        violations, guarded = [], 0
        for path in sorted(SERVING_DIR.rglob("*.py")):
            src = path.read_text()
            tree = ast.parse(src)
            funcs = [n for n in ast.walk(tree)
                     if isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))]
            for fn in funcs:
                params = {a.arg for a in fn.args.args}
                fn_src = ast.get_source_segment(src, fn) or ""
                for node in ast.walk(fn):
                    if not (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Attribute)
                            and node.func.attr in RECORDING_METHODS):
                        continue
                    recv = node.func.value
                    where = f"{path.name}:{node.lineno}"
                    if isinstance(recv, ast.Attribute) and \
                            recv.attr in ("tracer", "cost"):
                        # direct self.tracer.X(...) — always unguarded
                        violations.append(
                            f"{where}: direct .{recv.attr}"
                            f".{node.func.attr}() bypasses the guard")
                        continue
                    if not (isinstance(recv, ast.Name)
                            and recv.id in GUARD_NAMES):
                        continue        # unrelated API (e.g. registry)
                    if recv.id in params or GUARD_RE.search(fn_src):
                        guarded += 1    # guard-local or caller-guarded
                    else:
                        violations.append(
                            f"{where}: {recv.id}.{node.func.attr}() "
                            f"without a `= self._tr()/_co()` guard in "
                            f"{fn.name}()")
        return violations, guarded

    def test_every_instrumentation_site_is_guarded(self):
        violations, guarded = self._violations()
        assert not violations, "\n".join(violations)
        # sanity: the sweep actually sees the instrumentation
        assert guarded >= 20, f"only {guarded} guarded sites found"

    def test_sweep_sees_the_multitick_step(self):
        """ISSUE 13 satellite: the multi-tick step path must sit
        behind the same one-attribute guards as every other step
        path. The engine's ``_multitick_step`` is inside the swept
        tree by construction; pin that it (a) exists, (b) contains
        tracer/cost instrumentation, and (c) that instrumentation is
        guard-disciplined (the sweep above would flag violations —
        this test makes sure the sweep actually has multi-tick sites
        to look at, so a refactor that moved them out of serving/
        could not silently shrink coverage)."""
        src = (SERVING_DIR / "engine.py").read_text()
        assert "_multitick_step" in src
        fn = src.split("def _multitick_step(")[1].split("\n    def ")[0]
        # the step's instrumentation goes through the guards...
        assert "tr = self._tr()" in fn and "co = self._co()" in fn
        # ...and the hot sites never touch self.tracer/self.cost raw
        assert "self.tracer." not in fn and "self.cost." not in fn
        # the program handout rides the counting chokepoint, so the
        # mtick program's dispatches are exactly attributed
        assert "_wrap_prog" in src.split("def _mtick_fn(")[1].split(
            "\n    def ")[0]

    def test_sweep_sees_the_quantized_kv_paths(self):
        """ISSUE 14 satellite: the int8-KV append/dequant call sites
        live inside the swept tree and stay guard-disciplined. Every
        quantized append routes through ONE helper (``_kv_write`` —
        quantize-on-write cannot fork per site), the packed forward's
        attention unpacks scales through ``_kv_attn_args`` (the one
        dequant handoff), and the engine hands pool arguments out
        through ``kv_args()`` at the SAME ``_wrap_prog``-counted
        launch sites as before — so quantized dispatches are exactly
        attributed and no new raw tracer/cost touch appeared."""
        dec = (SERVING_DIR / "decode.py").read_text()
        for fn_name in ("_packed_span_forward", "_fused_decode_tick",
                        "_paged_suffix_prefill_impl"):
            body = dec.split(f"def {fn_name}(")[1].split("\ndef ")[0]
            assert "_kv_write(" in body, fn_name
            assert "_kv_attn_args(" in body or "_kv_gather_rows(" \
                in body, fn_name
            # no stray raw pool scatter survived the refactor: appends
            # that bypass _kv_write would silently skip quantization
            assert ".at[phys" not in body, fn_name
        eng = (SERVING_DIR / "engine.py").read_text()
        for step in ("_unified_step", "_multitick_step", "_spec_step"):
            body = eng.split(f"def {step}(")[1].split("\n    def ")[0]
            assert "kv_args()" in body, step
            assert "self.tracer." not in body \
                and "self.cost." not in body, step
        # the quantized program variants ride the same counted handout
        for fn_name in ("_ragged_fn", "_mtick_fn", "_spec_fn",
                        "_suffix_fn", "_prefill_fn"):
            body = eng.split(f"def {fn_name}(")[1].split("\n    def ")[0]
            assert "_wrap_prog" in body, fn_name
            assert "_kvtag" in body or "_wtag" in body, fn_name

    def test_sweep_pins_a8_layer_body_dequant_free(self):
        """ISSUE 19 satellite: under ``quantize_activations`` the
        scanned layer body is PROVABLY dequant-free — no int8 weight is
        ever materialized at fp in the layer body; the only fp
        materialization is the int32 accumulator's post-dot rescale.
        Pinned structurally (AST, not substrings) so a refactor that
        quietly re-introduced a ``q.astype(f32) * s`` weight dequant
        into the a8 path fails here, not in a perf trace."""
        src = (SERVING_DIR / "decode.py").read_text()
        tree = ast.parse(src)
        fns = {n.name: n for n in ast.walk(tree)
               if isinstance(n, ast.FunctionDef)}
        # the a8 short-circuit is the FIRST statement of _dq_layer:
        # nothing dequantizes ahead of the early return
        first = [n for n in fns["_dq_layer"].body
                 if not (isinstance(n, ast.Expr)
                         and isinstance(n.value, ast.Constant))][0]
        assert isinstance(first, ast.If) \
            and isinstance(first.body[0], ast.Return)
        # _dq_head's a8 branch passes the int8 pair through (transpose
        # only) — it never falls into the _dq call below it
        head_first = [n for n in fns["_dq_head"].body
                      if isinstance(n, ast.If)][0]
        assert not any(isinstance(c, ast.Call)
                       and isinstance(c.func, ast.Name)
                       and c.func.id == "_dq"
                       for n in head_first.body for c in ast.walk(n))
        # none of the int8x8 projection helpers reach the dequant
        # helper (directly or via _dq_layer)
        for name in ("_a8_apply", "_a8_dot", "quantize_act_rows",
                     "_qkv_proj", "_swiglu_proj", "_o_proj",
                     "_head_logits"):
            calls = [n for n in ast.walk(fns[name])
                     if isinstance(n, ast.Call)
                     and isinstance(n.func, ast.Name)]
            assert not any(c.func.id in ("_dq", "_dq_layer")
                           for c in calls), name
        # _a8_apply: ONE dot_general with int32 accumulate, and the
        # single astype applies to the accumulator — never the weight
        a8 = fns["_a8_apply"]
        astypes = [n for n in ast.walk(a8) if isinstance(n, ast.Call)
                   and isinstance(n.func, ast.Attribute)
                   and n.func.attr == "astype"]
        assert len(astypes) == 1
        assert isinstance(astypes[0].func.value, ast.Name) \
            and astypes[0].func.value.id == "acc"
        dots = [n for n in ast.walk(a8) if isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "dot_general"]
        assert len(dots) == 1
        assert any(kw.arg == "preferred_element_type"
                   for kw in dots[0].keywords)
        # every scanned layer body routes its projections through the
        # structure-dispatch helpers — an inline einsum could not
        # reintroduce a dequant site unnoticed
        # (the whole-prompt prefill and the packed-span forward share
        # ONE body, ``_decoder_layer``, and hand it ``_dq_layer``'s output)
        # (since PR 54 ``_decoder_layer`` is the attention around
        # ``_mixer_ffn_layer``, which holds the FFN half for both kinds of
        # layer: the two are the one body)
        assert "_mixer_ffn_layer(" in src.split(
            "def _decoder_layer(")[1].split("\ndef ")[0]
        for fn_name in ("_decoder_layer", "_fused_decode_tick",
                        "_paged_suffix_prefill_impl"):
            body = _layer_body_source(src, fn_name)
            for helper in ("_qkv_proj(", "_swiglu_proj(", "_o_proj("):
                assert helper in body, (fn_name, helper)
        for fn_name in ("_packed_span_forward", "_fused_decode_tick",
                        "_paged_suffix_prefill_impl", "_prefill_impl"):
            body = src.split(f"def {fn_name}(")[1].split("\ndef ")[0]
            assert "_dq_layer(" in body, fn_name
        for fn_name in ("_packed_span_forward", "_prefill_impl"):
            body = src.split(f"def {fn_name}(")[1].split("\ndef ")[0]
            assert "_decoder_layer(" in body, fn_name

    def test_sweep_sees_the_tp_launch_path(self):
        """ISSUE 15 satellite: the tensor-parallel launch path stays
        guard-disciplined. Collective-byte accounting (the one NEW
        instrumentation the sharded path adds) routes through ONE
        engine helper (``_record_collectives``) and every call site
        sits behind the ``co = self._co()`` guard — the sweep above
        would flag a raw touch; this test makes sure the TP sites are
        actually inside the swept tree. The sharded programs ride the
        SAME ``_wrap_prog`` chokepoint (the tp tag joins the key, so
        dispatch attribution stays exact per variant), and the
        builders' shard_map wiring lives in decode.py where the
        quantized-path sweep already looks."""
        eng = (SERVING_DIR / "engine.py").read_text()
        assert "_record_collectives" in eng
        # every _record_collectives call site is co-guarded: the call
        # always receives the guarded `co` local, never self.cost
        sites = list(re.finditer(
            r"self\._record_collectives\(\s*([a-z_]+)", eng))
        assert len(sites) >= 5      # unified/mtick/spec/cold/suffix
        assert all(m.group(1) == "co" for m in sites)
        assert "self.cost.record_collective" not in eng
        # the sharded program handout rides the counted chokepoint
        # with the tp tag in the key
        for fn_name in ("_ragged_fn", "_mtick_fn", "_spec_fn",
                        "_suffix_fn", "_prefill_fn"):
            body = eng.split(f"def {fn_name}(")[1].split("\n    def ")[0]
            assert "_wrap_prog" in body, fn_name
            assert "_tptag" in body, fn_name
        # the TP wiring lives in the swept decode module: shard_map
        # wrapper + param/pool partition specs + the per-layer reduce
        dec = (SERVING_DIR / "decode.py").read_text()
        for name in ("_tp_shard", "_params_pspec", "_pool_pspec",
                     "_tp_allreduce"):
            assert f"def {name}(" in dec, name
        # every layer body applies tp_reduce at BOTH sites (o-proj +
        # down-proj) — the one-all-reduce-pair-per-layer contract
        # (``_decoder_layer`` is the body of the whole-prompt prefill and
        # of the packed-span forward, which pass it their ``tp_reduce``)
        for fn_name in ("_decoder_layer", "_fused_decode_tick",
                        "_paged_suffix_prefill_impl"):
            body = _layer_body_source(dec, fn_name)
            assert body.count("tp_reduce(o)") == 1, fn_name
            assert body.count("tp_reduce(m)") == 1, fn_name
        for fn_name in ("_packed_span_forward", "_prefill_impl"):
            body = dec.split(f"def {fn_name}(")[1].split("\ndef ")[0]
            assert body.count("tp_reduce=tp_reduce") == 1, fn_name

    def test_sweep_sees_the_overlap_path(self):
        """The collective-overlap path stays inside the counted/guarded
        tree. (a) The overlap schedule is constructed at ONE site
        (``_tp_allreduce``) and applied at exactly the o-proj +
        down-proj ``tp_reduce`` pair the per-layer contract already
        pins — the three DECODE builders pass ``overlap=`` while the
        prefill/suffix builders cannot (decode latency is the target;
        prefill keys stay banked). (b) Every step program is handed
        out through ``_wrap_prog``. (c) The census accessor rides the ``_wrap_prog`` chokepoint: the
        ONE ``record_census`` call site is ``_CountedProgram.__call__``
        — no serving code records a census of its own."""
        dec_path = SERVING_DIR / "decode.py"
        dec = dec_path.read_text()
        tree = ast.parse(dec)
        top = {n.name: n for n in tree.body
               if isinstance(n, ast.FunctionDef)}

        def calls_in(fn, callee):
            return [n for n in ast.walk(fn)
                    if isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Name)
                    and n.func.id == callee]

        # (a) one construction site: _overlap_reduce/_permute_allreduce
        # are referenced (outside their own defs) only from
        # _tp_allreduce and _overlap_reduce respectively
        for helper, owner in (("_overlap_reduce", "_tp_allreduce"),
                              ("_permute_allreduce", "_tp_allreduce")):
            users = [name for name, fn in top.items()
                     if name != helper
                     and any(isinstance(n, ast.Name) and n.id == helper
                             for n in ast.walk(fn))]
            assert users == [owner], (helper, users)
        # ...and exactly the decode-step builders request the overlap
        with_ov, without_ov = [], []
        for name, fn in top.items():
            for call in calls_in(fn, "_tp_allreduce"):
                kwargs = {kw.arg for kw in call.keywords}
                (with_ov if "overlap" in kwargs
                 else without_ov).append(name)
        assert sorted(with_ov) == ["build_multitick_step_fn",
                                   "build_ragged_step_fn",
                                   "build_spec_verify_fn"]
        assert sorted(without_ov) == ["build_paged_suffix_prefill_fn",
                                      "build_prefill_fn"]
        # the overlapped reduce lands at the SAME two per-layer sites
        # the tp contract pins (tp_reduce(o) / tp_reduce(m) above) —
        # no third application point exists anywhere in the module
        assert dec.count("tp_reduce(") == dec.count("tp_reduce(o)") \
            + dec.count("tp_reduce(m)") + dec.count("tp_reduce(x)")
        # (b) the step programs ride the counted handouts
        eng = (SERVING_DIR / "engine.py").read_text()
        for fn_name in ("_ragged_fn", "_mtick_fn", "_spec_fn",
                        "_suffix_fn", "_prefill_fn"):
            fbody = eng.split(f"def {fn_name}(")[1].split("\n    def ")[0]
            assert "_wrap_prog" in fbody, fn_name
        # (c) census recording has ONE call site: the counted-program
        # chokepoint in the profiler itself
        cost_src = (SERVING_DIR.parent / "profiler" / "cost.py").read_text()
        assert cost_src.count("co.record_census(") == 1
        assert "_CountedProgram" in cost_src.split(
            "co.record_census(")[0].rsplit("class ", 1)[1]
        serving_srcs = "".join(p.read_text()
                               for p in SERVING_DIR.rglob("*.py"))
        assert "record_census" not in serving_srcs

    def test_sweep_sees_the_tier_path(self):
        """ISSUE 16 satellite: the KV-tier spill/readmit/transfer call
        sites live inside the swept tree and stay guard-disciplined.
        The trie has no driver-installed tracer of its own, so the
        engine's ``_co()`` is the ONE chokepoint that hands it the
        observatory (``pc.cost`` sync) — and every ``record_tier``
        site reads a None-guarded local, never ``self.cost`` raw. The
        transfer programs ride the compile-once lru-cache registry
        (``kv_cache.tier_compilations``), so spilling a block can
        never add a jit key a future refactor would miss."""
        pcs = (SERVING_DIR / "prefix_cache.py").read_text()
        # spill (d2h) and readmit (h2d) both record through the
        # guarded local; no raw self.cost touch anywhere in the trie
        assert "co = self.cost" in pcs
        assert "self.cost.record_tier" not in pcs
        assert len(re.findall(r"co\.record_tier\(", pcs)) >= 2
        flt = (SERVING_DIR / "fleet" / "fleet.py").read_text()
        assert "self.cost.record_tier" not in flt
        assert re.search(r"co\.record_tier\(\s*\"peer\"", flt)
        # the engine's _co() guard is where the trie gets (and loses)
        # its observatory — one attribute sync, same discipline as the
        # handout guards
        eng = (SERVING_DIR / "engine.py").read_text()
        co_fn = eng.split("def _co(")[1].split("\n    def ")[0]
        assert "prefix_cache" in co_fn and "pc.cost" in co_fn
        # compile-once transfer pair: runtime-scalar block ids through
        # the registered lru-cached programs, counted by the accessor
        kvc = (SERVING_DIR / "kv_cache.py").read_text()
        for name in ("_tier_fetch", "_tier_inject", "tier_compilations"):
            assert f"def {name}(" in kvc, name
        assert "_TIER_PROGRAMS" in kvc
        bm = (SERVING_DIR / "block_manager.py").read_text()
        assert "_tier_fetch" in bm and "_tier_inject" in bm

    def test_sweep_covers_the_fleet_package(self):
        """ISSUE 12 satellite: the rglob sweep must keep covering
        ``serving/fleet/`` — the fleet's router-decision/failover/
        migration instants ride the same one-attribute ``_tr()``
        discipline as the engine's sites, and a future re-layout that
        moved the fleet out of ``serving/`` would silently shrink the
        sweep."""
        swept = {p.name for p in SERVING_DIR.rglob("*.py")}
        assert {"fleet.py", "router.py", "replica.py"} <= swept
        # and the fleet actually contributes guarded sites: the fleet
        # module's _tr() pattern must appear at least once
        fleet_src = (SERVING_DIR / "fleet" / "fleet.py").read_text()
        assert GUARD_RE.search(fleet_src) is not None

    def test_sweep_sees_the_policy_paths(self):
        """ISSUE 18 satellite: the multi-tenant policy package lives
        inside the swept tree and its decision sites stay
        guard-disciplined. The scheduler's admission decisions record
        through the same nullable ``_tr()`` idiom as the engine (the
        engine syncs the alias at the top of every step, BEFORE
        ``_policy_preempt`` runs, so preemption and headroom instants
        ride the step's already-guarded tracer), and the engine's
        SLO-preemption site reads the guarded local — a refactor that
        moved the policy out of ``serving/`` or grew a raw
        ``self.tracer.`` touch would silently shed the ≤1%-disabled-
        overhead property on the hottest new decision path."""
        swept = {p.name for p in SERVING_DIR.rglob("*.py")}
        assert {"classes.py", "admission.py", "victim.py"} <= swept
        adm = (SERVING_DIR / "policy" / "admission.py").read_text()
        body = adm.split("def admissions(")[1].split("\n    def ")[0]
        assert "tr = self._tr()" in body
        assert "self.tracer." not in body
        eng = (SERVING_DIR / "engine.py").read_text()
        pp = eng.split("def _policy_preempt(")[1].split("\n    def ")[0]
        assert "tr = self._tr()" in pp
        assert "self.tracer." not in pp
        # the step syncs the scheduler's alias before consulting policy
        assert "self.scheduler.tracer = tr" in eng
        assert eng.index("self.scheduler.tracer = tr") < \
            eng.index("self._policy_preempt(finished)")


# ---------------------------------------------------- profiler CLI (json)
# ------------------------------------------------------ the census's rules
def _launch(x):
    """One interpreted kernel launch; its body holds a loop of its own."""
    def kernel(x_ref, o_ref):
        o_ref[...] = jax.lax.while_loop(lambda a: a[0] < 3.0,
                                        lambda a: a + 1.0, x_ref[...])
    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        x.shape, x.dtype), interpret=True)(x)


def _scanned(x):
    return jax.lax.scan(lambda c, _: (_launch(c), None), x, None,
                        length=3)[0]


def _looped(x):
    return jax.lax.while_loop(lambda c: c[0] < 5.0,
                              lambda c: _launch(_launch(c)), x)


def _branched(x):
    return jax.lax.cond(x[0] > 0, _launch,
                        lambda c: _launch(_launch(_launch(c))), x)


def _sharded(x):
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("x",))
    spec = jax.sharding.PartitionSpec("x")
    return jax.shard_map(lambda c: jax.lax.psum(_launch(c), "x"),
                         mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)


@jax.custom_vjp
def _with_vjp(x):
    return _launch(x)


_with_vjp.defvjp(lambda x: (_launch(x), None), lambda _, g: (g,))


def _contained(x):
    return jax.jit(_launch)(x) + jax.checkpoint(_launch)(x) + _with_vjp(x)


def _while_in_scan(x):
    return jax.lax.scan(lambda c, _: (_looped(c), None), x, None,
                        length=4)[0]


_TWO_LAUNCHES = {"pallas_calls": 2, "collectives": 0}

#: rule -> (program, pallas_calls, collectives, loop_bodies)
CENSUS_RULES = {
    "scan-times-trip-count": (_scanned, 3, 0, []),
    "while-once-and-its-body": (_looped, 2, 0, [_TWO_LAUNCHES]),
    "cond-max-of-branches": (_branched, 3, 0, []),
    "kernel-body-not-walked": (_launch, 1, 0, []),
    "collectives-under-shard-map": (_sharded, 1, 1, []),
    "jit-remat-custom-vjp-walked": (_contained, 3, 0, []),
    "while-in-scan-one-body": (_while_in_scan, 8, 0, [_TWO_LAUNCHES]),
    "empty-program-zeros": (lambda x: x, 0, 0, []),
}


@pytest.mark.parametrize("rule", list(CENSUS_RULES))
def test_census_rule(rule):
    """``cost._census_walk``'s rules, each on a program of a few lines
    (traced by ``jax.make_jaxpr``, never run)."""
    fn, pallas, coll, bodies = CENSUS_RULES[rule]
    assert jaxpr_census(fn, jnp.zeros((8,), jnp.float32)) == {
        "pallas_calls": pallas, "collectives": coll, "loop_bodies": bodies}


class TestProfilerCLIChrome:
    @pytest.fixture(scope="class")
    def trace_file(self, model, tmp_path_factory):
        tr = SpanTracer().enable()
        eng = _engine(model, decode_chunk=1)
        eng.tracer = tr
        eng.cost = CostObservatory()
        eng.generate(_reqs(2, max_new=4))
        p = tmp_path_factory.mktemp("chrome") / "trace.json"
        p.write_text(json.dumps(tr.export()))
        return str(p)

    def _run(self, argv):
        from paddle_tpu.profiler.__main__ import main
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = main(argv)
        return rc, buf.getvalue()

    def test_text_table_per_lane_self_time(self, trace_file):
        rc, out = self._run([trace_file, "--top", "6"])
        assert rc == 0
        assert "self_ms" in out and "engine:" in out
        assert "counter samples" in out

    def test_json_and_top_honored(self, trace_file):
        rc, out = self._run([trace_file, "--json", "--top", "3"])
        assert rc == 0
        doc = json.loads(out)
        assert 0 < len(doc["rows"]) <= 3
        for r in doc["rows"]:
            assert {"lane", "name", "count", "total_ms",
                    "self_ms"} <= set(r)
        # self time <= total time, always
        assert all(r["self_ms"] <= r["total_ms"] + 1e-6
                   for r in doc["rows"])

    def test_unparseable_exits_one(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("not json at all")
        rc, out = self._run([str(bad)])
        assert rc == 1 and "unparseable" in out
        noevents = tmp_path / "noevents.json"
        noevents.write_text(json.dumps({"foo": 1}))
        rc, out = self._run([str(noevents)])
        assert rc == 1
        rc, out = self._run([str(noevents), "--json"])
        assert rc == 1 and "error" in json.loads(out)
