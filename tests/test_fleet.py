"""Engine fleet (ISSUE 12): replicated serving with prefix-affinity
routing, failover-to-sibling, and live request migration
(serving/fleet/, README "Engine fleet").

The acceptance matrix:

- ROUTER POLICIES are pure and deterministic: least-loaded tie-breaks
  to the lowest index, prefix-affinity wins only within the load band,
  round-robin rotates — and a fixed submission order routes
  identically on every replay (the VirtualClock chaos-replay pin);
- REPLICA KILL mid-decode (supervision exhausted under the chaos
  matrix) loses ZERO requests: every live stream fails over to a
  sibling by ``restore()`` recompute and continues BYTE-IDENTICALLY —
  greedy and seeded-sampled — to an unkilled single-engine run;
- LIVE MIGRATION moves an in-flight request between healthy replicas
  (evict: chain donated + PRNG snapshot; adopt: restore) with the
  stream byte-identical, and drain/rebalance ride it;
- COMPILE-ONCE holds per pool geometry across the fleet: same-geometry
  replicas share one jit-cache dict and each still reports
  ``decode_compilations() == 1``; mixed geometries isolate their
  dicts (pooling shape-keyed traces would break both pins);
- /METRICS carries a ``replica`` label on every per-replica series in
  ONE shared registry, and any single replica's crash-recovery rebuild
  keeps its series monotonic (per-replica carried counter bases);
- the fleet HTTP surface: routed completions, ``GET /debug/fleet``,
  ``POST /fleet/drain`` / ``/fleet/rebalance``, aggregated
  ``/healthz``.
"""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.serving import FaultPlan, GenerationRequest, VirtualClock
from paddle_tpu.serving.fleet import (EngineFleet, LeastLoadedRouter,
                                      PrefixAffinityRouter,
                                      RoundRobinRouter, make_router)

import serving_support
from serving_support import (BS, CHUNK, S_MAX, SLOTS, clone as _clone,
                             prompt as _prompt, wait_until as _await)
from test_metrics_prom import parse_prometheus


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)


def _req(ps, n=12, **kw):
    kw.setdefault("max_new_tokens", 8)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


#: the standard mixed workload: greedy shorts, one seeded-sampled row,
#: one long prompt that chunks (60 > CHUNK)
def _traffic():
    return [_req(1), _req(2, n=10),
            _req(3, temperature=0.9, top_k=5, seed=123),
            _req(4, n=60, max_new_tokens=5)]


def _baseline(model, reqs, num_slots=SLOTS):
    """Fault-free single-engine oracle streams for the same requests."""
    eng = serving_support.engine(model, num_slots=num_slots,
                                 prefix_cache=True)
    return [o.tolist() for o in eng.generate([_clone(r) for r in reqs])]


def _fleet(model, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("router", "round-robin")
    kw.setdefault("num_slots", SLOTS)
    kw.setdefault("max_seq_len", S_MAX)
    kw.setdefault("prefix_block_size", BS)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("max_queue", 32)
    kw.setdefault("retry_backoff_s", 0.0)
    kw.setdefault("start", False)
    return EngineFleet(model, **kw)


# ----------------------------------------------------------- router units
class _StubReplica:
    """Router-facing stand-in: fixed load + per-prompt match table."""

    def __init__(self, index, load, matches=()):
        self.index = index
        self._load = load
        self._matches = dict(matches)
        self.routable = True
        self.alive = True

    def load(self):
        return self._load

    def prefix_match_tokens(self, prompt):
        return self._matches.get(bytes(np.asarray(prompt)), 0)


class TestRouterPolicies:
    def test_least_loaded_ties_break_to_lowest_index(self):
        reps = [_StubReplica(2, 5), _StubReplica(0, 5), _StubReplica(1, 3)]
        r = LeastLoadedRouter()
        order = r.rank(_req(1), reps)
        assert [x.index for x in order] == [1, 0, 2]
        # exact tie everywhere: pure index order
        reps = [_StubReplica(i, 7) for i in (2, 1, 0)]
        assert [x.index for x in r.rank(_req(1), reps)] == [0, 1, 2]

    def test_affinity_wins_only_within_the_load_band(self):
        req = _req(5)
        key = bytes(np.asarray(req.prompt))
        warm_near = _StubReplica(1, load=4, matches={key: 32})
        cold_min = _StubReplica(0, load=0)
        warm_far = _StubReplica(2, load=40, matches={key: 64})
        r = PrefixAffinityRouter(band=16)
        order = r.rank(req, [cold_min, warm_near, warm_far])
        # warm_near is in band (4 <= 0+16) and matches -> wins; the
        # MOST-matching replica is 40 loads past the floor -> ranked
        # after the whole band no matter its trie
        assert [x.index for x in order] == [1, 0, 2]
        # band=0: only exact-minimum-load replicas are affinity
        # candidates; warm_near (load 4) drops out of the band
        r0 = PrefixAffinityRouter(band=0)
        assert [x.index for x in r0.rank(
            req, [cold_min, warm_near, warm_far])][0] == 0

    def test_affinity_ties_break_by_load_then_index(self):
        req = _req(6)
        key = bytes(np.asarray(req.prompt))
        a = _StubReplica(0, load=2, matches={key: 16})
        b = _StubReplica(1, load=1, matches={key: 16})
        c = _StubReplica(2, load=1, matches={key: 16})
        order = PrefixAffinityRouter(band=16).rank(req, [a, b, c])
        assert [x.index for x in order] == [1, 2, 0]

    def test_round_robin_rotates(self):
        reps = [_StubReplica(i, 0) for i in range(3)]
        r = RoundRobinRouter()
        heads = [r.rank(_req(1), reps)[0].index for _ in range(6)]
        assert heads == [0, 1, 2, 0, 1, 2]

    def test_make_router(self):
        assert isinstance(make_router("least-loaded"), LeastLoadedRouter)
        assert make_router("affinity", band=3).band == 3
        custom = RoundRobinRouter()
        assert make_router(custom) is custom
        with pytest.raises(ValueError, match="unknown router"):
            make_router("random")
        with pytest.raises(ValueError, match="band"):
            PrefixAffinityRouter(band=-1)


# ------------------------------------------------- routing determinism
class TestRoutingDeterminism:
    @pytest.mark.slow  # 6 s replay duplicate: test_kill_replay_is_deterministic
    # below keeps the default fleet-determinism rep (870s cap)
    def test_virtual_clock_replay_routes_identically(self, model):
        """The chaos-replay pin: policies read replica state only, so
        the same submission order over a VirtualClock fleet produces
        the same decision log and the same streams, twice."""
        reqs = _traffic()
        want = _baseline(model, reqs)
        runs = []
        for _ in range(2):
            clk = VirtualClock()
            fleet = _fleet(model, router="least-loaded", clock=clk)
            streams = [fleet.submit(_clone(r)) for r in reqs]
            fleet.start()
            outs = [st.result() for st in streams]
            runs.append(([i for _, i in fleet.decisions],
                         [ids.tolist() for ids, _ in outs]))
            fleet.shutdown(drain=True, timeout=30)
        (dec1, got1), (dec2, got2) = runs
        assert dec1 == dec2
        assert got1 == got2 == want

    def test_full_waiting_room_sheds_sideways_then_429s(self, model):
        from paddle_tpu.serving.server import QueueFullError
        fleet = _fleet(model, router="least-loaded", max_queue=1)
        fleet.submit(_req(1))           # r0 full (driver stopped)
        st2 = fleet.submit(_req(2))     # sheds to r1
        assert fleet.decisions[1][1] != fleet.decisions[0][1]
        with pytest.raises(QueueFullError):
            fleet.submit(_req(3))       # every replica full -> 429
        assert st2.gateway is fleet.replicas[
            fleet.decisions[1][1]].gateway
        fleet.start()
        fleet.shutdown(drain=True, timeout=30)


# ------------------------------------------------ compile-once / shared jit
class TestFleetCompileDiscipline:
    def test_same_geometry_shares_one_jit_cache(self, model):
        """The tentpole compile pin: same-geometry replicas share one
        jit dict — the whole fleet traces each program ONCE — and each
        engine still reports decode_compilations() == 1 after serving
        real traffic."""
        fleet = _fleet(model)
        e0 = fleet.replicas[0].gateway.engine
        e1 = fleet.replicas[1].gateway.engine
        assert e0._jit is e1._jit
        streams = [fleet.submit(_clone(r)) for r in _traffic()]
        fleet.start()
        for st in streams:
            st.result()
        assert e0.decode_compilations() == 2
        assert e1.decode_compilations() == 2
        fleet.shutdown(drain=True, timeout=30)

    def test_mixed_geometry_isolates_jit_caches(self, model):
        """Differing pool geometry (num_slots) must NOT pool traces
        under one fn: isolated dicts, each engine's pin intact."""
        fleet = _fleet(model, num_slots=[SLOTS, SLOTS + 1],
                       router="round-robin")
        e0 = fleet.replicas[0].gateway.engine
        e1 = fleet.replicas[1].gateway.engine
        assert e0._jit is not e1._jit
        streams = [fleet.submit(_clone(r)) for r in _traffic()]
        fleet.start()
        for st in streams:
            st.result()
        assert e0.decode_compilations() == 2
        assert e1.decode_compilations() == 2
        fleet.shutdown(drain=True, timeout=30)

    @pytest.mark.slow  # 7 s geometry duplicate: test_mixed_geometry_isolates_
    # jit_caches above is the default geometry rep (870s cap)
    def test_mixed_prefix_blocks_is_pool_geometry_too(self, model):
        """Review regression: prefix_blocks sizes the pool arrays the
        traced programs close over (num_blocks = live + trie budget),
        so replicas differing ONLY in prefix_blocks must isolate their
        jit dicts — sharing one would double both engines'
        decode_compilations()."""
        fleet = _fleet(model, prefix_blocks=[8, 16],
                       router="round-robin")
        e0 = fleet.replicas[0].gateway.engine
        e1 = fleet.replicas[1].gateway.engine
        assert e0._jit is not e1._jit
        streams = [fleet.submit(_clone(r)) for r in _traffic()]
        fleet.start()
        for st in streams:
            st.result()
        assert e0.decode_compilations() == 1
        assert e1.decode_compilations() == 1
        fleet.shutdown(drain=True, timeout=30)

    def test_heterogeneous_max_seq_len_routes_by_capacity(self, model):
        """Review regression: with per-replica max_seq_len, a request
        only one replica can hold must route there (not 400 off the
        small replica's validate), and failover must never adopt a
        sequence onto a replica too small for it (crash-loop
        cascade)."""
        big = _req(41, n=40, max_new_tokens=20)    # needs 60 rows
        small = _req(42, n=8, max_new_tokens=4)
        want = _baseline(model, [big, small])
        fleet = _fleet(model, max_seq_len=[S_MAX, 32],
                       router="least-loaded", prefill_chunk=CHUNK)
        st_big = fleet.submit(_clone(big))
        st_small = fleet.submit(_clone(small))
        assert st_big.gateway is fleet.replicas[0].gateway  # only fit
        fleet.start()
        outs = [st.result() for st in (st_big, st_small)]
        assert [ids.tolist() for ids, _ in outs] == want
        fleet.shutdown(drain=True, timeout=30)

    def test_failover_skips_too_small_sibling(self, model):
        """A dying replica's oversized request must terminate with an
        error (no sibling can hold it) while its holdable bystanders
        still fail over — never a crash loop on the sibling."""
        big = _req(43, n=40, max_new_tokens=20)    # 60 rows > 32
        ok = _req(44, n=8, max_new_tokens=4)       # fits anywhere
        fleet = _fleet(model, max_seq_len=[S_MAX, 32],
                       router="least-loaded", max_restarts=0,
                       fault_hooks=[FaultPlan().at_step(3, "fatal"),
                                    None])
        st_big = fleet.submit(_clone(big))
        st_ok = fleet.submit(_clone(ok))
        assert st_big.gateway is fleet.replicas[0].gateway
        fleet.start()
        with pytest.raises(RuntimeError):
            st_big.result()
        assert st_big.finish_reason == "error"
        ids, reason = st_ok.result()
        assert reason in ("length", "stop")
        # the sibling survived the failover untouched by the big one
        assert fleet.replicas[1].state in ("ok", "degraded")
        assert fleet.replicas[1].gateway.restarts == 0
        fleet.shutdown(drain=True, timeout=30)


# --------------------------------------------------- failover-to-sibling
class TestFailoverToSibling:
    def test_replica_kill_mid_decode_zero_lost_byte_identical(self, model):
        """THE acceptance pin: a replica whose supervision is
        exhausted mid-decode (fatal fault, no restart budget) loses
        ZERO requests — its live streams (greedy AND seeded-sampled,
        chunked long prompt included) fail over to the sibling and
        finish byte-identically to an unkilled single-engine run."""
        reqs = _traffic()
        want = _baseline(model, reqs)
        fleet = _fleet(model, max_restarts=0,
                       fault_hooks=[FaultPlan().at_step(3, "fatal"),
                                    None])
        streams = [fleet.submit(_clone(r)) for r in reqs]
        fleet.start()
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == want
        assert all(r in ("length", "stop") for _, r in outs)  # 0 lost
        assert fleet.replicas[0].state == "dead"
        assert fleet.replicas[1].state in ("ok", "degraded")
        assert fleet._m_failovers.value() == 1
        assert fleet._m_migrated.value(cause="failover") >= 1
        assert fleet.health_state == "degraded"   # reduced capacity
        fleet.shutdown(drain=True, timeout=30)

    def test_kill_replay_is_deterministic(self, model):
        """Chaos-matrix replay: the same kill plan over the same
        submission order reproduces the same routing decisions, the
        same fault log, and the same streams."""
        reqs = _traffic()
        runs = []
        for _ in range(2):
            plan = FaultPlan().at_step(3, "fatal")
            fleet = _fleet(model, max_restarts=0,
                           fault_hooks=[plan, None])
            streams = [fleet.submit(_clone(r)) for r in reqs]
            fleet.start()
            outs = [st.result() for st in streams]
            runs.append(([i for _, i in fleet.decisions], plan.log,
                         [ids.tolist() for ids, _ in outs]))
            fleet.shutdown(drain=True, timeout=30)
        assert runs[0] == runs[1]

    def test_intra_replica_recovery_never_escalates(self, model):
        """With restart budget available the replica recovers ITSELF
        (the PR-7 path): no failover, replica stays alive, streams
        byte-identical, decode_compilations() still 1 on the rebuilt
        engine."""
        reqs = _traffic()
        want = _baseline(model, reqs)
        fleet = _fleet(model, max_restarts=8,
                       fault_hooks=[FaultPlan().at_step(3, "fatal"),
                                    None])
        streams = [fleet.submit(_clone(r)) for r in reqs]
        fleet.start()
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == want
        rep0 = fleet.replicas[0]
        assert rep0.state != "dead"
        assert rep0.gateway.restarts == 1
        assert rep0.gateway.engine.decode_compilations() == 2
        assert fleet._m_failovers.value() == 0
        fleet.shutdown(drain=True, timeout=30)

    def test_last_replica_death_strands_with_errors_not_hangs(self, model):
        """Nobody to fail over to (single-replica fleet): the
        pre-fleet contract holds — every request terminates with an
        error event, never a hang."""
        fleet = _fleet(model, replicas=1, max_restarts=0,
                       fault_hooks=[FaultPlan().at_step(2, "fatal")])
        streams = [fleet.submit(_clone(r)) for r in _traffic()]
        fleet.start()
        for st in streams:
            with pytest.raises(RuntimeError):
                st.result()
        assert all(st.finish_reason == "error" for st in streams)
        assert fleet.health_state == "draining"


# ----------------------------------------------------- live migration
class TestLiveMigration:
    def test_migrate_mid_decode_byte_identical(self, model):
        req = _req(7, max_new_tokens=40)
        want = _baseline(model, [req])[0]
        fleet = _fleet(model, router="least-loaded", start=True)
        st = fleet.submit(_clone(req))
        _await(lambda: st.seq is not None and len(st.seq.tokens) >= 8)
        source = st.gateway
        fleet.migrate(st, target=1)
        ids, reason = st.result()
        assert ids.tolist() == want and reason == "length"
        assert st.gateway is fleet.replicas[1].gateway
        assert st.gateway is not source
        assert fleet._m_migrated.value(cause="migration") == 1
        # exact accounting on the source: slot freed, nothing leaked
        eng = fleet.replicas[0].gateway.engine
        _await(lambda: eng.cache.num_free == SLOTS)
        fleet.shutdown(drain=True, timeout=30)

    def test_drain_replica_migrates_and_cordons(self, model):
        reqs = [_req(i, max_new_tokens=30) for i in (11, 12, 13, 14)]
        want = _baseline(model, reqs)
        fleet = _fleet(model, router="round-robin", start=True)
        streams = [fleet.submit(_clone(r)) for r in reqs]
        _await(lambda: any(st.seq is not None and st.seq.tokens
                           for st in streams))
        moved = fleet.drain_replica(0)
        assert not fleet.replicas[0].accepting
        assert fleet.replicas[0].state == "draining"
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == want
        assert moved >= 1
        # drained replica took no NEW work; undrain restores routing
        st = fleet.submit(_req(15))
        assert st.gateway is fleet.replicas[1].gateway
        fleet.undrain_replica(0)
        assert fleet.replicas[0].routable
        st.result()
        fleet.shutdown(drain=True, timeout=30)

    def test_migration_refused_recovers_locally(self, model):
        """A migration with no routable target must not lose the
        request: the source restores it locally and the stream still
        finishes byte-identically."""
        req = _req(9, max_new_tokens=30)
        want = _baseline(model, [req])[0]
        fleet = _fleet(model, replicas=1, router="round-robin",
                       start=True)
        st = fleet.submit(_clone(req))
        _await(lambda: st.seq is not None and len(st.seq.tokens) >= 4)
        fleet.migrate(st)               # nowhere to go
        ids, reason = st.result()
        assert ids.tolist() == want and reason == "length"
        fleet.shutdown(drain=True, timeout=30)


# ------------------------------------------------------- fleet metrics
class TestFleetMetrics:
    def test_replica_labels_and_monotonic_across_rebuild(self, model):
        """ISSUE 12 satellite: one shared registry, every per-replica
        series replica-labeled, and a SINGLE replica's crash-recovery
        rebuild keeps its counters monotonic (per-replica carried
        (base, engine) snapshots) while the sibling's series never
        move."""
        reqs = _traffic()
        fleet = _fleet(model, max_restarts=8,
                       fault_hooks=[FaultPlan().at_step(3, "fatal"),
                                    None])
        streams = [fleet.submit(_clone(r)) for r in reqs]
        fleet.start()
        for st in streams:
            st.result()
        gw0 = fleet.replicas[0].gateway
        gw1 = fleet.replicas[1].gateway
        assert gw0.restarts == 1 and gw1.restarts == 0
        # the dead incarnation's tokens were banked into the base...
        assert gw0._stat_base["tokens_generated"] > 0
        text = fleet.registry.render()
        fams = parse_prometheus(text)   # strict: raises on bad format
        restarts = fams["serving_engine_restarts_total"]["samples"]
        assert restarts[("serving_engine_restarts_total",
                         (("replica", "0"),))] == 1
        assert restarts[("serving_engine_restarts_total",
                         (("replica", "1"),))] == 0
        # ...and the rendered per-replica carried series reads
        # base + live — the monotonic carry, now per (replica, base,
        # engine): the scraped value can never be less than the dead
        # incarnation's banked base
        chunks = fams["serving_prefill_chunks_total"]["samples"]
        assert chunks[("serving_prefill_chunks_total",
                       (("replica", "0"),))] == \
            gw0._stat("prefill_chunks") >= \
            gw0._stat_base["prefill_chunks"]
        assert fams["serving_requests_total"]["samples"][
            ("serving_requests_total", (("replica", "0"),))] + \
            fams["serving_requests_total"]["samples"][
            ("serving_requests_total", (("replica", "1"),))] == len(reqs)
        # fleet-level series
        assert fams["serving_fleet_replicas"]["samples"][
            ("serving_fleet_replicas", ())] == 2
        decided = fams["serving_fleet_router_decisions_total"]["samples"]
        assert sum(decided.values()) == len(reqs)
        fleet.shutdown(drain=True, timeout=30)

    def test_fleet_table_reads_like_the_scrape(self, model):
        fleet = _fleet(model, start=False)
        streams = [fleet.submit(_clone(r)) for r in _traffic()]
        fleet.start()
        for st in streams:
            st.result()
        rows = fleet.fleet_table()
        assert [r["replica"] for r in rows] == [0, 1]
        for rep, row in zip(fleet.replicas, rows):
            gw = rep.gateway
            assert row["state"] in ("ok", "degraded", "recovering")
            assert row["tokens_generated"] == gw._stat("tokens_generated")
            assert row["dispatches_per_decoded_token"] == round(
                gw.cost.totals["dispatches"]
                / max(gw._stat("tokens_generated"), 1), 4)
            assert row["restarts"] == 0
            assert row["last_rebuild_age_s"] is None
        assert sum(r["tokens_generated"] for r in rows) > 0
        fleet.shutdown(drain=True, timeout=30)


# ------------------------------------------------------------ HTTP surface
class TestFleetHTTP:
    @pytest.fixture()
    def server(self, model):
        from paddle_tpu.serving.server import serve_fleet
        srv = serve_fleet(model, replicas=2, port=0, num_slots=SLOTS,
                          max_seq_len=S_MAX, prefix_block_size=BS,
                          prefill_chunk=CHUNK, model_name="fleet-test")
        yield srv
        srv.shutdown(drain=False, timeout=30)

    def _get(self, srv, path):
        with urllib.request.urlopen(srv.url + path, timeout=30) as r:
            return r.status, json.load(r)

    def _post(self, srv, path, obj):
        req = urllib.request.Request(
            srv.url + path, data=json.dumps(obj).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, json.load(r)

    def test_routed_completion_and_debug_fleet(self, server):
        status, doc = self._post(server, "/v1/completions", {
            "prompt": [int(t) for t in _prompt(21)], "max_tokens": 6})
        assert status == 200
        assert doc["choices"][0]["finish_reason"] == "length"
        assert len(doc["choices"][0]["token_ids"]) == 6
        assert doc["id"].startswith("cmpl-r")     # fleet-unique ids
        status, doc = self._get(server, "/debug/fleet")
        assert status == 200
        assert [r["replica"] for r in doc["replicas"]] == [0, 1]
        assert doc["router"] == "affinity"
        for row in doc["replicas"]:
            assert {"state", "live_kv_blocks", "free_kv_blocks",
                    "queue_depth", "dispatches_per_decoded_token",
                    "last_rebuild_age_s", "restarts"} <= set(row)

    def test_healthz_metrics_and_requests_aggregate(self, server):
        status, doc = self._get(server, "/healthz")
        assert status == 200
        assert doc["status"] == "ok"
        assert doc["num_replicas"] == 2 and doc["routable_replicas"] == 2
        assert len(doc["replicas"]) == 2
        with urllib.request.urlopen(server.url + "/metrics",
                                    timeout=30) as r:
            text = r.read().decode()
        fams = parse_prometheus(text)
        assert ("serving_num_slots", (("replica", "0"),)) in \
            fams["serving_num_slots"]["samples"]
        assert ("serving_num_slots", (("replica", "1"),)) in \
            fams["serving_num_slots"]["samples"]
        assert "serving_fleet_replicas" in fams
        status, doc = self._get(server, "/debug/requests")
        assert status == 200 and doc["num_replicas"] == 2
        status, doc = self._get(server, "/debug/profile")
        assert status == 200 and set(doc["replicas"]) == {"0", "1"}
        status, doc = self._get(server, "/debug/trace")
        assert status == 200 and "traceEvents" in doc

    def test_drain_rebalance_endpoints(self, server):
        status, doc = self._post(server, "/fleet/drain", {"replica": 0})
        assert status == 200 and doc["state"] == "draining"
        status, doc = self._get(server, "/healthz")
        assert doc["status"] == "degraded"     # capacity reduced
        status, doc = self._post(server, "/fleet/drain",
                                 {"replica": 0, "undrain": True})
        assert status == 200 and doc["state"] == "accepting"
        status, doc = self._post(server, "/fleet/rebalance", {})
        assert status == 200 and "migrations_requested" in doc
        # bad replica index -> 400
        try:
            self._post(server, "/fleet/drain", {"replica": 9})
            assert False, "expected 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400


# --------------------------------------------------------------- CLI args
class TestFleetCLIArgs:
    def test_bad_num_slots_is_an_argparse_error(self):
        """Review regression: --num-slots grew comma-list parsing and
        must keep argparse error semantics — no tracebacks, no silent
        truncation of a list without --replicas."""
        from paddle_tpu.serving.server.__main__ import main
        for argv in (["--num-slots", "abc"],
                     ["--num-slots", ","],
                     ["--num-slots", "8,4"],                 # replicas=1
                     ["--replicas", "3", "--num-slots", "8,4"]):
            with pytest.raises(SystemExit) as ei:
                main(argv)
            assert ei.value.code == 2                        # usage error
