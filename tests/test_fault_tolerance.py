"""Fault-tolerant serving (ISSUE 8): supervised engine driver with
crash recovery, preemption-by-recompute, and the deterministic
fault-injection harness (serving/faults.py).

The acceptance matrix, per the robustness contract:

- under injected faults (step crash at arbitrary indices, repeated
  crash pinned to one request, pool exhaustion, hung step past the
  watchdog deadline) NO request ever hangs: every submitted request
  terminates with stop|length|cancelled|timeout|error;
- bystander greedy streams are BYTE-IDENTICAL to the fault-free run
  after recovery/preemption (and seeded-sampled streams too — the PRNG
  walk is snapshotted host-side);
- poisoned requests are the ONLY ones failed (finish_reason="error"),
  isolated by the gateway's bisection quarantine;
- ``decode_compilations() == 1`` survives an engine rebuild (the jit
  cache is shared through the factory — no recompile storm);
- slot/block accounting is exact after any crash/preemption/quarantine:
  ``cache.num_free`` restored, no block double-freed or leaked, and
  cancellation arriving DURING recovery is honored;
- ``PoolExhausted`` is typed (RuntimeError subclass), carries pool
  occupancy, and keeps the sizing hint;
- the new /metrics series strict-parse and ``/healthz`` exposes the
  watchdog externally.
"""
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.serving import (BlockManager, FINISH_REASONS, FatalFault,
                                FaultPlan, GenerationRequest, PagedKVCache,
                                PoolExhausted, VirtualClock)
from paddle_tpu.serving.server import ServingGateway, serve

import serving_support
from serving_support import (BS, CHUNK, S_MAX, SLOTS, clone as _clone,
                             drain as _drive, prompt as _prompt, wait_until)
from test_metrics_prom import parse_prometheus


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA, pallas decode


def _mk_factory(model, **kw):
    """An engine factory with the fixed test geometry — the SAME
    factory builds the first engine and every recovery rebuild, sharing
    one jit cache (the support module's, unless given), exactly like
    ``serve()`` wires it."""
    kw.setdefault("prefix_cache", True)

    def factory():
        return serving_support.engine(model, **kw)
    return factory


def _req(ps, n=12, **kw):
    kw.setdefault("max_new_tokens", 8)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


#: the standard mixed workload: greedy shorts, one seeded-sampled row,
#: one long prompt that chunks (60 > CHUNK)
def _traffic():
    return [_req(1), _req(2, n=10),
            _req(3, temperature=0.9, top_k=5, seed=123),
            _req(4, n=60, max_new_tokens=5)]


def _baseline(model, reqs, **kw):
    """Fault-free oracle streams for the same requests."""
    eng = _mk_factory(model, **kw)()
    return [o.tolist() for o in eng.generate([_clone(r) for r in reqs])]


class TestPoolExhausted:
    def test_typed_with_counts_and_sizing_hint(self):
        """The satellite pin: PoolExhausted subclasses RuntimeError
        (back-compat), carries live/pinned/free block counts, and the
        sizing hint survives in the message."""
        pool = BlockManager(1, 4, BS, 1, 4)
        cache = PagedKVCache(1, 2, 2 * BS, 1, 4, block_size=BS, pool=pool)
        for _ in range(4):
            pool.ref(pool.alloc())        # simulate pinned occupancy
        with pytest.raises(RuntimeError) as ei:
            cache._alloc_block()
        e = ei.value
        assert isinstance(e, PoolExhausted)
        assert (e.live_blocks, e.pinned_blocks, e.free_blocks) == (4, 4, 0)
        msg = str(e)
        assert "KV block pool exhausted" in msg
        assert "live=4, pinned=4, free=0" in msg
        # the sizing hint the old untyped raise carried is kept
        assert "num_slots * max_blocks + prefix budget" in msg

    def test_error_is_in_finish_vocabulary(self):
        assert "error" in FINISH_REASONS


class TestPreemptionByRecompute:
    def test_pool_fault_preempts_youngest_streams_identical(self, model):
        """Injected pool exhaustion mid-traffic: the engine preempts the
        youngest slot-holder (donating its chain to the trie), re-queues
        it, and every stream — victim included — is byte-identical to
        the fault-free run. Slot and block accounting land exact."""
        reqs = _traffic()
        want = _baseline(model, reqs)
        factory = _mk_factory(model)
        eng = factory()
        seqs = [eng.submit(_clone(r)) for r in reqs]
        FaultPlan().at_step(3, "pool").install(eng)
        _drive(eng)
        assert [s.tokens for s in seqs] == want
        assert eng.stats["preemptions"] == 1
        assert eng.stats["restores"] == 1
        assert all(s.finish_reason in ("length", "stop") for s in seqs)
        # exactly-once accounting: every slot back, pool blocks either
        # free or owned by the trie (refcounts fully released)
        assert eng.cache.num_free == SLOTS
        pool = eng.cache.pool
        assert pool.num_used == eng.prefix_cache.num_cached_blocks
        assert int((pool._ref > 0).sum()) == 0
        # the donated chain made the victim's recompute a trie hit
        assert eng.prefix_cache.stats["hits"] >= 1

    def test_preemption_without_trie_recomputes_cold(self, model):
        """No prefix cache: the preempted chain is freed outright and
        the recompute prefills from scratch — still byte-identical."""
        reqs = _traffic()
        want = _baseline(model, reqs, prefix_cache=False)
        factory = _mk_factory(model, prefix_cache=False)
        eng = factory()
        seqs = [eng.submit(_clone(r)) for r in reqs]
        FaultPlan().at_step(4, "pool").install(eng)
        _drive(eng)
        assert [s.tokens for s in seqs] == want
        assert eng.stats["preemptions"] == 1
        assert eng.cache.num_free == SLOTS
        assert eng.cache.pool.num_used == 0    # nothing leaked

    def test_unrepairable_exhaustion_reraises(self, model):
        """Exhaustion with NO preemptible slot-holder (nothing to
        displace) re-raises instead of spinning — typed, so a
        supervisor can still classify it fatal."""
        eng = _mk_factory(model)()
        eng.submit(_req(5))
        FaultPlan().at_step(0, "pool").install(eng)  # before any admit
        with pytest.raises(PoolExhausted):
            eng.step()
        # the popped-but-never-admitted request went back to the queue
        # intact: the next step admits and finishes it normally
        assert eng.scheduler.num_queued == 1
        _drive(eng)
        assert eng.cache.num_free == SLOTS


class TestEngineRestore:
    def test_restore_mid_stream_byte_identical(self, model):
        """The crash-recovery primitive: live sequences moved to a
        fresh engine mid-decode (prompt + generated tokens + PRNG
        snapshot) continue byte-identically — greedy AND seeded-sampled
        — with no token replayed and no retrace."""
        reqs = _traffic()
        want = _baseline(model, reqs)
        factory = _mk_factory(model)
        eng = factory()
        seqs = [eng.submit(_clone(r)) for r in reqs]
        emitted = {s.request_id: [] for s in seqs}
        eng.on_token = lambda s, t: emitted[s.request_id].append(t)
        for _ in range(4):
            eng.step()
        # the gateway's recovery snapshot, engine-level
        keys = np.asarray(eng._keys, np.uint32)
        live = sorted((s for s in eng._slots if s is not None
                       and not s.done), key=lambda s: s.request_id)
        for s in live:
            if s.tokens and s.status == "running":
                s.key = keys[s.slot].copy()
        queued = [s for s in eng.scheduler.queue]
        eng2 = factory()
        eng2.on_token = eng.on_token
        before = eng2.decode_compilations()
        for s in live + queued:
            assert eng2.restore(s)
        _drive(eng2)
        assert [s.tokens for s in seqs] == want
        # every token reached on_token exactly once across both engines
        assert [emitted[s.request_id] for s in seqs] == want
        assert eng2.decode_compilations() == before == 2

    def test_mid_admission_crash_unwinds_to_queue(self, model):
        """A NON-pool exception escaping mid-admission (a real runtime
        error, not an injected boundary raise) must not strand the
        popped-but-uninstalled sequences in limbo: they go back to the
        queue, where crash recovery's snapshot — or simply the next
        step — can see them."""
        reqs = _traffic()
        want = _baseline(model, reqs)
        eng = _mk_factory(model)()
        seqs = [eng.submit(_clone(r)) for r in reqs]
        orig = eng._admit_cold
        state = {"armed": True}

        def boom(group, finished):
            if state["armed"]:
                state["armed"] = False
                raise FatalFault("device error mid-admission")
            return orig(group, finished)

        eng._admit_cold = boom
        with pytest.raises(FatalFault):
            eng.step()
        # every popped sequence is back in the queue IN ARRIVAL ORDER
        # (the admitted batch was suffix-sorted; the unwind must restore
        # FIFO), nothing holds a slot or a pin, and the run then
        # completes byte-identically
        assert [q.request_id for q in eng.scheduler.queue] == \
            [s.request_id for s in seqs]
        assert eng.cache.num_free == SLOTS
        _drive(eng)
        assert [s.tokens for s in seqs] == want

    def test_restored_long_content_chunks_cold(self, model):
        """Without a trie to hit, a restored sequence whose
        prompt + generated content exceeds the chunk budget re-enters
        through CHUNKED prefill (recompute never monopolizes a step)."""
        factory = _mk_factory(model, prefix_cache=False)
        eng = factory()
        seq = eng.submit(_req(6, n=40, max_new_tokens=30))
        want = _baseline(model, [_req(6, n=40, max_new_tokens=30)],
                         prefix_cache=False)[0]
        while len(seq.tokens) < 10:
            eng.step()
        eng._preempt(seq)                 # 40 + 9 = 49 rows > CHUNK
        assert seq.status == "queued" and seq.work_len == 49
        chunks0 = eng.stats["prefill_chunks"]
        _drive(eng)
        assert seq.tokens == want
        assert eng.stats["prefill_chunks"] > chunks0

    @pytest.mark.parametrize("grants", [
        # cuts of the 49-row recompute that keep the stream ...
        [16, 16, 16, 1], [7, 16, 10, 16], [13, 3, 16, 16, 1], [1] * 49,
        # ... and the two of 40 seeded random cuts that do not (ROADMAP
        # D11: on PR 32's tree and on its parent's alike, so it is not the
        # ragged kernel's accumulation order): token 10, the first after
        # the recompute, comes out 178 and 113 where the uninterrupted
        # stream has 89
        pytest.param([10, 12, 6, 16, 1, 1, 2, 13],
                     marks=pytest.mark.xfail(strict=False, reason="D11")),
        pytest.param([2, 13, 16, 1, 10, 9],
                     marks=pytest.mark.xfail(strict=False, reason="D11")),
    ], ids=lambda g: "-".join(map(str, g)) if len(g) < 10 else "by_token")
    def test_restored_stream_whatever_the_grant_cuts(self, model, grants):
        """The chunk grant follows the wall clock (``_prefill_budget``);
        here it is dealt from a list, so that a cut of the recompute that
        changes the stream is a case with a name and not a flake of
        ``test_restored_long_content_chunks_cold`` on a loaded machine."""
        eng = _mk_factory(model, prefix_cache=False)()
        seq = eng.submit(_req(6, n=40, max_new_tokens=30))
        want = _baseline(model, [_req(6, n=40, max_new_tokens=30)],
                         prefix_cache=False)[0]
        while len(seq.tokens) < 10:
            eng.step()
        deal = iter(grants)
        eng._prefill_budget = lambda: next(deal, CHUNK)
        eng._preempt(seq)
        _drive(eng)
        assert seq.tokens == want

    def test_restored_with_trie_recomputes_by_reference(self, model):
        """With the trie on, the preempted chain was donated, so the
        recompute prefill covers almost everything by ZERO-COPY
        reference — recovery is nearly free (the ROADMAP's
        "preempt-by-donation is cheap" claim, pinned)."""
        factory = _mk_factory(model)
        eng = factory()
        seq = eng.submit(_req(6, n=40, max_new_tokens=30))
        while len(seq.tokens) < 10:
            eng.step()
        saved0 = eng.stats["prefill_tokens_saved"]
        eng._preempt(seq)
        _drive(eng)
        # 49 work rows, 48 coverable by donated blocks (6 full blocks)
        assert eng.stats["prefill_tokens_saved"] - saved0 >= 40


def _gateway(model, plan, jit_cache=None, **kw):
    """A supervised gateway wired exactly like serve() does it — one
    factory for the first engine and every rebuild — but NOT started,
    so tests submit their whole workload first and the fault plan's
    step indices are deterministic relative to the traffic."""
    factory = _mk_factory(model, jit_cache=jit_cache)
    kw.setdefault("max_queue", 16)
    return ServingGateway(factory(), engine_factory=factory,
                          fault_hook=plan, start=False, **kw)


class TestSupervisedDriver:
    def test_transient_fault_retries_same_engine(self, model):
        reqs = _traffic()
        want = _baseline(model, reqs)
        plan = FaultPlan().at_step(2, "transient")
        gw = _gateway(model, plan)
        streams = [gw.submit(_clone(r)) for r in reqs]
        gw.start()
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == want
        assert gw.restarts == 0           # retried, never rebuilt
        assert plan.log == [(2, "transient")]
        fams = parse_prometheus(gw.registry.render())
        assert fams["serving_faults_total"]["samples"][
            ("serving_faults_total", (("kind", "transient"),))] == 1
        gw.shutdown(drain=True, timeout=30)
        assert gw.health_state == "draining"

    def test_transient_streak_escalates_to_rebuild(self, model):
        plan = FaultPlan()
        for i in range(6):                # > max_transient_retries=3
            plan.at_step(2 + i, "transient")
        gw = _gateway(model, plan, max_transient_retries=3,
                      retry_backoff_s=0.0)
        streams = [gw.submit(_clone(r)) for r in _traffic()]
        gw.start()
        for st in streams:
            st.result()
        assert gw.restarts >= 1
        assert all(st.finish_reason in ("length", "stop")
                   for st in streams)
        gw.shutdown(drain=True, timeout=30)

    def test_fatal_crash_recovers_streams_byte_identical(self, model):
        """The tentpole pin: a fatal step fault rebuilds the engine and
        every in-flight request — greedy and seeded-sampled — continues
        byte-identically, with decode_compilations() still 1 on the
        rebuilt engine (shared jit cache: no recompile storm)."""
        reqs = _traffic()
        want = _baseline(model, reqs)
        plan = FaultPlan().at_step(3, "fatal")
        gw = _gateway(model, plan)
        streams = [gw.submit(_clone(r)) for r in reqs]
        gw.start()
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == want
        assert [r for _, r in outs] == ["length"] * 3 + ["length"]
        assert gw.restarts == 1
        assert gw.engine.decode_compilations() == 2   # the whole point
        assert len(gw.restart_latencies) == 1
        assert gw.restart_latencies[0] >= 0.0
        gw.shutdown(drain=True, timeout=30)

    def test_nan_corruption_recovery_recomputes(self, model):
        """The nan fault REALLY poisons the KV pool before crashing;
        byte-identical bystanders prove recovery recomputed from host
        token state instead of reusing corrupt device state."""
        reqs = _traffic()
        want = _baseline(model, reqs)
        plan = FaultPlan().at_step(4, "nan")
        gw = _gateway(model, plan)
        streams = [gw.submit(_clone(r)) for r in reqs]
        gw.start()
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == want
        assert gw.restarts == 1
        gw.shutdown(drain=True, timeout=30)

    def test_hung_step_watchdog_rebuilds(self, model):
        """A step that overran the (virtual) watchdog deadline is
        classified hung and recovered like a fatal fault — with the
        injected clock the whole scenario takes no real time."""
        reqs = _traffic()
        want = _baseline(model, reqs)
        clk = VirtualClock()
        plan = FaultPlan(clock=clk).at_step(3, "hung", stall_s=99.0)
        gw = _gateway(model, plan, watchdog_deadline_s=5.0, clock=clk)
        streams = [gw.submit(_clone(r)) for r in reqs]
        gw.start()
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == want
        assert gw.restarts == 1
        fams = parse_prometheus(gw.registry.render())
        assert fams["serving_faults_total"]["samples"][
            ("serving_faults_total", (("kind", "hung"),))] == 1
        gw.shutdown(drain=True, timeout=30)

    def test_watchdog_exempts_compiling_steps(self, model):
        """A step that traced a new program is exempt from the watchdog
        (compile time is not a hang — on a real chip a cold start
        routinely exceeds the deadline and must not burn the restart
        budget); the same stall on a WARM step still classifies hung."""
        clk = VirtualClock()
        plan = (FaultPlan(clock=clk).at_step(0, "hung", stall_s=99.0)
                .at_step(5, "hung", stall_s=99.0))
        # programs of its own: a COLD step is what is exempted. On the jnp
        # attention path, the cheapest step program there is to lower
        cold = serving_support.model("llama", seed=33, decode_attention="jnp")
        gw = _gateway(cold, plan, jit_cache={}, watchdog_deadline_s=5.0,
                      clock=clk)
        streams = [gw.submit(_clone(r)) for r in _traffic()]
        gw.start()
        for st in streams:
            st.result()
        assert all(st.finish_reason == "length" for st in streams)
        # step 0 stalled but compiled (fresh jit cache) -> exempt;
        # step 5 stalled warm -> one rebuild, not two
        assert gw.restarts == 1
        gw.shutdown(drain=True, timeout=30)

    def test_no_factory_strands_with_errors_not_hangs(self, model):
        """Without an engine_factory a fatal fault still terminates
        every request (finish_reason via the error event) — the one
        thing that may never happen is a hang."""
        plan = FaultPlan().at_step(2, "fatal")
        factory = _mk_factory(model)
        gw = ServingGateway(factory(), fault_hook=plan, start=False)
        streams = [gw.submit(_clone(r)) for r in _traffic()]
        gw.start()
        for st in streams:
            with pytest.raises(RuntimeError, match="engine driver died"):
                st.result()
        assert all(st.finish_reason == "error" for st in streams)

    def test_restart_budget_exhaustion_strands_with_errors(self, model):
        """An unfixable fault burns the restart budget, then every
        remaining request errors out — bounded, never a crash loop."""
        plan = FaultPlan().poison(lambda s: True, kind="fatal")
        gw = _gateway(model, plan, max_restarts=2, retry_backoff_s=0.0)
        streams = [gw.submit(_clone(r)) for r in _traffic()]
        gw.start()
        for st in streams:
            try:
                st.result()
            except RuntimeError:
                pass
        assert gw.restarts == 2
        assert all(st.finish_reason is not None for st in streams)


class TestPoisonQuarantine:
    def test_bisection_fails_only_the_culprit(self, model):
        """Repeated crash pinned to ONE request: the bisection
        quarantine isolates it, fails it with finish_reason="error",
        and every bystander completes byte-identically."""
        bystanders = [_req(i, n=8 + i) for i in range(4)]      # 8..11
        want = _baseline(model, bystanders)
        poison = _req(50, n=13, max_new_tokens=40)             # unique len
        plan = FaultPlan().poison(lambda s: s.prompt_len == 13)
        gw = _gateway(model, plan, max_restarts=16,
                      retry_backoff_s=0.0)
        streams = [gw.submit(_clone(r)) for r in bystanders]
        bad = gw.submit(_clone(poison))
        gw.start()
        outs = [st.result() for st in streams]
        with pytest.raises(RuntimeError, match="poisoned request"):
            bad.result()
        assert bad.finish_reason == "error"
        assert [ids.tolist() for ids, _ in outs] == want
        assert all(r == "length" for _, r in outs)
        assert gw.restarts >= 2           # fault recurred, then isolated
        # quarantine drained: nothing parked, nothing suspect
        assert not gw._parked and gw._suspect_ids is None
        wait_until(lambda: gw.health_state == "ok")
        gw.shutdown(drain=True, timeout=30)

    def test_cancel_during_recovery_is_honored(self, model):
        """A cancellation arriving while the gateway is mid-quarantine
        (engine rebuilt at least once, victim still crashing) takes
        effect: the cancelled bystander terminates "cancelled" and its
        slot accounting is exact."""
        plan = FaultPlan().poison(lambda s: s.prompt_len == 13)
        gw = _gateway(model, plan, max_restarts=16,
                      retry_backoff_s=0.0)
        victim = gw.submit(_req(60, n=8, max_new_tokens=60))
        bad = gw.submit(_req(61, n=13, max_new_tokens=60))
        gw.start()
        wait_until(lambda: gw.restarts >= 1)
        victim.cancel()
        ids, reason = victim.result()
        assert reason in ("cancelled", "length")
        assert victim.finish_reason == reason
        try:
            bad.result()
        except RuntimeError:
            pass
        wait_until(lambda: gw.engine.cache.num_free == SLOTS)
        gw.shutdown(drain=True, timeout=30)


    def test_parked_deadline_still_expires(self, model):
        """A request benched outside the engine by the bisection is
        beyond the engine's deadline sweep — the gateway's own parked
        sweep must still honor its timeout_s."""
        gw = _gateway(model, None)
        st = gw.submit(_req(80, max_new_tokens=60, timeout_s=0.05))
        gw._admit_intake()            # driver-side submit (thread idle)
        seq = st.seq
        assert gw.engine.scheduler.remove(seq)   # simulate parking
        seq.status = "queued"
        gw._parked.append(seq)
        time.sleep(0.06)
        gw.start()
        ids, reason = st.result()
        assert reason == "timeout" and len(ids) == 0
        gw.shutdown(drain=True, timeout=30)


class TestHealthAndMetrics:
    def test_new_metric_series_strict_parse(self, model):
        """The satellite pin: serving_faults_total{kind},
        serving_engine_restarts_total, serving_preemptions_total,
        serving_recovered_requests_total and the watchdog age gauge all
        render valid Prometheus text with the expected values."""
        clk = VirtualClock()
        plan = (FaultPlan(clock=clk)
                .at_step(2, "transient").at_step(4, "pool")
                .at_step(7, "fatal").at_step(11, "hung", stall_s=99.0))
        gw = _gateway(model, plan, watchdog_deadline_s=5.0,
                      clock=clk)
        streams = [gw.submit(_clone(r)) for r in _traffic()]
        gw.start()
        for st in streams:
            st.result()
        text = gw.registry.render()
        fams = parse_prometheus(text)     # strict: raises on bad format
        faults = fams["serving_faults_total"]
        assert faults["type"] == "counter"
        got = {lab[0][1]: v for (_, lab), v in faults["samples"].items()}
        assert got == {"transient": 1, "fatal": 1, "hung": 1}
        assert fams["serving_engine_restarts_total"]["samples"][
            ("serving_engine_restarts_total", ())] == 2
        assert fams["serving_preemptions_total"]["samples"][
            ("serving_preemptions_total", ())] == 1
        assert fams["serving_recovered_requests_total"]["samples"][
            ("serving_recovered_requests_total", ())] >= 2
        age = fams["serving_watchdog_last_step_age_seconds"]
        assert age["type"] == "gauge"
        # preemptions stay monotonic across the rebuild (base carried)
        assert gw._stat_base["preemptions"] == 1
        gw.shutdown(drain=True, timeout=30)

    def test_healthz_reports_watchdog_and_restarts(self, model):
        """/healthz carries the supervisor's external surface: status,
        seconds-since-last-completed-step, restart count; the SSE and
        blocking error paths return proper terminal responses."""
        plan = FaultPlan().poison(lambda s: s.prompt_len == 13)
        srv = serve(model, port=0, num_slots=SLOTS, max_seq_len=S_MAX,
                    prefix_block_size=BS, prefill_chunk=CHUNK,
                    max_restarts=16, model_name="chaos-test",
                    fault_hook=plan)
        try:
            body = json.dumps({"prompt": _prompt(70, 13).tolist(),
                               "max_tokens": 40}).encode()
            req = urllib.request.Request(
                srv.url + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    assert False, f"expected 500, got {r.status}"
            except urllib.error.HTTPError as e:
                assert e.code == 500
                doc = json.load(e)
                assert doc["choices"][0]["finish_reason"] == "error"
                assert doc["error"]["type"] == "server_error"
            with urllib.request.urlopen(srv.url + "/healthz",
                                        timeout=10) as r:
                doc = json.load(r)
            assert doc["status"] in ("ok", "degraded", "recovering")
            assert doc["engine_restarts"] >= 1
            assert isinstance(doc["last_step_age_s"], float)
        finally:
            srv.shutdown(drain=False, timeout=30)
