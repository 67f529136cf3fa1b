"""Request-lifecycle tracing, step timeline, and SLO instrumentation
(profiler/tracing.py + its threading through the serving stack; README
"Tracing & debugging").

The properties under test, per the observability contract:

- the tracer itself: bounded ring, injectable clock, off-by-default
  no-op path, dense request-lane normalization;
- every emitted event is valid Chrome trace JSON (``ph/ts/pid/tid/
  name``) and same-lane spans nest properly;
- the engine emits the full request lifecycle (``queued → prefill /
  prefill_chunk[i] → decode → finished``) and step phases (``plan /
  launch / host-accept / donate``), with tracing NEVER changing a
  token;
- the SLO substrate: ``Sequence`` carries engine-clock TTFT/TPOT/
  queue-wait stamps, and ``serving_tpot_seconds`` /
  ``serving_queue_wait_seconds`` strict-parse on ``/metrics`` and keep
  accumulating across an engine rebuild;
- a mixed chaos+spec trace under ``VirtualClock`` is byte-stable
  across replays and contains the fault/rebuild/recovery/preemption/
  spec-acceptance events, with streams byte-identical to the
  fault-free baseline and ``decode_compilations() == 1``;
- the ``/debug/trace`` and ``/debug/requests`` endpoints work over
  live HTTP, and ``/healthz`` reports the saturation fields;
- the ``python -m paddle_tpu.profiler`` CLI summarizes a real trace
  directory.
"""
import contextlib
import io
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.profiler.tracing import (NULL_SPAN, TID_ENGINE,
                                         TID_GATEWAY, TID_REQ0, SpanTracer)
from paddle_tpu.serving import FaultPlan, GenerationRequest, VirtualClock
from paddle_tpu.serving.server import (ServingGateway, TraceBusyError,
                                       serve)

import serving_support
from test_metrics_prom import parse_prometheus

NUM_SLOTS, S_MAX = 2, 256


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=31)


def _reqs(n=3, max_new=5, plen=8, seed0=100):
    rng = np.random.RandomState(7)
    out = []
    for i in range(n):
        kw = {}
        if i % 3 == 2:     # every third request seeded-sampled
            kw = dict(temperature=0.8, top_k=5, seed=seed0 + i)
        out.append(GenerationRequest(
            prompt=rng.randint(0, 256, (plen,)).astype(np.int32),
            max_new_tokens=max_new, **kw))
    return out


def _engine(model, tracer=None, **kw):
    """The shared helper with a tracer hung on, at this file's geometry:
    256 positions and the ENGINE's own chunk and block, which is what the
    ``server`` fixture's ``serve()`` builds and the spans below describe."""
    kw.setdefault("num_slots", NUM_SLOTS)
    kw.setdefault("max_seq_len", S_MAX)
    kw.setdefault("decode_chunk", 1)
    eng = serving_support.engine_as_given(model, **kw)
    eng.tracer = tracer
    return eng


def validate_chrome_trace(doc, require_events=True):
    """The schema pin: every event carries ph/ts/pid/tid/name, spans
    are X events with non-negative durations, and same-lane spans nest
    (no partial overlap)."""
    assert "traceEvents" in doc
    evs = doc["traceEvents"]
    if require_events:
        assert evs, "empty trace"
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e), e
        assert e["ph"] in ("X", "i", "C"), e
        if e["ph"] == "C":      # counter samples carry numeric series
            assert e["args"] and all(
                isinstance(v, (int, float)) for v in e["args"].values())
        assert e["ts"] >= 0
        assert isinstance(e["tid"], int) and isinstance(e["pid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    for tid in {e["tid"] for e in evs}:
        spans = sorted((e for e in evs
                        if e["tid"] == tid and e["ph"] == "X"),
                       key=lambda e: (e["ts"], -e["dur"]))
        stack = []          # open spans' end timestamps
        for e in spans:
            while stack and e["ts"] >= stack[-1] - 1e-9:
                stack.pop()
            if stack:       # strictly inside the enclosing span
                assert e["ts"] + e["dur"] <= stack[-1] + 1e-6, \
                    f"span {e} overlaps its enclosing span on tid {tid}"
            stack.append(e["ts"] + e["dur"])
    return evs


# ---------------------------------------------------------------- unit
class TestSpanTracerUnit:
    def test_disabled_is_noop(self):
        clk = VirtualClock(5.0)
        tr = SpanTracer(capacity=16, clock=clk)
        assert not tr.enabled
        tr.instant("x")
        tr.complete("y", 5.0)
        assert tr.span("z") is NULL_SPAN
        with tr.span("z"):
            pass
        assert tr.events() == []

    def test_ring_buffer_bounds_and_drop_count(self):
        tr = SpanTracer(capacity=4, clock=VirtualClock()).enable()
        for i in range(10):
            tr.instant(f"e{i}")
        evs = tr.events()
        assert len(evs) == 4 and tr.dropped == 6
        assert [e["name"] for e in evs] == ["e6", "e7", "e8", "e9"]

    def test_injectable_clock_and_epoch_relative_ts(self):
        clk = VirtualClock(100.0)
        tr = SpanTracer(clock=clk).enable()      # epoch = 100.0
        clk.advance(0.5)
        tr.instant("a")
        t0 = tr.now()
        clk.advance(0.25)
        tr.complete("b", t0)
        a, b = tr.events()
        assert a["ts"] == pytest.approx(500000.0)
        assert b["ts"] == pytest.approx(500000.0)
        assert b["dur"] == pytest.approx(250000.0)

    def test_spans_closed_at_one_reading_end_at_one_timestamp(self):
        """``retire`` closes at the reading that closes its ``step``: the
        end is rounded as a start is, so the child never sticks out of its
        parent by the rounding of two durations."""
        tr = SpanTracer(clock=VirtualClock()).enable()
        outer = tr.span("step", t0=1.4994e-6)
        inner = tr.span("retire", t0=2.4996e-6)
        inner.end(t1=10.0004e-6)
        outer.end(t1=10.0004e-6)
        retire, step = tr.events()
        assert (step["ts"], retire["ts"]) == (1.499, 2.5)
        assert (step["dur"], retire["dur"]) == (8.501, 7.5)
        validate_chrome_trace(tr.export())

    def test_req_tid_dense_first_seen(self):
        tr = SpanTracer(clock=VirtualClock()).enable()
        assert tr.req_tid(42) == TID_REQ0
        assert tr.req_tid(7) == TID_REQ0 + 1
        assert tr.req_tid(42) == TID_REQ0
        tr.clear()
        assert tr.req_tid(7) == TID_REQ0      # re-normalized

    def test_req_tid_map_bounded_by_capacity(self):
        # persistent tracing must not grow host memory with total
        # requests served: the id->tid map prunes to the ring capacity
        # (tids stay dense and are never reused)
        tr = SpanTracer(capacity=4, clock=VirtualClock()).enable()
        tids = [tr.req_tid(i) for i in range(10)]
        assert tids == list(range(TID_REQ0, TID_REQ0 + 10))
        assert len(tr._req_tids) <= 4
        assert tr.req_tid(9) == TID_REQ0 + 9    # recent ids stable

    def test_clear_resets_epoch_and_pre_window_marks_clamp(self):
        clk = VirtualClock()
        tr = SpanTracer(clock=clk).enable()
        stale = tr.now()                      # mark before the window
        clk.advance(2.0)
        tr.clear()                            # epoch = 2.0
        tr.complete("x", stale)               # t0 predates the epoch
        tr.complete("y", None)                # None = since epoch
        x, y = tr.events()
        assert x["ts"] == 0.0                 # clamped, not negative
        assert y["ts"] == 0.0
        with pytest.raises(ValueError):
            SpanTracer(capacity=0)

    def test_export_is_json_and_span_cm(self):
        tr = SpanTracer(clock=VirtualClock()).enable()
        with tr.span("outer", args={"k": 1}):
            tr.instant("inner", args={"j": 2})
        doc = json.loads(json.dumps(tr.export()))
        evs = validate_chrome_trace(doc)
        assert [e["name"] for e in evs] == ["inner", "outer"]
        assert evs[1]["args"] == {"k": 1}


# -------------------------------------------------------- engine spans
class TestEngineTracing:
    @pytest.mark.slow  # 6 s schema duplicate: the chunk-span and midflight-capture
    # reps below run by default (870s cap)
    def test_lifecycle_and_step_phases_schema(self, model):
        tracer = SpanTracer().enable()
        eng = _engine(model, tracer=tracer, prefix_cache=True,
                      prefix_block_size=8)
        outs = eng.generate(_reqs(3, max_new=5))
        assert all(o.finish_reason == "length" for o in outs)
        doc = tracer.export()
        evs = validate_chrome_trace(doc)
        names = {e["name"] for e in evs}
        assert {"queued", "prefill", "decode", "finished", "step",
                "sweep", "plan", "launch", "call", "host-accept", "retire",
                "admit", "prefill_launch", "donate"} <= names
        # one lifecycle lane per request, each with exactly one
        # queued span, one decode span and one finished instant
        lanes = {e["tid"] for e in evs if e["tid"] >= TID_REQ0}
        assert len(lanes) == 3
        for lane in lanes:
            mine = [e for e in evs if e["tid"] == lane]
            assert [e["name"] for e in mine if e["name"] == "queued"] \
                == ["queued"]
            dec = [e for e in mine if e["name"] == "decode"]
            assert len(dec) == 1
            assert dec[0]["args"]["finish_reason"] == "length"
            assert dec[0]["args"]["tokens"] == 5
            fin = [e for e in mine if e["name"] == "finished"]
            assert len(fin) == 1 and fin[0]["ph"] == "i"
        # engine-lane step spans: one per engine step
        steps = [e for e in evs
                 if e["name"] == "step" and e["tid"] == TID_ENGINE]
        assert len(steps) == eng.stats["steps"]

    def test_a_step_starts_in_sweep_and_ends_in_retire(self, model):
        """Every step's first child is ``sweep`` (args: the queue's length
        and what it admitted) and its last ``retire``, which ends with it;
        ``call`` is the jitted call inside ``dispatch``. An engine with no
        gateway has no driver clock: the spans are the tracer's alone."""
        tracer = SpanTracer(clock=time.perf_counter).enable()
        eng = _engine(model, tracer=tracer)
        assert eng.driver_clock is None
        eng.generate(_reqs(3, max_new=4))
        evs = [e for e in validate_chrome_trace(tracer.export())
               if e["ph"] == "X" and e["tid"] == TID_ENGINE]
        steps = [e for e in evs if e["name"] == "step"]
        assert len(steps) == eng.stats["steps"]
        for s in steps:
            lo, hi = s["ts"], s["ts"] + s["dur"]
            kids = sorted((e for e in evs if e is not s and lo <= e["ts"]
                           and e["ts"] + e["dur"] <= hi + 1e-6),
                          key=lambda e: (e["ts"], -e["dur"]))
            assert kids[0]["name"] == "sweep"
            assert set(kids[0]["args"]) == {"queued", "admitted"}
            last = max(kids, key=lambda e: (e["ts"] + e["dur"], e["ts"]))
            assert last["name"] == "retire"
            assert last["ts"] + last["dur"] == pytest.approx(hi, abs=1e-6)
        sweeps = [e["args"] for e in evs if e["name"] == "sweep"]
        assert sweeps[0] == {"queued": 3, "admitted": NUM_SLOTS}
        assert sum(a["admitted"] for a in sweeps) == 3
        dispatches = [e for e in evs if e["name"] == "dispatch"]
        calls = [e for e in evs if e["name"] == "call"]
        assert len(calls) == len(dispatches) > 0
        for d, c in zip(dispatches, calls):
            assert d["ts"] <= c["ts"] and \
                c["ts"] + c["dur"] <= d["ts"] + d["dur"] + 1e-6

    def test_chunked_prefill_chunk_spans(self, model):
        tracer = SpanTracer().enable()
        eng = _engine(model, tracer=tracer, prefill_chunk=32,
                      prefix_block_size=8)
        long_req = GenerationRequest(
            prompt=np.arange(1, 81, dtype=np.int32), max_new_tokens=3)
        out = eng.generate([long_req])[0]
        assert out.finish_reason == "length"
        evs = validate_chrome_trace(tracer.export())
        chunks = sorted((e for e in evs
                         if e["name"].startswith("prefill_chunk[")),
                        key=lambda e: e["args"]["offset"])
        # 80 tokens through a 32-token chunk: 32 + 32 + 16
        assert [e["name"] for e in chunks] == [
            "prefill_chunk[0]", "prefill_chunk[1]", "prefill_chunk[2]"]
        assert [e["args"]["tokens"] for e in chunks] == [32, 32, 16]
        assert [e["args"]["offset"] for e in chunks] == [0, 32, 64]
        assert all(e["args"]["offset"] % 8 == 0 for e in chunks)

    def test_midflight_capture_names_phases_correctly(self, model):
        # a capture window opened AFTER a request was admitted must
        # close its spans under the right phase name: the phase tracks
        # state even while tracing is off
        tr = SpanTracer()
        eng = _engine(model, tracer=tr)
        seq = eng.submit(GenerationRequest(prompt=[1, 2, 3, 4],
                                           max_new_tokens=6))
        eng.step()                      # admitted + decoding, tracer off
        assert seq.status == "running"
        tr.enable()                     # mid-flight capture
        while eng.has_work():
            eng.step()
        lane = [e for e in tr.events() if e["tid"] >= TID_REQ0]
        names = [e["name"] for e in lane]
        assert "decode" in names
        assert "queued" not in names    # it was NOT queued this window
        dec = next(e for e in lane if e["name"] == "decode")
        assert dec["ts"] == 0.0         # since capture epoch

    def test_tracing_never_changes_tokens_and_off_is_silent(self, model):
        reqs = _reqs(3, max_new=6)
        base = [o.tolist() for o in
                _engine(model).generate(reqs)]
        # attached-but-disabled: no events, identical streams
        tr_off = SpanTracer()
        eng_off = _engine(model, tracer=tr_off)
        assert [o.tolist() for o in eng_off.generate(reqs)] == base
        assert tr_off.events() == []
        # recording: identical streams, compile-once intact
        tr_on = SpanTracer().enable()
        eng_on = _engine(model, tracer=tr_on)
        assert [o.tolist() for o in eng_on.generate(reqs)] == base
        assert tr_on.events()
        assert eng_on.decode_compilations() == 1


# ------------------------------------------------------- SLO substrate
class TestSLOSubstrate:
    def test_sequence_latency_stamps(self, model):
        eng = _engine(model)
        seqs = [eng.submit(r) for r in _reqs(2, max_new=4)]
        while eng.has_work():
            eng.step()
        for seq in seqs:
            assert seq.t_submit is not None
            assert seq.t_admitted >= seq.t_submit
            assert seq.t_first_token >= seq.t_admitted
            assert seq.t_finish >= seq.t_first_token
            assert seq.queue_wait_s >= 0
            assert seq.ttft_s > 0
            assert seq.tpot_s > 0       # 4 tokens -> 3 gaps
        # a one-token request has no inter-token gap
        one = eng.submit(GenerationRequest(prompt=[1, 2, 3],
                                           max_new_tokens=1))
        while eng.has_work():
            eng.step()
        assert one.tpot_s is None and one.ttft_s is not None

    def test_slo_histograms_strict_parse(self, model):
        gw = ServingGateway(_engine(model), start=False)
        streams = [gw.submit(r) for r in _reqs(4, max_new=4)]
        gw.start()
        for s in streams:
            s.result()
        text = gw.registry.render()
        gw.shutdown(drain=True, timeout=30)
        fams = parse_prometheus(text)   # strict: raises on format errors
        for name in ("serving_tpot_seconds", "serving_queue_wait_seconds"):
            assert fams[name]["type"] == "histogram"
            assert fams[name]["samples"][(f"{name}_count", ())] == 4.0
            assert fams[name]["samples"][(f"{name}_sum", ())] >= 0.0
        # TPOT is a per-token cadence: sum/count must sit well under
        # the whole-request latency average
        lat = fams["serving_request_latency_seconds"]["samples"]
        tp = fams["serving_tpot_seconds"]["samples"]
        assert (tp[("serving_tpot_seconds_sum", ())]
                <= lat[("serving_request_latency_seconds_sum", ())])

    @pytest.mark.slow  # 5 s rebuild duplicate: test_slo_histograms_strict_parse
    # above is the default SLO-histogram rep (870s cap)
    def test_slo_histograms_accumulate_across_rebuild(self, model):

        def factory():
            return _engine(model)

        plan = FaultPlan().at_step(2, "fatal")
        gw = ServingGateway(factory(), engine_factory=factory,
                            fault_hook=plan, retry_backoff_s=0.0,
                            start=False)
        streams = [gw.submit(r) for r in _reqs(3, max_new=5)]
        gw.start()
        for s in streams:
            ids, reason = s.result()
            assert reason == "length"
        assert gw.restarts >= 1
        fams = parse_prometheus(gw.registry.render())
        gw.shutdown(drain=True, timeout=30)
        # gateway-owned, Sequence-stamp-backed: every request lands in
        # the histograms exactly once even though the engine (and its
        # stats) was rebuilt mid-flight
        assert fams["serving_tpot_seconds"]["samples"][
            ("serving_tpot_seconds_count", ())] == 3.0
        assert fams["serving_queue_wait_seconds"]["samples"][
            ("serving_queue_wait_seconds_count", ())] == 3.0


# ------------------------------------- deterministic chaos+spec trace
def _chaos_workload():
    rng = np.random.RandomState(17)
    reqs = []
    for i in range(5):
        kw = {}
        if i % 3 == 2:
            kw = dict(temperature=0.8, top_k=5, seed=300 + i)
        reqs.append(GenerationRequest(
            prompt=rng.randint(0, 256, (10,)).astype(np.int32),
            max_new_tokens=8, **kw))
    reqs.append(GenerationRequest(
        prompt=rng.randint(0, 256, (72,)).astype(np.int32),
        max_new_tokens=4))
    return reqs


def _chaos_run(model, reqs, with_plan, trace):
    """One full supervised serving pass under a VirtualClock; the fault
    plan (when on) exercises transient retry, pool preemption, fatal
    rebuild, NaN recompute and a hung-step watchdog rebuild."""
    clk = VirtualClock()

    def factory():
        return _engine(model, prefix_cache=True, prefix_block_size=8,
                       prefill_chunk=32, spec_decode=True, spec_k=3,
                       step_clock=clk)

    plan = None
    if with_plan:
        plan = (FaultPlan(clock=clk)
                .at_step(3, "transient")
                .at_step(6, "pool")
                .at_step(9, "fatal")
                .at_step(13, "hung", stall_s=60.0)
                .at_step(17, "nan"))
    tracer = SpanTracer(clock=clk)
    gw = ServingGateway(factory(), engine_factory=factory, max_queue=32,
                        fault_hook=plan, clock=clk,
                        watchdog_deadline_s=5.0, retry_backoff_s=0.0,
                        max_restarts=16, start=False, tracer=tracer,
                        trace=trace)
    streams = [gw.submit(r) for r in reqs]
    gw.start()
    outs = [s.result() for s in streams]
    engine = gw.engine
    gw.shutdown(drain=True, timeout=60)
    return ([(list(ids), reason) for ids, reason in outs], tracer,
            gw, engine, plan)


class TestDeterministicChaosTrace:
    @pytest.mark.slow  # 6 s chaos-trace duplicate: tracing-off token identity and
    # the chaos byte-identity pins elsewhere run by default (870s cap)
    def test_chaos_spec_trace_byte_stable_and_complete(self, model):
        reqs = _chaos_workload()
        # fault-free baseline, tracing OFF (also warms every program)
        base, _, _, base_eng, _ = _chaos_run(model, reqs,
                                             with_plan=False, trace=False)
        assert all(r in ("stop", "length") for _, r in base)
        # warm pass WITH the plan (recovery-path prefill buckets may
        # compile here; the compared replays below must both run warm,
        # or the watchdog's compile exemption could classify the hung
        # step differently between them)
        _chaos_run(model, reqs, with_plan=True, trace=True)
        outs1, tr1, gw1, eng1, plan1 = _chaos_run(
            model, reqs, with_plan=True, trace=True)
        outs2, tr2, gw2, eng2, plan2 = _chaos_run(
            model, reqs, with_plan=True, trace=True)
        # token streams: byte-identical to the fault-free baseline —
        # tracing observes, recovery recomputes, neither changes a token
        assert outs1 == base and outs2 == base
        # the trace replays BYTE-STABLE: same events, same ts, same
        # normalized request lanes
        doc1 = json.dumps(tr1.export(), sort_keys=True)
        doc2 = json.dumps(tr2.export(), sort_keys=True)
        assert doc1 == doc2
        assert plan1.log == plan2.log and gw1.restarts == gw2.restarts
        # valid chrome trace, and the chaos story is all there
        evs = validate_chrome_trace(json.loads(doc1))
        names = {e["name"] for e in evs}
        assert {"step", "sweep", "plan", "launch", "call", "host-accept",
                "retire", "queued", "decode", "finished", "spec_accept",
                "fault", "rebuild", "recovery", "preempted"} <= names
        assert "gc" not in names        # never under an injected clock
        kinds = {e["args"]["kind"] for e in evs if e["name"] == "fault"}
        assert kinds == {"transient", "fatal", "hung"}
        assert gw1.restarts >= 3      # fatal + hung + nan
        rebuilds = [e for e in evs if e["name"] == "rebuild"]
        assert len(rebuilds) == gw1.restarts
        assert all(e["tid"] == TID_GATEWAY for e in rebuilds)
        recoveries = [e for e in evs if e["name"] == "recovery"]
        assert len(recoveries) == gw1.restarts
        # spec acceptance is visible per launch AND per request
        acc = [e for e in evs if e["name"] == "spec_accept"]
        assert acc and all(e["args"]["accept_lens"] for e in acc)
        dec_args = [e["args"] for e in evs if e["name"] == "decode"]
        assert any("accept_lens" in a for a in dec_args)
        # the hung fault's virtual stall is on the timeline: events
        # after it sit >= 60s past the epoch
        assert max(e["ts"] for e in evs) >= 60e6
        # compile-once discipline includes the traced replay
        assert eng2.decode_compilations() == 1
        assert base_eng.decode_compilations() == 1


# ----------------------------------------------------------- live HTTP
@pytest.fixture(scope="class")
def server(model):
    srv = serve(model, port=0, num_slots=NUM_SLOTS, max_seq_len=S_MAX,
                max_queue=8, model_name="trace-test")
    # warm the decode/prefill programs so capture windows see steps
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


class TestDebugEndpointsHTTP:
    def test_healthz_saturation_fields(self, server):
        status, doc = _get(server, "/healthz")
        assert status == 200
        assert doc["running_slots"] == 0
        assert doc["prefilling_slots"] == 0
        assert doc["waiting_room_occupancy"] == 0
        assert doc["waiting_room_capacity"] == 8
        assert doc["num_slots"] == NUM_SLOTS

    def test_debug_requests_live_table(self, server):
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
        assert row["slot"] is not None
        assert row["prompt_tokens"] == 4
        assert row["max_new_tokens"] == 64
        assert row["queue_wait_s"] is not None
        assert row["ttft_s"] is not None and row["ttft_s"] >= 0
        assert row["kv_tokens"] > 0
        assert row["kv_blocks"] >= 1      # paged default
        ids, reason = stream.result()
        assert reason == "length"
        # drained: the table empties
        _, doc = _get(server, "/debug/requests")
        assert all(r["id"] != stream.id for r in doc["requests"])

    def test_debug_trace_capture_over_http(self, server):
        stream = server.gateway.submit(GenerationRequest(
            prompt=[9, 10, 11, 12], max_new_tokens=96))
        status, doc = _get(server, "/debug/trace?steps=4&timeout_s=30")
        stream.result()
        assert status == 200
        evs = validate_chrome_trace(doc)
        steps = [e for e in evs if e["name"] == "step"]
        assert len(steps) == 4
        assert {"plan", "launch", "host-accept"} <= \
            {e["name"] for e in evs}
        # the capture window closed: tracer is disabled again (this
        # server was not started with --trace)
        assert server.gateway.tracer.enabled is False
        # steps=0 on a non-persistent server: immediate snapshot of
        # whatever the last window captured
        status, doc0 = _get(server, "/debug/trace?steps=0")
        assert status == 200 and doc0["traceEvents"]
        status, _ = _get(server, "/debug/trace?steps=bogus")
        assert status == 400

    def test_capture_serializes(self, model):
        gw = ServingGateway(_engine(model), start=False)
        done = threading.Event()
        results = {}

        def first():
            # idle engine: no steps complete, the window times out and
            # returns whatever was captured (here: nothing)
            results["first"] = gw.capture_trace(steps=4, timeout_s=1.5)
            done.set()

        t = threading.Thread(target=first)
        t.start()
        for _ in range(200):
            if gw._capture is not None:
                break
            time.sleep(0.005)
        assert gw._capture is not None
        with pytest.raises(TraceBusyError):
            gw.capture_trace(steps=1, timeout_s=0.1)
        done.wait(10)
        t.join(10)
        assert "traceEvents" in results["first"]
        assert gw.tracer.enabled is False
        gw.shutdown(drain=False, timeout=10)

    def test_capture_timeout_clamps_and_cleans_up(self, model):
        gw = ServingGateway(_engine(model), start=False)
        # negative timeout clamps to 0: immediate empty-window return,
        # with the capture slot released and the tracer disabled (a
        # failed capture must never 409 every later one)
        doc = gw.capture_trace(steps=2, timeout_s=-5)
        assert "traceEvents" in doc
        assert gw._capture is None
        assert gw.tracer.enabled is False
        doc = gw.capture_trace(steps=2, timeout_s=0)    # reusable
        assert "traceEvents" in doc and gw._capture is None
        gw.shutdown(drain=False, timeout=10)

    def test_persistent_trace_flag_reports_effective(self, model):
        srv = serve(model, port=0, num_slots=NUM_SLOTS,
                    max_seq_len=S_MAX, start=False, trace=True,
                    trace_buffer=2048)
        try:
            # the banner reads exactly these (effective-value idiom)
            assert srv.gateway.tracer.enabled is True
            assert srv.gateway.tracer.capacity == 2048
        finally:
            srv.gateway.shutdown(drain=False, timeout=10)


# -------------------------------------------------------- profiler CLI
class TestProfilerCLI:
    """The FILE path (a Chrome trace) is held by test_cost_observatory's
    ``TestProfilerCLIChrome``; a device trace DIRECTORY is the benchmark's
    to read (``benchmark/xplane_reduce.py``, ``benchmark/tests``)."""

    def test_a_directory_exits_one_and_names_the_reducer(self, tmp_path):
        from paddle_tpu.profiler.__main__ import main
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = main([str(tmp_path)])
        assert rc == 1
        assert "benchmark/xplane_reduce.py" in buf.getvalue()
