"""The driver thread's phase clock (ISSUE 35; ten phases and the long visits
since ISSUE 52).

``profiler.driver_clock.DriverClock`` partitions the time of the thread that
runs the gateway's loop and ``engine.step()`` into ten phases, on the wall
clock and on the thread's CPU clock, always on; the engine's and the gateway's
``_mark`` tick it at the boundaries where the spans are, and the spans carry
the marks' own readings. Pinned here: the partition is exact over a run with
every kind of boundary, the two synchronous steps included; a phase's spans
sum to what the clock charged it (``retire`` less the one reading a step from
the ``step`` span's end to the gateway's mark); a visit longer than
``LONG_VISIT_S`` is counted once, in its phase; the families on ``/metrics``
are whole, monotonic and survive a rebuild; tracing off records nothing and a
chaos replay is still byte-stable; the benchmark's seven readers read what
they say (the host's own work is still every phase but the two waiting ones),
and None where the family is absent; the traced step's counter samples lie
under its ``step`` span and walk no table.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.profiler.driver_clock import (LONG_VISIT_S, PHASES,
                                              DriverClock)
from paddle_tpu.profiler.tracing import (NULL_SPAN, TID_ENGINE, TID_GATEWAY,
                                         SpanTracer)
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.faults import FaultPlan, VirtualClock
from paddle_tpu.serving.server.gateway import ServingGateway

import serving_support
from test_metrics_prom import parse_prometheus

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import driver_clock as bench_clock  # noqa: E402
import readers  # noqa: E402

NUM_SLOTS, S_MAX, CHUNK = 3, 128, 32
TICK = 0.125        # dyadic, and a whole number of microseconds
FAMILY = "serving_driver_seconds_total"
LONG_FAMILIES = ("serving_driver_long_visits_total",
                 "serving_driver_long_visit_seconds_total")
#: the phases whose spans open and close at the clock's own marks
SPAN_PHASES = ("sweep", "admit", "plan", "dispatch", "device-wait",
               "host-accept")


class TickingClock(VirtualClock):
    """A virtual clock that every reading moves on by one tick: whatever
    lies between two marks has a length, and every sum of readings is exact
    in floating point."""

    def __call__(self):
        self.t += TICK
        return self.t


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=35)


def _engine(model, **kw):
    """The shared helper at this file's geometry: the phases' sums below
    are reckoned for three slots, 128 positions and chunks of 32."""
    return serving_support.engine(
        model, num_slots=NUM_SLOTS, max_seq_len=S_MAX, prefill_chunk=CHUNK,
        **kw)


def _reqs():
    """Whole-prompt admissions, one prompt that goes through chunks, more
    requests than slots."""
    rng = np.random.RandomState(5)
    lens = (10, 12, 72, 9, 14)
    return [GenerationRequest(
        prompt=rng.randint(1, 256, (n,)).astype(np.int32),
        max_new_tokens=6 + i) for i, n in enumerate(lens)]


def _drive(gw, streams, cancel=None):
    """The gateway's own loop on the caller's thread, until everything
    submitted has finished: one thread, so a run is deterministic and a
    mark made before and after it bounds it. ``cancel`` is a stream that is
    cancelled once it has streamed two tokens (a drain outside a step)."""
    seen, on_token = [0], gw.engine.on_token

    def hook(seq, token):
        on_token(seq, token)
        if cancel is not None and gw._live.get(seq.request_id) is cancel:
            seen[0] += 1
            if seen[0] == 2:
                cancel.cancel()
    gw.engine.on_token = hook
    first = gw.driver_clock.enter("loop")
    gw._closed = True               # _run returns once it has drained
    gw._run()
    last = gw.driver_clock.enter("idle-wait")
    assert all(s.finish_reason is not None for s in streams)
    return first, last


def _gateway(model, clock, engine_kw=None, **kw):
    return ServingGateway(_engine(model, **(engine_kw or {})), clock=clock,
                          start=False, max_queue=32, **kw)


def _spans(evs, name):
    return [e for e in evs if e["name"] == name and e["ph"] == "X"]


def _inside(evs, parent, name):
    lo, hi = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in _spans(evs, name)
            if lo <= e["ts"] and e["ts"] + e["dur"] <= hi]


# ---------------------------------------------------------------- the clock
class TestDriverClock:
    def test_marks_partition_the_time_between_them(self):
        wall, cpu = iter([1.0, 1.5, 4.0, 4.25]), iter([10, 30, 70, 75])
        dc = DriverClock(wall=lambda: next(wall), cpu=lambda: next(cpu))
        assert dc.phase is None
        assert dc.enter("loop") == 1.0         # the first mark charges nothing
        assert dc.enter("plan") == 1.5
        dc.enter("device-wait")
        dc.enter("loop")
        assert dc.wall_s == {**dict.fromkeys(PHASES, 0.0), "loop": 0.5,
                             "plan": 2.5, "device-wait": 0.25}
        assert dc.cpu_ns == {**dict.fromkeys(PHASES, 0), "loop": 20,
                             "plan": 40, "device-wait": 5}
        assert dc.seconds("plan", "wall") == 2.5
        assert dc.seconds("plan", "cpu") == 40e-9
        assert sum(dc.wall_s.values()) == 4.25 - 1.0
        assert dc.phase == "loop"

    def test_ten_phases_and_no_more(self):
        assert len(PHASES) == len(set(PHASES)) == 10
        assert {"sweep", "retire", "other"} <= set(PHASES)

    def test_a_long_visit_is_counted_once_in_its_own_phase(self):
        tick = 2.0 ** -20                       # exact sums
        wall = iter([0.0, LONG_VISIT_S + tick,              # plan: long
                     2 * LONG_VISIT_S + tick,               # dispatch: not
                     2 * LONG_VISIT_S + 2 * tick,           # loop: not
                     1.0])                                  # device-wait
        dc = DriverClock(wall=lambda: next(wall), cpu=lambda: 0)
        for phase in ("plan", "dispatch", "loop", "device-wait", "loop"):
            dc.enter(phase)
        # exactly LONG_VISIT_S is not longer than it; the waiting phases
        # are counted like the others (their readers leave them out)
        assert dc.long_visits == {**dict.fromkeys(PHASES, 0), "plan": 1,
                                  "device-wait": 1}
        assert dc.long_visit_s == {
            **dict.fromkeys(PHASES, 0.0), "plan": LONG_VISIT_S + tick,
            "device-wait": 1.0 - 2 * LONG_VISIT_S - 2 * tick}
        assert 0.004 < LONG_VISIT_S < 0.0107    # under every device step

    def test_default_clocks_are_the_threads_own(self):
        dc = DriverClock()
        dc.enter("loop")
        t_end = time.thread_time() + 0.02       # (the thread's own CPU: a
        while time.thread_time() < t_end:       # loaded box gives it less
            pass                                # than the wall shows)
        dc.enter("idle-wait")
        time.sleep(0.05)                        # blocked: wall only
        dc.enter("loop")
        assert dc.seconds("loop", "cpu") >= 0.01
        assert dc.seconds("idle-wait", "wall") >= 0.05
        assert dc.seconds("idle-wait", "cpu") < 0.02

    def test_an_engine_without_a_gateway_has_none(self, model):
        eng = _engine(model)
        assert eng.driver_clock is None
        eng.generate(_reqs()[:2])               # every mark is a no-op


# ------------------------------------------------------------- the partition
class TestPhasesPartitionTheRun:
    def test_wall_sums_to_elapsed_over_every_kind_of_boundary(self, model):
        clk = TickingClock()
        gw = _gateway(model, clk, fault_hook=FaultPlan().at_step(6, "pool"))
        streams = [gw.submit(r) for r in _reqs()]
        first, last = _drive(gw, streams, cancel=streams[1])
        st, dc = gw.engine.stats, gw.driver_clock
        # the run held every boundary the clock is marked at
        assert st["prefills"] >= 3 and st["prefill_chunks"] >= 2
        assert st["drains_idle"] >= 1 and st["drains_cancel"] == 1
        assert st["drains_pool"] + st["preemptions"] >= 1
        assert streams[1].finish_reason == "cancelled"
        assert sum(dc.wall_s.values()) == last - first      # exactly
        assert all(dc.wall_s[p] > 0 for p in PHASES if p != "idle-wait")
        assert gw.tracer.events() == []         # and tracing was off
        # every reading moves the clock on by far more than LONG_VISIT_S:
        # every visit is a long one, and they too partition the run
        assert sum(dc.long_visit_s.values()) == last - first
        assert dc.long_visit_s == dc.wall_s

    @pytest.mark.parametrize("engine_kw", [
        dict(spec_decode=True, spec_k=3), dict(decode_ticks=4)],
        ids=["spec", "multitick"])
    def test_the_synchronous_steps_partition_and_span_alike(self, model,
                                                            engine_kw):
        clk = TickingClock()
        gw = _gateway(model, clk, engine_kw, trace=True)
        first, last = _drive(gw, [gw.submit(r) for r in _reqs()])
        st, dc, evs = gw.engine.stats, gw.driver_clock, gw.tracer.events()
        assert st["spec_steps"] + st["mtick_syncs"] >= 3
        assert st["unified_steps"] == st["mtick_syncs"]
        assert sum(dc.wall_s.values()) == last - first
        assert all(dc.wall_s[p] > 0 for p in PHASES if p != "idle-wait")
        steps = _spans(evs, "step")
        for name in ("sweep", "retire"):        # one each, inside the step
            assert [len(_inside(evs, s, name)) for s in steps] \
                == [1] * len(steps), name
        dispatches = _spans(evs, "dispatch")
        assert dispatches and all(len(_inside(evs, d, "call")) == 1
                                  for d in dispatches)
        assert len(_spans(evs, "call")) == len(dispatches)

    def test_spans_sum_to_what_the_clock_charged_their_phase(self, model):
        clk = TickingClock()
        at_first_step = {}

        def snapshot(engine):       # top of a step: the loop is behind it
            if not at_first_step:
                at_first_step.update(gw.driver_clock.wall_s)
        gw = _gateway(model, clk, trace=True, fault_hook=snapshot)
        assert gw.driver_clock.stamps_spans
        streams = [gw.submit(r) for r in _reqs()]
        _drive(gw, streams)
        evs = gw.tracer.events()
        wall = gw.driver_clock.wall_s
        for name in SPAN_PHASES + ("loop",):
            spans = _spans(evs, name)
            assert spans, name
            assert sum(e["dur"] for e in spans) / 1e6 \
                == wall[name] - at_first_step[name], name
        # ``retire`` closes at the reading that closes ``step`` (spans of a
        # lane nest); the clock's phase runs on to the gateway's mark, one
        # reading later
        steps, retires = _spans(evs, "step"), _spans(evs, "retire")
        assert len(retires) == len(steps) == gw.engine.stats["steps"]
        assert sum(e["dur"] for e in retires) / 1e6 \
            == wall["retire"] - TICK * len(steps)
        assert [s["ts"] + s["dur"] for s in steps] \
            == [r["ts"] + r["dur"] for r in retires]
        # ``sweep`` and ``retire`` are the step's first and last child, and
        # ``call`` lies inside ``dispatch`` (a span of the tracer alone)
        for s in steps:
            (sweep,) = _inside(evs, s, "sweep")
            (retire,) = _inside(evs, s, "retire")
            assert sweep["ts"] > s["ts"] and sweep["tid"] == TID_ENGINE
            assert set(sweep["args"]) == {"queued", "admitted"}
        dispatches = _spans(evs, "dispatch")
        assert [len(_inside(evs, d, "call")) for d in dispatches] \
            == [1] * len(dispatches)
        assert sum(e["dur"] for e in _spans(evs, "call")) / 1e6 \
            < wall["dispatch"]
        # before the first step the loop has no span; after it, all of it
        assert at_first_step["loop"] > 0
        assert all(e["tid"] == TID_GATEWAY for e in evs
                   if e["name"] == "loop")
        # ``other`` and ``idle-wait`` are phases of the clock alone, and
        # ``call`` is a span of the tracer alone
        assert not {"other", "idle-wait"} & {e["name"] for e in evs}
        assert "call" not in PHASES

    def test_a_deadline_drain_stops_the_sweep_and_it_goes_on_after(
            self, model):
        """A running sequence found past its deadline is retired by the
        sweep, after the program in flight is fenced: ``device-wait`` and
        ``host-accept`` inside the sweep. Its span stops at the fence and
        a second one goes on after, so the spans still sum to the clock."""
        from test_tracing import validate_chrome_trace
        clk = TickingClock()
        gw = _gateway(model, clk, trace=True)
        streams = [gw.submit(r) for r in _reqs()[:2]]
        seen, on_token = [0], gw.engine.on_token

        def hook(seq, token):
            on_token(seq, token)
            if gw._live.get(seq.request_id) is streams[0]:
                seen[0] += 1
                if seen[0] == 2:
                    seq.deadline = 0.0      # due at the next step's sweep
        gw.engine.on_token = hook
        _drive(gw, streams)
        assert gw.engine.stats["drains_deadline"] == 1
        assert streams[0].finish_reason == "timeout"
        evs, wall = gw.tracer.events(), gw.driver_clock.wall_s
        for name in SPAN_PHASES:
            assert sum(e["dur"] for e in _spans(evs, name)) / 1e6 \
                == wall[name], name
        sweeps = [_inside(evs, s, "sweep") for s in _spans(evs, "step")]
        assert sorted(map(len, sweeps)) == [1] * (len(sweeps) - 1) + [2]
        (first, second), = [sw for sw in sweeps if len(sw) == 2]
        between = [e["name"] for e in _spans(evs, "device-wait")
                   + _spans(evs, "host-accept")
                   if first["ts"] + first["dur"] <= e["ts"] < second["ts"]]
        assert between == ["device-wait", "host-accept"]
        assert "queued" in first["args"] and "admitted" in second["args"]
        validate_chrome_trace(gw.tracer.export())

    def test_a_tracer_on_its_own_clock_reads_that(self, model):
        """The marks' readings are the gateway clock's: a tracer injected
        with another clock must not be handed them."""
        tracer_clock = VirtualClock(start=1000.0)
        tracer = SpanTracer(clock=tracer_clock).enable()
        gw = _gateway(model, TickingClock(), tracer=tracer)
        assert not gw.driver_clock.stamps_spans
        _drive(gw, [gw.submit(r) for r in _reqs()[:2]])
        spans = [e for e in tracer.events() if e["ph"] == "X"]
        assert spans and all(e["ts"] == 0.0 and e["dur"] == 0.0
                             for e in spans)
        assert sum(gw.driver_clock.wall_s.values()) > 0


# ------------------------------------------------------------------ /metrics
def _family(gw, long_visits=False):
    fams = parse_prometheus(gw.registry.render())
    assert fams[FAMILY]["type"] == "counter"
    out = {dict(labels)["phase"] + "/" + dict(labels)["clock"]: v
           for (_, labels), v in fams[FAMILY]["samples"].items()}
    if long_visits:
        for name in LONG_FAMILIES:
            assert fams[name]["type"] == "counter"
            out.update({dict(labels)["phase"] + "/" + name: v for
                        (_, labels), v in fams[name]["samples"].items()})
    return out


class TestMetricsFamily:
    def test_all_phases_both_clocks_and_the_wall_partition(self, model):
        gw = _gateway(model, None)
        t0 = time.monotonic()
        gw.start()
        for s in [gw.submit(r) for r in _reqs()]:
            s.result()
        time.sleep(0.1)             # the driver is in its idle wait
        fam = _family(gw)
        elapsed = time.monotonic() - t0
        assert set(fam) == {p + "/" + c for p in PHASES
                            for c in ("wall", "cpu")}
        wall = sum(v for k, v in fam.items() if k.endswith("/wall"))
        assert 0.5 * elapsed < wall <= elapsed
        assert fam["idle-wait/wall"] > 0 and fam["dispatch/wall"] > 0
        # the thread cannot have computed for longer than it lived
        assert sum(v for k, v in fam.items() if k.endswith("/cpu")) <= wall
        # the long visits: a series a phase in both families, seconds no
        # more than the phase's own, and a long one is longer than the bar.
        # Read with the driver stopped: only it writes the clock, and a
        # live one may end a long idle wait between a scrape's reads
        assert gw.shutdown(drain=True, timeout=60)
        both = _family(gw, long_visits=True)
        for p in PHASES:
            n, secs = (both[p + "/" + name] for name in LONG_FAMILIES)
            assert n == gw.driver_clock.long_visits[p]
            assert n * LONG_VISIT_S <= secs <= both[p + "/wall"] + 1e-9
        assert both["idle-wait/" + LONG_FAMILIES[0]] >= 1   # the 0.1 s above

    def test_monotonic_through_an_engine_rebuild(self, model):
        plan = FaultPlan().at_step(4, "fatal").at_step(9, "transient")
        gw = ServingGateway(_engine(model),
                            engine_factory=lambda: _engine(model),
                            fault_hook=plan, retry_backoff_s=0.0,
                            start=False, max_queue=32)
        clock, samples, stop = gw.driver_clock, [], threading.Event()

        def scrape():
            while not stop.is_set():
                samples.append(_family(gw, long_visits=True))
                time.sleep(0.002)
        th = threading.Thread(target=scrape)
        streams = [gw.submit(r) for r in _reqs()]
        th.start()
        gw.start()
        for s in streams:
            assert s.result()[1] in ("stop", "length")
        stop.set()
        th.join(10)
        assert not th.is_alive()
        samples.append(_family(gw, long_visits=True))
        assert gw.restarts >= 1
        assert gw.driver_clock is clock and gw.engine.driver_clock is clock
        for key in samples[0]:
            vals = [s[key] for s in samples]
            assert all(a <= b for a, b in zip(vals, vals[1:])), key
        assert samples[-1]["dispatch/wall"] > samples[0]["dispatch/wall"]
        gw.shutdown(drain=True, timeout=60)


# --------------------------------------------------- tracing off, and replays
def _chaos(model, trace):
    clk = VirtualClock()
    plan = (FaultPlan(clock=clk).at_step(3, "transient").at_step(5, "pool")
            .at_step(8, "fatal").at_step(12, "hung", stall_s=60.0))
    gw = ServingGateway(
        _engine(model, step_clock=clk),
        engine_factory=lambda: _engine(model, step_clock=clk), clock=clk,
        fault_hook=plan, watchdog_deadline_s=5.0, retry_backoff_s=0.0,
        max_restarts=8, start=False, max_queue=32, trace=trace)
    streams = [gw.submit(r) for r in _reqs()]
    gw.start()
    outs = [s.result() for s in streams]
    gw.shutdown(drain=True, timeout=60)
    return [(list(ids), why) for ids, why in outs], gw, plan


class TestTracingOffAndReplays:
    def test_tracing_off_records_no_event_and_the_clock_runs(self, model):
        outs, gw, plan = _chaos(model, trace=False)
        assert gw.tracer.events() == [] and gw.tracer.dropped == 0
        assert [k for _, k in plan.log] \
            == ["transient", "pool", "fatal", "hung"]
        # the hung step's virtual stall is the only time that passed, and
        # it passed in the fault hook, which the step's ``sweep`` holds:
        # one long visit, counted there
        dc = gw.driver_clock
        assert dc.wall_s["sweep"] == 60.0
        assert sum(dc.wall_s.values()) == 60.0
        assert dc.long_visits == {**dict.fromkeys(PHASES, 0), "sweep": 1}
        assert dc.long_visit_s["sweep"] == 60.0

    def test_tracing_off_builds_no_args_and_opens_no_span_at_any_mark(
            self, model):
        """Every mark of a run with the tracer off, the new ``sweep`` and
        ``retire`` included, is handed no args dict and no span: sites
        build them behind the tracer (``tr and {...}``)."""
        gw = _gateway(model, TickingClock())
        marks, mark = [], gw.engine._mark

        def spy(phase, span=False, **kw):
            marks.append((phase, kw))
            opened = mark(phase, span=span, **kw)
            assert opened is None
            return opened
        gw.engine._mark = spy
        spans = []
        gw.tracer.span = lambda *a, **kw: spans.append(a)   # never reached
        _drive(gw, [gw.submit(r) for r in _reqs()])
        assert {"sweep", "admit", "plan", "dispatch", "device-wait",
                "host-accept", "retire", "other"} == {p for p, _ in marks}
        # (a drain's ``launch`` is the tracer's shared no-op span)
        assert {repr(v) for _, kw in marks for v in kw.values()
                if v is not None and v is not NULL_SPAN} == set()
        assert spans == [] and gw.engine._sweep is None \
            and gw.engine._retire_span is None

    def test_two_chaos_replays_are_byte_identical(self, model):
        _chaos(model, trace=True)       # recovery-path programs compile here
        outs1, gw1, plan1 = _chaos(model, trace=True)
        outs2, gw2, plan2 = _chaos(model, trace=True)
        assert outs1 == outs2 and plan1.log == plan2.log
        assert gw1.restarts == gw2.restarts >= 2
        doc1 = json.dumps(gw1.tracer.export(), sort_keys=True)
        assert doc1 == json.dumps(gw2.tracer.export(), sort_keys=True)
        names = {e["name"] for e in json.loads(doc1)["traceEvents"]}
        assert {"step", "sweep", "admit", "plan", "launch", "dispatch",
                "call", "device-wait", "host-accept", "retire", "loop",
                "rebuild", "kv_blocks"} <= names
        assert "gc" not in names        # never under an injected clock
        assert gw1.driver_clock.wall_s == gw2.driver_clock.wall_s
        assert gw1.driver_clock.long_visits == gw2.driver_clock.long_visits


# --------------------------------------------------------- the traced step
class TestTracedStepCounters:
    def test_samples_lie_under_the_step_span_and_walk_no_table(
            self, model, monkeypatch):
        clk = TickingClock()
        gw = _gateway(model, clk, trace=True)
        walks = []
        monkeypatch.setattr(
            type(gw.engine.cache), "occupancy",
            lambda self: walks.append(1) or {"live": 0, "trie": 0,
                                             "free": 0})
        _drive(gw, [gw.submit(r) for r in _reqs()])
        assert walks == []          # a traced step never walks the tables
        evs = gw.tracer.events()
        steps = [e for e in evs if e["name"] == "step"]
        tracks = ("kv_blocks", "block_table_fill", "dispatches",
                  "transfer_bytes")
        for track in tracks:
            samples = [e for e in evs if e["name"] == track]
            assert len(samples) == len(steps)
            for c, s in zip(samples, steps):
                assert s["ts"] < c["ts"] < s["ts"] + s["dur"], track
        kv = [e["args"] for e in evs if e["name"] == "kv_blocks"]
        pool = gw.engine.cache.pool
        assert all(set(a) == {"used", "free"}
                   and a["used"] + a["free"] == pool.num_blocks for a in kv)
        assert kv[-1] == {"used": pool.num_used, "free": pool.num_free}


# ------------------------------------------------- the benchmark's readers
def _scrape(t, steps, wall, cpu=None, family=True):
    """One ``(time, scrape)`` as ``kinds/serve.parse_prometheus`` gives it:
    ``wall`` and ``cpu`` are seconds by phase."""
    s = {"serving_step_duration_seconds_count": {"": float(steps)}}
    if family:
        s[FAMILY] = {}
        for clock, vals in (("wall", wall), ("cpu", cpu or wall)):
            for p in PHASES:
                s[FAMILY]['{clock="%s",phase="%s"}' % (clock, p)] = \
                    float(vals.get(p, 0.0))
    return t, s


def _src(family=True, n=4):
    """A traced run by hand: four scrapes, the second pair brackets the 3 s
    trace. Per scrape: steps, wall seconds by phase, CPU seconds by phase."""
    rows = [
        (0.0, 0, {}, {}),
        (1.0, 100, {"loop": 0.01, "admit": 0.02, "plan": 0.01,
                    "dispatch": 0.10, "device-wait": 0.80,
                    "host-accept": 0.04, "other": 0.02},
         {"loop": 0.01, "admit": 0.01, "plan": 0.01, "dispatch": 0.06,
          "device-wait": 0.01, "host-accept": 0.04, "other": 0.02}),
        (4.5, 300, {"loop": 0.05, "admit": 0.10, "plan": 0.03,
                    "dispatch": 0.70, "device-wait": 3.20,
                    "host-accept": 0.16, "other": 0.22, "idle-wait": 0.04},
         {"loop": 0.05, "admit": 0.03, "plan": 0.03, "dispatch": 0.40,
          "device-wait": 0.03, "host-accept": 0.16, "other": 0.20}),
        (5.5, 400, {"loop": 0.06, "admit": 0.12, "plan": 0.04,
                    "dispatch": 0.80, "device-wait": 4.00,
                    "host-accept": 0.20, "other": 0.24, "idle-wait": 0.04},
         {"loop": 0.06, "admit": 0.04, "plan": 0.04, "dispatch": 0.46,
          "device-wait": 0.04, "host-accept": 0.20, "other": 0.22}),
    ][:n]
    scrapes = [_scrape(t, k, w, c, family) for t, k, w, c in rows]
    return {"metrics_delta": {"start": scrapes[0][1], "end": scrapes[-1][1],
                              "scrapes": scrapes},
            "trace_window_s": 3.0}


# over the window: 400 steps; wall busy 0.06 + 0.12 + 0.04 + 0.80 + 0.20 + 0.24
# = 1.46 s, CPU busy 1.02 s, device-wait 4.00 s, idle-wait 0.04 s; between
# the bracket's scrapes 200 steps and 1.26 - 0.20 = 1.06 s busy, outside it
# 200 steps and 0.40 s
EXPECTED = {
    "host_busy_ms_per_step": 1e3 * 1.46 / 400,
    "host_headroom_share": 100.0 * 4.00 / 5.46,
    "dispatch_ms_per_step": 1e3 * 0.80 / 400,
    "admit_ms_per_step": 1e3 * 0.12 / 400,
    "loop_ms_per_step": 1e3 * 0.06 / 400,
    "driver_offcpu_ms_per_step": 1e3 * (1.46 - 1.02) / 400,
    "profiler_host_inflation_ms_per_step":
        1e3 * 1.06 / 200 - 1e3 * 0.40 / 200,
}


def _split_other(src):
    """The same run as a program with ten phases reports it: what the
    parent charged to ``other`` is ``sweep``, ``retire`` and a rest."""
    for _, scrape in src["metrics_delta"]["scrapes"]:
        fam = scrape[FAMILY]
        for clock in ("wall", "cpu"):
            key = '{clock="%s",phase="%%s"}' % clock
            whole = fam[key % "other"]
            fam[key % "sweep"] = 0.25 * whole
            fam[key % "retire"] = 0.5 * whole
            fam[key % "other"] = 0.25 * whole
    return src


class TestBenchmarkReaders:
    @pytest.mark.parametrize("name", sorted(EXPECTED))
    def test_reads_the_hand_computed_value(self, name):
        assert readers.same_as(name)(_src()) \
            == pytest.approx(EXPECTED[name], rel=1e-12)

    @pytest.mark.parametrize("name", sorted(EXPECTED))
    def test_the_same_totals_with_other_split_in_three(self, name):
        """``busy_s`` sums every phase but the two waiting ones by label:
        the host's own work, its headroom and its off-CPU time read what
        they read when ``sweep`` and ``retire`` were part of ``other``."""
        assert readers.same_as(name)(_split_other(_src())) \
            == pytest.approx(EXPECTED[name], rel=1e-12)

    @pytest.mark.parametrize("name", sorted(EXPECTED))
    def test_none_where_the_family_is_absent(self, name):
        reduce = readers.same_as(name)
        assert reduce(_src(family=False)) is None   # the parent's program
        assert reduce({}) is None                   # a training run
        assert reduce({"metrics_delta": None}) is None

    def test_the_bracket_needs_three_scrapes_and_a_trace(self):
        reduce = readers.same_as("profiler_host_inflation_ms_per_step")
        assert bench_clock.bracket(_src()) == (1, 2)
        assert reduce(_src(n=2)) is None            # a ``--trace 0`` run
        untraced = dict(_src(), trace_window_s=None)
        assert reduce(untraced) is None
        # scrapes a second apart and none over the trace's length: no pair
        even = _src()
        even["metrics_delta"]["scrapes"] = [
            (float(i), s) for i, (_, s)
            in enumerate(even["metrics_delta"]["scrapes"])]
        assert bench_clock.bracket(even) is None and reduce(even) is None

    def test_every_new_metric_is_listed_for_every_serving_cell(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        serving = [w["name"] for w in bench["workloads"]
                   if w["name"].startswith("serve-")]
        entries = {m["name"]: m for m in bench["per_layer"]}
        for name in EXPECTED:
            m = entries[name]
            assert m["workloads"] == serving and len(serving) >= 6
            assert m["source"] == "program_counter"
            assert m["moves"] == "gap_p50_ms"
