"""The collector's pauses (ISSUE 52): ``profiler.gc_watch.GcWatch``.

A collection stops every thread of the process, so the gateway counts each
one by generation, always (``serving_gc_pause_seconds_total``,
``serving_gc_collections_total``), and while the tracer records on the
machine's clock gives each a ``gc`` span on a lane of its own, mirrored into
the device trace. Pinned here: a forced collection adds to generation 2's two
series and yields one span; under an injected clock it yields none, and a
chaos replay stays byte-identical whatever the collector does; the watcher
is on ``gc.callbacks`` while a gateway's driver runs, once, rebuilds
included, and gone when it has closed; tracing off records nothing and
builds nothing; a span recorded by a thread that holds the tracer's lock
does not deadlock.
"""
import gc
import json
import threading
import time

import pytest

from paddle_tpu.profiler import chrometrace
from paddle_tpu.profiler.gc_watch import GENERATIONS, GcWatch
from paddle_tpu.profiler.tracing import (TID_ENGINE, TID_GATEWAY, TID_GC,
                                         TID_REQ0, SpanTracer)
from paddle_tpu.serving import FaultPlan, VirtualClock
from paddle_tpu.serving.server.gateway import ServingGateway

import serving_support
from test_driver_clock import _chaos, _engine, _reqs
from test_metrics_prom import parse_prometheus
from test_one_timeline import Mirror

FAMILIES = ("serving_gc_pause_seconds_total", "serving_gc_collections_total")


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=35)


@pytest.fixture
def installed():
    """Install watchers for the length of a test, whatever it raises."""
    watchers = []

    def install(watch):
        watchers.append(watch.install())
        return watch
    yield install
    for w in watchers:
        w.remove()


def _mine(gw):
    return [cb for cb in gc.callbacks if cb is gw.gc_watch]


# ------------------------------------------------------------- the watcher
class TestGcWatch:
    def test_a_forced_collection_adds_to_generation_two(self, installed):
        w = installed(GcWatch(wall=time.perf_counter))
        before = (dict(w.collections), dict(w.pause_s))
        gc.collect()
        assert w.collections[2] == before[0][2] + 1
        assert w.pause_s[2] > before[1][2]
        gc.collect(0)
        assert w.collections[0] == before[0][0] + 1
        assert set(w.collections) == set(w.pause_s) == set(GENERATIONS)

    def test_install_is_idempotent_and_remove_removes(self):
        w = GcWatch(wall=time.perf_counter)
        w.install().install()
        assert gc.callbacks.count(w) == 1
        w.remove()
        w.remove()
        assert w not in gc.callbacks
        n = w.collections[2]
        gc.collect()
        assert w.collections[2] == n            # gone: counts nothing

    def test_installed_between_start_and_stop_counts_nothing(self):
        w = GcWatch(wall=time.perf_counter)
        w("stop", {"generation": 2, "collected": 0, "uncollectable": 0})
        assert w.collections == dict.fromkeys(GENERATIONS, 0)

    def test_a_span_a_collection_on_the_real_clock(self, installed):
        mirror = Mirror()
        tr = SpanTracer(annotate=mirror).enable()
        w = installed(GcWatch(wall=tr.clock, tracer=tr))
        gc.collect()
        spans = [e for e in tr.events() if e["name"] == "gc"]
        full = [e for e in spans if e["args"]["generation"] == 2]
        assert len(full) == 1 and len(spans) == sum(w.collections.values())
        (e,) = full
        assert e["ph"] == "X" and e["tid"] == TID_GC and e["dur"] > 0
        assert set(e["args"]) == {"generation", "collected"}
        # on a lane of its own below the request lanes: mirrored into the
        # device trace like an engine- or gateway-lane span
        assert TID_GC not in (TID_ENGINE, TID_GATEWAY) and TID_GC < TID_REQ0
        assert chrometrace.lane_name(TID_GC) == "gc"
        assert ("gc", {"generation": 2}) in mirror.opened
        assert mirror.closed.count("gc") == len(spans)
        # the span's length is the counter's pause, to the two readings
        # between them
        assert e["dur"] / 1e6 == pytest.approx(w.pause_s[2], abs=5e-3)

    def test_no_span_under_an_injected_clock(self, installed):
        clk = VirtualClock()
        tr = SpanTracer(clock=clk).enable()
        assert not tr.real_clock and SpanTracer().real_clock
        w = installed(GcWatch(wall=clk, tracer=tr))
        gc.collect()
        assert tr.events() == []
        assert w.collections[2] == 1 and w.pause_s[2] == 0.0

    def test_tracing_off_records_nothing_and_builds_nothing(self, installed,
                                                            monkeypatch):
        tr = SpanTracer()                       # never enabled
        calls = []
        monkeypatch.setattr(tr, "span",
                            lambda *a, **kw: calls.append((a, kw)))
        w = installed(GcWatch(wall=time.perf_counter, tracer=tr))
        gc.collect()
        assert calls == [] and tr.events() == [] and w._span is None
        assert w.collections[2] == 1

    def test_a_collection_under_the_tracers_lock_does_not_deadlock(
            self, installed):
        """A collection starts between two bytecodes of whichever thread
        allocates, the one inside the tracer's lock included."""
        tr = SpanTracer().enable()
        installed(GcWatch(wall=tr.clock, tracer=tr))
        done = threading.Event()

        def collect_inside():
            with tr._lock:
                gc.collect()
            done.set()
        t = threading.Thread(target=collect_inside, daemon=True)
        t.start()
        assert done.wait(30), "the gc span's append waited for its own lock"
        t.join(30)
        assert any(e["name"] == "gc" for e in tr.events())


# ------------------------------------------------------------- the gateway
def _series(gw):
    fams = parse_prometheus(gw.registry.render())
    out = {}
    for name in FAMILIES:
        assert fams[name]["type"] == "counter"
        out[name] = {dict(labels)["generation"]: v
                     for (_, labels), v in fams[name]["samples"].items()}
    return out


class TestGatewayOwnsOne:
    def test_on_the_callbacks_while_the_driver_runs_and_gone_after(
            self, model):
        gw = ServingGateway(_engine(model), start=False, max_queue=32,
                            trace=True)
        assert _mine(gw) == []                  # not before it starts
        gw.start()
        for s in [gw.submit(r) for r in _reqs()[:2]]:
            s.result()
        assert _mine(gw) == [gw.gc_watch]
        before = _series(gw)
        assert all(set(v) == {"0", "1", "2"} for v in before.values())
        gc.collect()
        after = _series(gw)
        assert after[FAMILIES[1]]["2"] == before[FAMILIES[1]]["2"] + 1
        assert after[FAMILIES[0]]["2"] > before[FAMILIES[0]]["2"]
        assert all(after[f][g] >= before[f][g] for f in FAMILIES
                   for g in "012")
        # the gateway's tracer records on the real clock: one span a
        # collection, on the collector's lane
        full = [e for e in gw.tracer.events() if e["name"] == "gc"
                and e["args"]["generation"] == 2]
        assert len(full) == gw.gc_watch.collections[2] >= 1
        assert all(e["tid"] == TID_GC for e in full)
        assert gw.shutdown(drain=True, timeout=60)
        assert _mine(gw) == []
        n = gw.gc_watch.collections[2]
        gc.collect()
        assert gw.gc_watch.collections[2] == n

    def test_exactly_one_through_a_rebuild(self, model):
        gw = ServingGateway(_engine(model),
                            engine_factory=lambda: _engine(model),
                            fault_hook=FaultPlan().at_step(4, "fatal"),
                            retry_backoff_s=0.0, start=False, max_queue=32)
        watch = gw.gc_watch
        streams = [gw.submit(r) for r in _reqs()]
        gw.start()
        for s in streams:
            assert s.result()[1] in ("stop", "length")
        assert gw.restarts == 1
        assert gw.gc_watch is watch and _mine(gw) == [watch]
        assert gw.shutdown(drain=True, timeout=60)
        assert _mine(gw) == []

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_gone_when_the_driver_dies(self, model):
        gw = ServingGateway(_engine(model), max_restarts=0,
                            fault_hook=FaultPlan().at_step(1, "fatal"),
                            start=False, max_queue=32)
        stream = gw.submit(_reqs()[0])
        gw.start()
        with pytest.raises(RuntimeError):
            stream.result()
        gw._thread.join(60)
        assert not gw._thread.is_alive() and _mine(gw) == []


# -------------------------------------------------------------- the replay
def test_a_chaos_replay_is_byte_identical_whatever_the_collector_does(model):
    _chaos(model, trace=True)           # recovery-path programs compile here
    outs1, gw1, _ = _chaos(model, trace=True)
    stop = threading.Event()

    def collect_all_the_way():          # full collections, from a thread
        while not stop.is_set():        # that is not the driver's
            gc.collect()
            time.sleep(0.001)
    collector = threading.Thread(target=collect_all_the_way, daemon=True)
    collector.start()
    try:
        outs2, gw2, _ = _chaos(model, trace=True)
    finally:
        stop.set()
        collector.join(30)
    assert not collector.is_alive()
    assert gw2.gc_watch.collections[2] > gw1.gc_watch.collections[2]
    assert outs1 == outs2
    doc1 = json.dumps(gw1.tracer.export(), sort_keys=True)
    assert doc1 == json.dumps(gw2.tracer.export(), sort_keys=True)
    assert '"gc"' not in doc1
    # on a clock that does not move the collector takes no time
    assert set(gw2.gc_watch.pause_s.values()) <= {0.0, 60.0}
