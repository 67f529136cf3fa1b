"""``stall_trace.py`` and the ten metric files that read it (PR 52): on
hand-made host annotations, device gaps and scrapes. A program without the
families or the spans (a parent commit) gives None for every one of them, and
nothing raises; a program that has them gives a number, 0.0 included."""
import importlib.util
import json
import os

import pytest

from conftest import BENCH, ROOT

import stall_trace
import timeline

COUNTERS = ("sweep_ms_per_step", "retire_ms_per_step",
            "host_long_visit_ms_per_step", "gc_pause_ms_per_step",
            "gc_full_pause_mean_ms")
TRACED = ("idle_in_gc_ms_per_step", "idle_in_sweep_ms_per_step",
          "idle_in_retire_ms_per_step", "idle_in_call_ms_per_step",
          "long_gap_unnamed_ms_per_step")
PHASES = ("loop", "idle-wait", "sweep", "admit", "plan", "dispatch",
          "device-wait", "host-accept", "retire", "other")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def op(name, s, e):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop", s, e)


# --------------------------------------------------------------- the scrapes
def scrape(steps, wall=None, long_s=None, gc=None):
    """One scrape as ``kinds/serve.parse_prometheus`` gives it. ``wall`` and
    ``long_s``: seconds by phase (None: a parent's phases, no long visits);
    ``gc``: {generation: (seconds, collections)} (None: no such family)."""
    s = {"serving_step_duration_seconds_count": {"": float(steps)}}
    phases = PHASES if wall is not None else \
        tuple(p for p in PHASES if p not in ("sweep", "retire"))
    s["serving_driver_seconds_total"] = {
        '{clock="%s",phase="%s"}' % (c, p): float((wall or {}).get(p, 0.0))
        for c in ("wall", "cpu") for p in phases}
    if long_s is not None:
        s[stall_trace.LONG_VISIT_S] = {
            '{phase="%s"}' % p: float(long_s.get(p, 0.0)) for p in PHASES}
    if gc is not None:
        s[stall_trace.GC_PAUSE_S] = {
            '{generation="%d"}' % g: float(gc.get(g, (0, 0))[0])
            for g in range(3)}
        s[stall_trace.GC_COLLECTIONS] = {
            '{generation="%d"}' % g: float(gc.get(g, (0, 0))[1])
            for g in range(3)}
    return s


def src_of(start, end):
    return {"metrics_delta": {"start": start, "end": end,
                              "scrapes": [(0.0, start), (40.0, end)]}}


def test_counters_read_the_hand_computed_values():
    start = scrape(100, wall={"sweep": 1.0, "retire": 2.0},
                   long_s={"dispatch": 0.5, "device-wait": 9.0},
                   gc={0: (0.01, 50), 2: (0.2, 2)})
    end = scrape(500, wall={"sweep": 1.2, "retire": 3.0},
                 long_s={"dispatch": 0.9, "sweep": 0.1, "device-wait": 19.0,
                         "idle-wait": 5.0},
                 gc={0: (0.03, 90), 1: (0.02, 4), 2: (0.5, 5)})
    src = src_of(start, end)
    assert reader("sweep_ms_per_step")(src) == pytest.approx(1e3 * 0.2 / 400)
    assert reader("retire_ms_per_step")(src) == pytest.approx(1e3 * 1.0 / 400)
    # every phase but the two the host waits in
    assert reader("host_long_visit_ms_per_step")(src) \
        == pytest.approx(1e3 * (0.4 + 0.1) / 400)
    assert reader("gc_pause_ms_per_step")(src) \
        == pytest.approx(1e3 * (0.02 + 0.02 + 0.3) / 400)
    assert reader("gc_full_pause_mean_ms")(src) \
        == pytest.approx(1e3 * 0.3 / 3)


def test_a_window_without_a_full_collection_reads_zero():
    gc = {0: (0.01, 50), 2: (0.2, 2)}
    src = src_of(scrape(100, wall={}, long_s={}, gc=gc),
                 scrape(500, wall={}, long_s={},
                        gc={**gc, 0: (0.02, 80)}))
    assert reader("gc_full_pause_mean_ms")(src) == 0.0
    assert reader("gc_pause_ms_per_step")(src) \
        == pytest.approx(1e3 * 0.01 / 400)
    assert reader("host_long_visit_ms_per_step")(src) == 0.0
    assert reader("sweep_ms_per_step")(src) == 0.0


@pytest.mark.parametrize("name", COUNTERS + TRACED)
def test_a_parents_scrape_gives_none_and_nothing_raises(name):
    reduce = reader(name)
    assert reduce(src_of(scrape(100), scrape(500))) is None
    assert reduce({}) is None                       # a training run
    assert reduce({"metrics_delta": None}) is None
    # no step in the window: nothing to divide by
    both = scrape(100, wall={}, long_s={}, gc={})
    assert reduce(src_of(both, both)) in (None, 0.0)


# ----------------------------------------------------------------- the trace
def test_lay_out_names_the_long_gaps():
    # busy 0-10, 20-30, 34.9-40, 45-50, 56-60 (ms): gaps of 10, 4.9, 5, 6
    devices = {"/device:TPU:0": {"ops": [
        op("a", 0.000, 0.010), op("b", 0.020, 0.030), op("c", 0.0349, 0.040),
        op("d", 0.045, 0.050), op("e", 0.056, 0.060),
        ("%w = (f32[8]{0}) while((f32[8]{0}) %t), body=%b", 0.0, 0.060)],
        "async": []}}
    host = [
        # the first gap: half under ``gc`` and all of it under ``dispatch``,
        # of which the first 2 ms are the ``call``
        ("step", 0.009, 0.021), ("dispatch", 0.010, 0.020),
        ("call", 0.010, 0.012), ("gc", 0.015, 0.020),
        # the second (4.9 ms) and third (5 ms) gaps: under ``step`` alone
        ("step", 0.029, 0.046),
        # the fourth: 1 ms under ``sweep``, 2 ms under ``retire``
        ("sweep", 0.050, 0.051), ("retire", 0.053, 0.055),
        ("unrelated", 0.0, 0.06)]
    st = stall_trace.lay_out(devices, host)
    by = st["idle_by_span_s"]
    assert by["gc"] == pytest.approx(0.005)
    assert by["dispatch"] == pytest.approx(0.010)
    assert by["call"] == pytest.approx(0.002)
    assert by["sweep"] == pytest.approx(0.001)
    assert by["retire"] == pytest.approx(0.002)
    assert by["admit"] == by["loop"] == 0.0
    assert st["seen"] == {"dispatch", "call", "gc", "sweep", "retire"}
    # the 4.9 ms gap is no stall; the 5 ms one is, all of it unnamed; of
    # the 6 ms one, what neither ``sweep`` nor ``retire`` covers
    assert st["unnamed_long_s"] == pytest.approx(0.005 + 0.003)
    assert [round(g["ms"], 6) for g in st["long_gaps"]] == [10.0, 6.0, 5.0]
    first = st["long_gaps"][0]
    assert first["unnamed_ms"] == pytest.approx(0.0)
    assert first["under_ms"] == pytest.approx(
        {"dispatch": 10.0, "call": 2.0, "gc": 5.0, "step": 10.0})
    # the enclosing ``step`` names nothing; the list places the gap by it
    assert st["long_gaps"][2]["under_ms"] == pytest.approx({"step": 5.0})
    assert st["long_gaps"][2]["unnamed_ms"] == pytest.approx(5.0)
    assert stall_trace.lay_out({}, []) is None


def test_lay_out_is_a_mean_over_the_chips():
    ops = [op("a", 0.0, 0.010), op("b", 0.020, 0.030)]
    devices = {"/device:TPU:0": {"ops": ops, "async": []},
               "/device:TPU:1": {"ops": [op("a", 0.0, 0.030)], "async": []}}
    st = stall_trace.lay_out(devices, [("retire", 0.010, 0.014)])
    assert st["idle_by_span_s"]["retire"] == pytest.approx(0.002)
    assert st["unnamed_long_s"] == pytest.approx(0.003)


def traced_src(monkeypatch, host, gc_family=True, steps=2):
    devices = {"/device:TPU:0": {"ops": [op("a", 0.0, 0.010),
                                         op("b", 0.020, 0.030)],
                                 "async": []}}
    monkeypatch.setattr(timeline, "_find_trace",
                        lambda x: ("path", devices, host))
    end = scrape(500, wall={}, long_s={}, gc={} if gc_family else None)
    src = src_of(scrape(100, wall={}, long_s={},
                        gc={} if gc_family else None), end)
    src["xplane"] = {"window_s": 0.03, "busy_s": 0.02}
    src["timeline"] = {"steps": [(i, 0.0, 0.01) for i in range(steps)]}
    return src


def test_traced_readers_per_step(monkeypatch, capsys):
    host = [("sweep", 0.010, 0.011), ("dispatch", 0.011, 0.016),
            ("call", 0.011, 0.015), ("retire", 0.018, 0.020)]
    src = traced_src(monkeypatch, host)
    assert reader("idle_in_sweep_ms_per_step")(src) == pytest.approx(0.5)
    assert reader("idle_in_call_ms_per_step")(src) == pytest.approx(2.0)
    assert reader("idle_in_retire_ms_per_step")(src) == pytest.approx(1.0)
    # the program counts collections and the traced seconds held none
    assert reader("idle_in_gc_ms_per_step")(src) == 0.0
    # 10 ms gap, 8 of them named (``call`` lies inside ``dispatch``)
    assert reader("long_gap_unnamed_ms_per_step")(src) == pytest.approx(1.0)
    notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    (note,) = [n for n in notes if n["event"] == "stalls"]    # built once
    assert note["steps_in_trace"] == 2
    assert note["long_gaps"][0]["ms"] == pytest.approx(10.0)


@pytest.mark.parametrize("name", TRACED)
def test_a_parents_trace_gives_none(monkeypatch, name):
    host = [("step", 0.009, 0.021), ("dispatch", 0.011, 0.016),
            ("device-wait", 0.016, 0.019), ("loop", 0.021, 0.022)]
    src = traced_src(monkeypatch, host, gc_family=False)
    assert reader(name)(src) is None


@pytest.mark.parametrize("name", TRACED)
def test_no_trace_or_no_step_in_it_gives_none(monkeypatch, name):
    host = [("sweep", 0.010, 0.011), ("call", 0.011, 0.015),
            ("retire", 0.018, 0.020)]
    assert reader(name)(traced_src(monkeypatch, host, steps=0)) is None
    src = traced_src(monkeypatch, host)
    monkeypatch.setattr(timeline, "_find_trace", lambda x: None)
    assert reader(name)(src) is None            # not this run's trace
    assert reader(name)(dict(src, xplane=None)) is None     # ``--trace 0``


# ------------------------------------------------------------ BENCHMARK.json
def test_the_ten_entries_are_the_last_and_list_the_nine_serving_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    serving = [w["name"] for w in bench["workloads"]
               if w["name"].startswith("serve-")]
    assert len(serving) == 9
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in COUNTERS + TRACED}
    for name in COUNTERS + TRACED:
        m = entries[name]
        assert m["workloads"] == serving
        assert (m["unit"], m["better"], m["moves"]) \
            == ("ms", "lower", "gap_p50_ms")
        assert m["source"] == ("program_counter" if name in COUNTERS
                               else "device_trace")
        assert m["layer"] in layers             # a layer PERF.md has
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
