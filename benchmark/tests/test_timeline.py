"""``timeline.py`` and the metric files that read it (PR 24): on hand-made
intervals, on a hand-made ``src``, and on the recorded v5e trace beside this
file. A reader whose source is absent returns None and does not raise."""
import importlib.util
import os
import shutil

import pytest

from conftest import BENCH

import proc
import timeline
import xplane_reduce

TRACE = os.path.join(BENCH, "tests", "data", "small_v5e.xplane.pb")
NEW_METRICS = (
    "idle_attributed_share", "idle_in_plan_ms_per_step",
    "idle_in_dispatch_ms_per_step", "idle_in_accept_ms_per_step",
    "idle_in_loop_ms_per_step", "device_wait_ms_per_step",
    "decode_tokens_per_step", "prefill_tokens_per_step",
    "ragged_grid_live_share", "ragged_attn_roofline_counted",
    "train_fwd_share", "train_remat_share", "train_bwd_share",
    "train_opt_share")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    """A run directory holding the recorded trace as one cell's."""
    monkeypatch.setattr(proc, "RUN_DIR", str(tmp_path))
    d = tmp_path / "some-cell" / "xplane" / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    shutil.copy(TRACE, d / "host.xplane.pb")
    return tmp_path


def recorded_summary():
    devices, host = xplane_reduce.read_xplane(TRACE)
    return xplane_reduce.summarize(devices, host)


# ----------------------------------------------------------- the arithmetic
def op(name, s, e):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop", s, e)


def test_lay_out_attributes_idle_to_the_span_above_it():
    # busy 0-10, 14-20, 26-30 (ms): gaps 10-14 and 20-26
    devices = {"/device:TPU:0": {"ops": [
        op("a", 0.000, 0.010), op("b", 0.014, 0.020), op("c", 0.026, 0.030),
        # a while spanning everything is a container, not busy time
        ("%w = (f32[8]{0}) while((f32[8]{0}) %t), body=%b", 0.0, 0.030)],
        "async": []}}
    host = [("step", 0.001, 0.0125), ("device-wait", 0.002, 0.0105),
            ("host-accept", 0.0105, 0.0125), ("loop", 0.0125, 0.013),
            ("step", 0.013, 0.0235), ("plan", 0.013, 0.0135),
            ("dispatch", 0.0135, 0.0145), ("host-accept", 0.0205, 0.023),
            ("unrelated", 0.0, 0.03)]
    extras = {"steps": [(7, 0.001, 0.0125), (8, 0.013, 0.0235),
                        (9, 0.0299, 0.05)],
              "op_names": {"/device:TPU:0": {
                  op("a", 0, 0)[0]: "jit(step_fn)/jvp()/while/body/mul",
                  op("b", 0, 0)[0]:
                      "jit(step_fn)/transpose(jvp())/checkpoint/"
                      "rematted_computation/mul",
                  op("c", 0, 0)[0]: "jit(step_fn)/optimizer/add"}}}
    tl = timeline.lay_out(devices, host, extras)
    assert tl["window"] == (0.0, 0.030)
    assert tl["idle_s"] == pytest.approx(0.010)
    by = tl["idle_by_span_s"]
    assert by["device-wait"] == pytest.approx(0.0005)   # 10.0-10.5
    assert by["host-accept"] == pytest.approx(0.002 + 0.0025)
    assert by["loop"] == pytest.approx(0.0005)
    assert by["plan"] == pytest.approx(0.0005)
    assert by["dispatch"] == pytest.approx(0.0005)      # 13.5-14.0
    assert by["admit"] == 0.0
    # under some span: 10-14 whole, 20-23.5 of the second gap
    assert tl["idle_named_s"] == pytest.approx(0.004 + 0.0035)
    assert [n for n, _, _ in tl["steps"]] == [7, 8]     # by their midpoints
    assert tl["phase_s"] == pytest.approx(
        {"fwd": 0.010, "remat": 0.006, "bwd": 0.0, "opt": 0.004,
         "other": 0.0})


def test_lay_out_without_spans_or_names_reports_neither():
    devices = {"/device:TPU:0": {"ops": [op("a", 0.0, 0.01),
                                         op("b", 0.02, 0.03)], "async": []}}
    tl = timeline.lay_out(devices, [("train_step", 0.0, 0.03)],
                          {"steps": [], "op_names": {}})
    assert tl["idle_s"] == pytest.approx(0.01)
    assert tl["idle_by_span_s"] == {} and tl["phase_s"] == {}
    assert timeline.lay_out({}, [], {"steps": [], "op_names": {}}) is None


@pytest.mark.parametrize("name,phase", [
    ("jit(step_fn)/jvp()/while/body/closed_call/mlp/dot_general", "fwd"),
    ("jit(step_fn)/jvp(loss)/reduce_sum", "fwd"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", "remat"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "mlp/transpose", "bwd"),
    ("jit(step_fn)/transpose(jvp(loss))/mul", "bwd"),
    ("jit(step_fn)/optimizer/sqrt", "opt"),
    ("jit(step_fn)/jit(tril)/iota", "other"), (None, "other")])
def test_phase_of(name, phase):
    assert timeline.phase_of(name) == phase


# ------------------------------------------------------ the recorded trace
def test_read_extras_finds_step_numbers_and_op_names():
    ex = timeline.read_extras(TRACE, step_name="probe_step")
    _, host = xplane_reduce.read_xplane(TRACE)
    twins = sorted((s, e) for n, s, e in host if n == "probe_step")
    assert [n for n, _, _ in ex["steps"]] == [0, 1, 2]
    for (_, s, e), (hs, he) in zip(ex["steps"], twins):
        assert s == pytest.approx(hs, abs=1e-9)
        assert e == pytest.approx(he, abs=1e-9)
    ops = ex["op_names"]["/device:TPU:0"]
    assert any(v.startswith("jit(program)/probe_matmuls/dot_general")
               for v in ops.values())
    assert any(k.startswith("%fusion = ") for k in ops)
    assert timeline.read_extras(TRACE)["steps"] == []


def test_of_takes_this_runs_trace_and_refuses_another(run_dir):
    good = recorded_summary()
    tl = timeline.of({"xplane": good})
    assert tl is not None and tl["idle_s"] > 0
    assert tl["idle_by_span_s"] == {}          # no engine span in it
    assert tl["idle_s"] == pytest.approx(
        good["idle_share"] * good["window_s"], rel=1e-6)
    other = dict(good, busy_s=good["busy_s"] * 1.01)
    assert timeline.of({"xplane": other}) is None
    assert timeline.of({"xplane": None}) is None
    assert timeline.of({}) is None


def test_kernels_are_found_by_name(run_dir):
    # the recorded program predates the names: its flash call is
    # ``%program.1``; under its new name the same op is counted
    devices, host = xplane_reduce.read_xplane(TRACE)
    renamed = {p: {"ops": [(t.replace("%program.1 = ", "%flash_fwd.1 = "),
                            s, e) for t, s, e in d["ops"]],
                   "async": d["async"]} for p, d in devices.items()}
    tl = timeline.lay_out(renamed, host, {"steps": [], "op_names": {}})
    by_sig = sum(r["seconds"] for r in
                 xplane_reduce.summarize(devices)["mosaic_calls"].values())
    assert tl["kernels"]["flash_fwd"]["count"] == 3
    assert tl["kernels"]["flash_fwd"]["seconds"] == pytest.approx(by_sig)


# ------------------------------------------------------------ metric files
@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_reader_returns_none_without_its_source(name, run_dir):
    assert reader(name)({}) is None
    # a parent's run: a trace with no span, no scope, no counter, no args
    src = {"xplane": recorded_summary(), "seconds": 40.0,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "model": {"num_hidden_layers": 2, "hidden_size": 64,
                     "num_key_value_heads": 2, "num_attention_heads": 4},
           "metrics_delta": {
               "start": {"serving_step_duration_seconds_count": {"": 0.0}},
               "end": {"serving_step_duration_seconds_count": {"": 4.0}},
               "scrapes": []},
           "span_export": {"traceEvents": [
               {"name": "step", "ph": "X", "ts": 10.0 * i, "dur": 9.0,
                "pid": 1, "tid": 1, "args": {"step": i, "tokens": 5}}
               for i in range(4)]}}
    assert reader(name)(src) is None


def hand_made_src():
    def step(i, ts):
        return [
            {"name": "step", "ph": "X", "ts": ts, "dur": 100.0, "pid": 1,
             "tid": 1, "args": {"step": i, "tokens": 6, "chunks": False}},
            {"name": "dispatch", "ph": "X", "ts": ts + 10, "dur": 5.0,
             "pid": 1, "tid": 1, "args": {
                 "grid_steps": 1000, "live_steps": 10 * (i + 1),
                 "kv_tokens": 100 * (i + 1), "attn_pairs": 100 * (i + 1),
                 "decode_rows": 6, "decode_tokens": 6, "prefill_tokens": 0}},
            {"name": "device-wait", "ph": "X", "ts": ts + 15, "dur": 80.0,
             "pid": 1, "tid": 1}]
    events = [e for i in range(4) for e in step(i, 200.0 * i)]
    return {
        "span_export": {"traceEvents": events},
        "metrics_delta": {
            "start": {"serving_step_duration_seconds_count": {"": 10.0},
                      "serving_step_tokens_total": {
                          '{kind="decode"}': 50.0, '{kind="prefill"}': 500.0}},
            "end": {"serving_step_duration_seconds_count": {"": 12.0},
                    "serving_step_tokens_total": {
                        '{kind="decode"}': 62.0, '{kind="prefill"}': 1524.0}},
            "scrapes": []},
        "model": {"num_hidden_layers": 2, "hidden_size": 64,
                  "num_key_value_heads": 2, "num_attention_heads": 4},
        "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        "seconds": 40.0}


def test_program_span_and_counter_readers_on_a_hand_made_src():
    src = hand_made_src()
    # the window is the last two steps (delta of the step histogram's count)
    assert reader("device_wait_ms_per_step")(src) == pytest.approx(0.08)
    assert reader("decode_tokens_per_step")(src) == pytest.approx(6.0)
    assert reader("prefill_tokens_per_step")(src) == pytest.approx(512.0)
    assert reader("ragged_grid_live_share")(src) \
        == pytest.approx(100.0 * (30 + 40) / 2000)
    assert [a["live_steps"] for a in timeline.dispatch_args(src, {0, 1})] \
        == [10, 20]
    assert timeline.dispatch_args(src, {17}) is None


def test_device_trace_readers_on_a_hand_made_timeline(monkeypatch):
    src = hand_made_src()
    src["xplane"] = {"mosaic_s": 0.001, "window_s": 0.03, "busy_s": 0.02}
    tl = {"idle_s": 0.010, "idle_named_s": 0.0095,
          "idle_by_span_s": {"admit": 0.001, "plan": 0.002,
                             "dispatch": 0.003, "device-wait": 0.0005,
                             "host-accept": 0.001, "loop": 0.002},
          "steps": [(1, 0.0, 0.1), (2, 0.1, 0.2)], "kernels": {},
          "phase_s": {"fwd": 2.0, "remat": 2.0, "bwd": 4.0, "opt": 1.0,
                      "other": 1.0}}
    monkeypatch.setattr(timeline, "of", lambda s: tl)
    assert reader("idle_attributed_share")(src) == pytest.approx(95.0)
    assert reader("idle_in_plan_ms_per_step")(src) == pytest.approx(1.5)
    assert reader("idle_in_dispatch_ms_per_step")(src) == pytest.approx(1.5)
    assert reader("idle_in_accept_ms_per_step")(src) == pytest.approx(0.5)
    assert reader("idle_in_loop_ms_per_step")(src) == pytest.approx(1.0)
    shares = [reader(f"train_{p}_share")(src)
              for p in ("fwd", "remat", "bwd", "opt")]
    assert shares == pytest.approx([20.0, 20.0, 40.0, 10.0])
    assert sum(shares) + timeline.phase_share(src, "other") \
        == pytest.approx(100.0)
    # steps 1 and 2: kv_tokens 200 + 300, as many pairs; memory-bound at
    # these peaks: 2 layers x (2 x 2 heads x 16 x 2 bytes) x 500 rows
    least = 2 * (2 * 2 * 16 * 2) * 500 / 1e9
    assert reader("ragged_attn_roofline_counted")(src) \
        == pytest.approx(100.0 * least / 0.001)
