"""The reducer against hand-made intervals and against the trace recorded
on one TPU v5e (``data/small_v5e.xplane.pb``, see ``data/record_trace.py``:
three runs of one program, each about 0.22 ms of device work, with a 20 ms
host sleep after each)."""
import os

import pytest
import xplane_reduce as xr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_v5e.xplane.pb")


def test_busy_union_with_overlapping_events():
    assert xr.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == 4
    assert xr.merge([(1, 3), (0, 2), (3, 4)]) == [(0, 4)]
    assert xr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert xr.gaps([(1, 2), (4, 5)], (0, 6)) == [(0, 1), (2, 4), (5, 6)]


def test_op_names_and_kinds():
    t = ('%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]'
         '{1,0} %p), kind=kLoop, calls=%fc')
    assert xr.short_name(t) == "fusion.3" and xr.op_kind(t) == "fusion"
    t = ('%all-reduce-start.1 = (f32[4]{0}, f32[4]{0}) all-reduce-start('
         'f32[4]{0} %x), replica_groups={}')
    assert xr.op_kind(t) == "all-reduce-start"
    assert xr.is_collective(xr.op_kind(t))
    assert xr.op_kind("%while.2 = (s32[], f32[2]{0}) while((s32[], f32[2]"
                      "{0}) %t), condition=%c, body=%b") == "while"
    assert not xr.is_collective("fusion")


def test_idle_share_and_exposed_collective_time():
    # one chip, window 0..10: compute 0-4, a collective in flight 3-6 (one
    # second hidden under compute, two exposed), compute 6-8, idle 8-10
    # closed by a last op 10-10.5 so that the window ends there
    ops = [("%fusion.1 = f32[1]{0} fusion(f32[1]{0} %a)", 0.0, 4.0),
           ("%all-reduce.1 = f32[1]{0} all-reduce(f32[1]{0} %b)", 3.0, 6.0),
           ("%fusion.2 = f32[1]{0} fusion(f32[1]{0} %c)", 6.0, 8.0),
           ("%while.1 = (f32[1]{0}) while((f32[1]{0}) %t)", 0.0, 10.5),
           ("%fusion.3 = f32[1]{0} fusion(f32[1]{0} %d)", 10.0, 10.5)]
    host = [("data_fetch", 8.1, 9.9), ("other", 0.0, 20.0)]
    s = xr.summarize({"/device:TPU:0": {"ops": ops, "async": []}}, host,
                     annotations=("data_fetch",))
    assert s["window_s"] == 10.5
    assert abs(s["busy_s"] - 8.5) < 1e-9      # the while container is skipped
    assert abs(s["idle_share"] - 2.0 / 10.5) < 1e-9
    assert abs(s["collective_s"] - 3.0) < 1e-9
    assert abs(s["collective_exposed_s"] - 2.0) < 1e-9
    assert s["gaps"][0] == ["data_fetch", 2.0]
    assert s["top_ops"][0] == ["fusion.1", 4.0]
    assert s["annotations"]["data_fetch"]["count"] == 1
    assert abs(s["annotations"]["data_fetch"]["seconds"] - 1.8) < 1e-9


def test_async_collective_spans_count_as_in_flight():
    ops = [("%all-gather-start.1 = (f32[1]{0}) all-gather-start(f32[1]{0} "
            "%a)", 0.0, 0.1),
           ("%fusion.1 = f32[1]{0} fusion(f32[1]{0} %b)", 0.1, 1.0),
           ("%all-gather-done.1 = f32[1]{0} all-gather-done((f32[1]{0}) "
            "%s)", 1.0, 3.0)]
    asyncs = [("%all-gather-start.1 = (f32[1]{0}) all-gather-start(f32[1]"
               "{0} %a)", 0.0, 3.0)]
    s = xr.summarize({"/device:TPU:0": {"ops": ops, "async": asyncs}})
    assert abs(s["collective_s"] - 3.0) < 1e-9
    assert abs(s["collective_exposed_s"] - 2.1) < 1e-9   # all but 0.1-1.0


def test_mean_over_devices():
    a = [("%fusion.1 = f32[1]{0} fusion(f32[1]{0} %a)", 0.0, 10.0)]
    b = [("%fusion.1 = f32[1]{0} fusion(f32[1]{0} %a)", 0.0, 5.0)]
    s = xr.summarize({"/device:TPU:0": {"ops": a, "async": []},
                      "/device:TPU:1": {"ops": b, "async": []}})
    assert s["devices"] == 2 and s["busy_s"] == 7.5
    assert abs(s["idle_share"] - 0.25) < 1e-9


def test_no_device_events_is_nothing():
    assert xr.summarize({}) is None
    assert xr.reduce_dir("/nonexistent") is None


def test_recorded_v5e_trace():
    pytest.importorskip("jax")
    devices, host = xr.read_xplane(DATA)
    assert list(devices) == ["/device:TPU:0"]
    s = xr.summarize(devices, host, ("probe_step", "probe_sleep"))
    # three runs of about 0.22 ms inside a window of two 22 ms sleeps
    assert 0.040 < s["window_s"] < 0.050
    assert 0.0006 < s["busy_s"] < 0.0007
    assert 0.98 < s["idle_share"] < 0.99
    # the flash forward kernel ran three times, 28.7 us each
    (sig, rec), = s["mosaic_calls"].items()
    assert sig == ("bf16[8,1024,128]|f32[8,1024,1] <- bf16[8,1024,128],"
                   "bf16[8,1024,128],bf16[8,1024,128]")
    assert rec["count"] == 3 and 80e-6 < rec["seconds"] < 90e-6
    assert s["collective_s"] == 0.0
    # the two long gaps are the host's sleeps
    assert [g[0] for g in s["gaps"][:2]] == ["probe_sleep", "probe_sleep"]
    assert all(0.0215 < g[1] < 0.0225 for g in s["gaps"][:2])
    assert s["annotations"]["probe_step"]["count"] == 3


def test_a_gap_is_named_by_the_innermost_span_that_covers_it():
    """The serving children pass the engine's and the gateway's span names
    (``timeline.SPAN_NAMES``), which nest: ``step`` > ``launch``
    > ``dispatch``. A gap wholly under all three is the leaf's; one that
    runs across two leaves is the enclosing span's; one under none keeps
    ``host, unattributed`` (an empty server between requests)."""
    import timeline
    names = timeline.SPAN_NAMES

    def op(n, s, e):
        return (f"%fusion.{n} = f32[1]{{0}} fusion(f32[1]{{0}} %a)", s, e)

    ops = [op(1, 0.0, 1.0), op(2, 3.0, 4.0), op(3, 4.5, 5.0),
           op(4, 10.0, 10.5)]
    host = [("step", 0.5, 5.5), ("launch", 0.8, 4.2),
            ("dispatch", 0.9, 3.2), ("device-wait", 3.9, 4.2),
            ("host-accept", 4.2, 4.8), ("sleep", 0.0, 11.0)]
    s = xr.summarize({"/device:TPU:0": {"ops": ops, "async": []}}, host,
                     annotations=names)
    assert s["gaps"] == [["host, unattributed", 5.0], ["dispatch", 2.0],
                         ["step", 0.5]]
    bare = xr.summarize({"/device:TPU:0": {"ops": ops, "async": []}}, host)
    assert [g[1] for g in bare["gaps"]] == [g[1] for g in s["gaps"]]
    assert {g[0] for g in bare["gaps"]} == {"host, unattributed"}


def test_span_names_change_the_labels_of_the_recorded_trace_and_no_number():
    pytest.importorskip("jax")
    devices, host = xr.read_xplane(DATA)
    bare = xr.summarize(devices, host)
    named = xr.summarize(devices, host, ("probe_step", "probe_sleep"))
    for key in ("window_s", "busy_s", "idle_share", "mosaic_s",
                "mosaic_calls", "collective_s", "collective_exposed_s",
                "top_ops", "devices"):
        assert bare[key] == named[key], key
    assert [g[1] for g in bare["gaps"]] == [g[1] for g in named["gaps"]]
    assert {g[0] for g in bare["gaps"]} == {"host, unattributed"}
    assert named["gaps"][0][0] == "probe_sleep"
