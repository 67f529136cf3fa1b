"""``flops_bytes_gdn``: the delta rule's required work at Olmo-Hybrid-7B's
published widths, against the figures ISSUE 33 reckons, and the readers of
``gdn_trace`` on hand-made traces; every reader the cell adds returns None,
and raises nothing, on a run without its source."""
import json
import os

import flops_bytes
import flops_bytes_gdn
import gdn_trace
import pytest
import run as bench_run

from conftest import BENCH

with open(os.path.join(BENCH, "configs",
                       "olmo-hybrid-7b-serve-16L.json")) as f:
    HYBRID = json.load(f)
with open(os.path.join(BENCH, "peaks.json")) as f:
    V5E = json.load(f)["devices"]["TPU v5 lite"]
NEW = ("gdn_mix_share", "gdn_proj_share", "gdn_update_roofline",
       "gdn_scan_roofline", "hybrid_attn_share")
CELL = "serve-olmohybrid-longout-decode"


def test_a_token_of_a_head_costs_7_dk_dv():
    assert flops_bytes_gdn.token_flops(HYBRID) == 7 * 96 * 192 == 129_024
    assert flops_bytes_gdn.linear_layers(HYBRID) == 12
    assert flops_bytes_gdn.state_bytes(HYBRID) == 30 * 96 * 192 * 4 \
        == 2_211_840                                    # "2.21 MB"


def test_a_decode_step_reads_and_writes_1_7_gb_of_state():
    """32 rows x 12 layers x 4.4 MB: 1.7 GB, 2.1 ms at the HBM peak; the
    FLOPs (1.5 G) are nowhere near a bound."""
    flops, nbytes = flops_bytes_gdn.update_work(HYBRID, 32)
    assert flops == 12 * 32 * 30 * 129_024
    state = 12 * 32 * 2 * 2_211_840
    assert 1.69e9 < state < 1.70e9
    assert nbytes == state + 12 * 32 * 30 * (2 * 96 + 2 * 192) * 4
    least, bound = flops_bytes.least_seconds(flops, nbytes, V5E)
    assert bound == "memory" and 2.0e-3 < least < 2.2e-3


def test_a_chunk_reads_its_state_once():
    """512 tokens of one span: the state once in and once out, whatever the
    length; the tokens' rows are what grows."""
    flops, nbytes = flops_bytes_gdn.recurrence_work(HYBRID, 512, 1)
    assert flops == 12 * 512 * 30 * 129_024
    assert nbytes == 12 * (2 * 2_211_840 + 512 * 30 * 576 * 4)
    assert flops_bytes_gdn.recurrence_work(HYBRID, 0, 0) == (0, 0)


def _op(name, kind="fusion"):
    return f"%{name} = bf16[8,128]{{1,0}} {kind}(bf16[8,128]{{1,0}} %p)"


def test_scope_seconds_by_path_component_and_kernels_by_name():
    ops = [(_op("fusion.1"), 0.0, 1.0),
           (_op("gdn_recurrent_update.2", "custom-call"), 1.0, 3.0),
           (_op("gdn_chunk_scan.3", "custom-call"), 3.0, 3.5),
           (_op("ragged_paged_attention.4", "custom-call"), 3.5, 7.5),
           (_op("fusion.5"), 7.5, 8.0), (_op("while.6", "while"), 0.0, 8.0)]
    devices = {"/device:TPU:0": {"ops": ops}}
    names = {"/device:TPU:0": {
        _op("fusion.1"): "jit(step)/ragged_step/gdn/gdn_proj/dot",
        _op("gdn_recurrent_update.2", "custom-call"):
            "jit(step)/ragged_step/gdn/gdn_mix/pallas_call",
        _op("gdn_chunk_scan.3", "custom-call"):
            "jit(step)/ragged_step/gdn/gdn_mix/pallas_call",
        _op("ragged_paged_attention.4", "custom-call"):
            "jit(step)/ragged_step/attn/pallas_call",
        _op("fusion.5"): "jit(step)/ragged_step/gdn/gdn_mix/conv",
        _op("while.6", "while"): "jit(step)/ragged_step/gdn_mix"}}
    assert gdn_trace.scope_seconds(devices, names) == {
        "gdn_mix": 3.0, "gdn_proj": 1.0, "gdn_recurrent_update": 2.0,
        "gdn_chunk_scan": 0.5, "ragged_paged_attention": 4.0}


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_without_their_source(name):
    """A parent commit, a model without linear layers or an untraced run: no
    scope, no counter, no span. The reader gives None and the line leaves it
    out."""
    reader = bench_run.load_py(os.path.join(BENCH, "metrics", name + ".py"))
    for src in ({}, {"xplane": None, "model": {}},
                {"metrics_delta": {"start": {}, "end": {}}, "model": {}},
                {"xplane": {"busy_s": 1.0, "window_s": 2.0, "mosaic_s": 0.5},
                 "model": {"num_experts": 64}, "peaks": V5E,
                 "span_export": {"traceEvents": []}}):
        assert reader.reduce(dict(src)) is None


def test_rooflines_from_counted_spans(monkeypatch):
    """The update's share counts the one-token spans only; the scan's its
    own tokens and spans."""
    secs = {"gdn_mix": 1.0, "gdn_proj": 1.0, "gdn_recurrent_update": 0.01,
            "gdn_chunk_scan": 0.004, "ragged_paged_attention": 0.002}
    args = [{"state_rows": 32, "scan_spans": 0, "scan_tokens": 0},
            {"state_rows": 31, "scan_spans": 1, "scan_tokens": 512}]
    monkeypatch.setattr(gdn_trace, "of", lambda src: secs)
    monkeypatch.setattr(gdn_trace, "traced_dispatch_args", lambda src: args)
    src = {"model": HYBRID, "peaks": V5E}

    def read(name):
        return bench_run.load_py(os.path.join(
            BENCH, "metrics", name + ".py")).reduce(src)

    _, nbytes = flops_bytes_gdn.update_work(HYBRID, 62)
    want = 100.0 * nbytes / V5E["hbm_bytes_per_s"] / 0.01
    assert abs(read("gdn_update_roofline") - want) < 1e-9 and 0 < want < 100
    _, nbytes = flops_bytes_gdn.recurrence_work(HYBRID, 512, 1)
    want = 100.0 * nbytes / V5E["hbm_bytes_per_s"] / 0.004
    assert abs(read("gdn_scan_roofline") - want) < 1e-9 and 0 < want < 100


def test_benchmark_lists_the_new_cell_where_the_issue_says():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) | {"chunk_step_share", "ragged_grid_live_share"} <= listed
    assert not any(n.startswith(("moe_", "mla_")) for n in listed)
    assert not listed & {"attn_kernel_share", "ragged_attn_roofline",
                         "ragged_attn_roofline_counted"}
    gap = next(m for m in bench["end_to_end"] if m["name"] == "gap_p50_ms")
    assert CELL in gap["workloads"]
    cfg = next(c for c in bench["configs"]
               if c["name"] == "olmo-hybrid-7b-serve-16L")
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "max_position_embeddings"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    with open(os.path.join(BENCH, "traffic",
                           "longout-decode-closed.json")) as f:
        mix = json.load(f)
    assert (mix["clients"], mix["ramp_s"], mix["max_requests"]) \
        == (32, 20, 4096)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 520,
                                    "max": 1000}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 0.5, "min": 256, "max": 1280}
