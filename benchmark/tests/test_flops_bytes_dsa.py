"""``flops_bytes_dsa``: the indexer's and the selected attention's required
work at GLM-5.2's published widths, against the figures ISSUE 43 reckons, and
the readers of ``dsa_trace`` on hand-made traces and spans; every reader the
cell adds returns None, and raises nothing, on a run without its source."""
import json
import os

import dsa_trace
import flops_bytes
import flops_bytes_dsa
import pytest
import run as bench_run

from conftest import BENCH, ROOT

with open(os.path.join(BENCH, "configs", "glm-5.2-serve-6L-ep16.json")) as f:
    GLM = json.load(f)
with open(os.path.join(BENCH, "peaks.json")) as f:
    V5E = json.load(f)["devices"]["TPU v5 lite"]
CELL = "serve-glm52-sparse-longctx-decode"
NEW = ("dsa_index_share", "dsa_select_share", "dsa_attend_share",
       "dsa_index_roofline", "dsa_attend_roofline",
       "dsa_rows_read_over_selected", "dsa_selected_share")


def test_a_decode_step_of_16_rows_at_12k():
    """Two indexers over 16 x 12k keys: 98 MB of index keys, bound by their
    bytes; six layers over 16 x 2,048 selected rows: 226 MB of latent rows
    (252 MB at the padded 640 lanes, which are not required work)."""
    ctx = 16 * 12_288
    flops, nbytes = flops_bytes_dsa.index_work(
        GLM, index_query_rows=2 * 16, index_key_rows=2 * ctx, kv_tokens=ctx)
    assert flops_bytes_dsa.indexer_layers(GLM) == 2
    assert flops == 2 * 128 * 32 * 2 * ctx
    assert nbytes == (2 * ctx * 128 + 32 * 32 * 128) * 2
    assert 1.00e8 < nbytes < 1.01e8
    least, bound = flops_bytes.least_seconds(flops, nbytes, V5E)
    assert bound == "memory" and 1.2e-4 < least < 1.3e-4
    flops, nbytes = flops_bytes_dsa.attention_work(
        GLM, selected_rows=6 * 16 * 2048, query_rows=6 * 16)
    assert flops == 6 * 16 * 2048 * 64 * 2176
    assert nbytes == (6 * 16 * 2048 * 576 + 96 * 64 * 1088) * 2
    assert 2.3e8 < nbytes < 2.5e8
    least, bound = flops_bytes.least_seconds(flops, nbytes, V5E)
    assert bound == "memory" and 2.8e-4 < least < 3.0e-4


def test_a_chunk_at_a_12k_prefix_is_bound_by_flops():
    """512 queries over 12k keys: 50 GFLOP an indexer; 0.15 TFLOP of
    attention a layer over the selected rows."""
    pairs = 512 * 12_000 + 512 * 513 // 2
    flops, nbytes = flops_bytes_dsa.index_work(
        GLM, index_query_rows=512, index_key_rows=pairs, kv_tokens=12_512)
    assert 5.0e10 < flops / 1 < 5.3e10 * 2 and flops == 2 * 128 * 32 * pairs
    assert flops_bytes.least_seconds(flops, nbytes, V5E)[1] == "compute"
    flops, _ = flops_bytes_dsa.attention_work(GLM, 512 * 2048, 512)
    assert 1.4e11 < flops < 1.5e11


def _op(name, kind="fusion"):
    return f"%{name} = bf16[8,128]{{1,0}} {kind}(bf16[8,128]{{1,0}} %p)"


def test_scope_seconds_by_path_component_and_kernels_by_name():
    devices = {"/device:TPU:0": {"ops": [
        (_op("fusion.1"), 0.0, 1.0),
        (_op("dsa_index_scores.2", "custom-call"), 1.0, 3.0),
        (_op("fusion.3"), 3.0, 3.5),
        (_op("dsa_attention.4", "custom-call"), 3.5, 7.5),
        (_op("fusion.5"), 7.5, 8.0), (_op("while.6", "while"), 0.0, 8.0)]}}
    names = {"/device:TPU:0": {
        _op("fusion.1"): "jit(step)/attn/mla/dsa_index_proj/dot",
        _op("dsa_index_scores.2", "custom-call"):
            "jit(step)/attn/mla/cond/dsa_index_score/pallas_call",
        _op("fusion.3"): "jit(step)/attn/mla/cond/dsa_select/while",
        _op("dsa_attention.4", "custom-call"):
            "jit(step)/attn/mla/dsa_attend/pallas_call",
        _op("fusion.5"): "jit(step)/attn/mla/mla_proj/wo",
        _op("while.6", "while"): "jit(step)/dsa_attend"}}
    assert dsa_trace.scope_seconds(devices, names) == {
        "dsa_index_proj": 1.0, "dsa_index_score": 2.0, "dsa_select": 0.5,
        "dsa_attend": 4.0, "dsa_index_scores": 2.0, "dsa_attention": 4.0}


def test_counts_from_the_dispatch_spans():
    def step(n, ts):
        return {"name": "step", "ph": "X", "ts": ts, "dur": 9.0,
                "args": {"step": n}}

    def dispatch(ts, **kw):
        return {"name": "dispatch", "ph": "X", "ts": ts, "dur": 1.0,
                "args": {"grid_steps": 1, "live_steps": 1, **kw}}

    row = dict(index_query_rows=32, index_key_rows=2 * 160_000,
               selected_rows=6 * 16 * 2048, attended_rows=6 * 160_000,
               attn_pairs=160_000, kv_tokens=160_000, decode_tokens=16,
               prefill_tokens=0)
    events = [step(1, 0), dispatch(1, **row), step(2, 10),
              dispatch(11, **row)]
    src = {"span_export": {"traceEvents": events},
           "model": {"num_hidden_layers": 6},
           "metrics_delta": {
               "start": {"serving_step_duration_seconds_count": {"": 0.0}},
               "end": {"serving_step_duration_seconds_count": {"": 2.0}}}}

    def read(name):
        return bench_run.load_py(os.path.join(
            BENCH, "metrics", name + ".py")).reduce(src)

    assert abs(read("dsa_rows_read_over_selected") - 160_000 / 32768) < 1e-9
    assert abs(read("dsa_selected_share") - 100 * 32768 / 160_000) < 1e-9


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_without_their_source(name):
    """A parent commit, another model or an untraced run: no scope, no
    counter, no span. The reader gives None and the line leaves it out."""
    reader = bench_run.load_py(os.path.join(BENCH, "metrics", name + ".py"))
    no_counts = {"name": "dispatch", "ph": "X", "ts": 1.0, "dur": 1.0,
                 "args": {"grid_steps": 3, "live_steps": 2,
                          "attn_pairs": 5, "kv_tokens": 5}}
    for src in ({}, {"xplane": None, "model": {}},
                {"metrics_delta": {"start": {}, "end": {}}, "model": {}},
                {"xplane": {"busy_s": 1.0, "window_s": 2.0, "mosaic_s": 0.5},
                 "model": {"num_hidden_layers": 8}, "peaks": V5E,
                 "span_export": {"traceEvents": [
                     {"name": "step", "ph": "X", "ts": 0.0, "dur": 5.0,
                      "args": {"step": 1}}, no_counts]},
                 "metrics_delta": {"start": {
                     "serving_step_duration_seconds_count": {"": 0.0}},
                     "end": {"serving_step_duration_seconds_count":
                             {"": 1.0}}}}):
        assert reader.reduce(dict(src)) is None


def test_benchmark_lists_the_cell_where_the_issue_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "gap_p50_ms"
    for name in ("gap_p95_ms", "chunk_step_share", "mla_proj_share",
                 "moe_held_experts_roofline", "ttft_p50_ms"):
        assert CELL in per_layer[name]["workloads"]
    for name in ("mla_attn_share", "mla_attn_roofline", "slo_attained_share"):
        assert CELL not in per_layer[name]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (
        1, "sparse-longctx-decode-closed")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
