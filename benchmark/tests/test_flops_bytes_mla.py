"""``flops_bytes_mla``: the absorbed attention's and the held experts'
required work at DeepSeek-V2's published widths, against the figures ISSUE 31
reckons, and the readers of ``mla_trace`` on hand-made traces; every reader
the cell adds returns None, and raises nothing, on a run without its
source."""
import json
import os

import flops_bytes
import flops_bytes_mla
import mla_trace
import pytest
import run as bench_run

from conftest import BENCH

with open(os.path.join(BENCH, "configs",
                       "deepseek-v2-serve-8L-ep8.json")) as f:
    DSV2 = json.load(f)
with open(os.path.join(BENCH, "peaks.json")) as f:
    V5E = json.load(f)["devices"]["TPU v5 lite"]
NEW = ("mla_attn_share", "mla_attn_roofline", "mla_proj_share",
       "moe_shared_share", "moe_held_experts_roofline",
       "moe_held_experts_touched_share", "moe_held_load_max_over_mean",
       "chunk_step_share")


def test_a_triple_costs_2176_flops_absorbed():
    assert flops_bytes_mla.triple_flops(DSV2) == 2 * 576 + 2 * 512 == 2176


def test_a_decode_step_over_160k_cached_tokens():
    """32 decode rows over 160k cached tokens, 8 layers, 128 heads: 356
    GFLOP and 1.5 GB of cache rows, about 1.8 ms at either peak (the form
    sits on the v5e's ridge)."""
    flops, nbytes = flops_bytes_mla.absorbed_attention_work(
        DSV2, attn_pairs=160_000, kv_tokens=160_000)
    assert flops == 160_000 * 8 * 128 * 2176
    assert 3.55e11 < flops < 3.58e11
    assert nbytes == 160_000 * 8 * 576 * 2 and 1.47e9 < nbytes < 1.48e9
    assert 240 < flops / nbytes < 243                   # ridge: 240
    least, _ = flops_bytes.least_seconds(flops, nbytes, V5E)
    assert 1.79e-3 < least < 1.82e-3
    # the queries' and outputs' own rows are counted too, and are small
    _, with_q = flops_bytes_mla.absorbed_attention_work(
        DSV2, 160_000, 160_000, query_tokens=32)
    assert with_q - nbytes == 8 * 32 * 128 * (576 + 512) * 2


def test_a_chunk_is_dearer_than_expanded():
    """512 tokens over a 2,000-token prefix: 2,176 against 640 FLOPs."""
    pairs = 512 * 2000 + 512 * 513 // 2
    flops, _ = flops_bytes_mla.absorbed_attention_work(DSV2, pairs, 2512)
    assert flops == 8 * pairs * 128 * 2176
    assert flops_bytes_mla.triple_flops(DSV2) / (2 * 192 + 2 * 128) == 3.4


def test_held_experts_at_their_own_width():
    assert flops_bytes_mla.expert_params(DSV2) == 3 * 5120 * 1536 \
        == 23_592_960                                   # "23.6 M"
    # the dense width would read eight times the work
    assert DSV2["intermediate_size"] == 8 * DSV2["moe_intermediate_size"]
    touched = 20 * (1 - (159 / 160) ** 192)     # 192 even picks over 160
    assert 13.9 < touched < 14.2                        # "about 71 %"
    flops, nbytes = flops_bytes_mla.held_experts_work(
        DSV2, pairs=7 * 24, experts_touched=7 * touched)
    assert flops == 2 * 23_592_960 * 7 * 24
    assert 4.6e9 < nbytes < 4.7e9                       # "experts 4.7 GB"
    least, bound = flops_bytes.least_seconds(flops, nbytes, V5E)
    assert bound == "memory" and 5.6e-3 < least < 5.8e-3
    assert flops_bytes_mla.held_experts_work(DSV2, 0, 0) == (0, 0)


def _op(name, kind="fusion"):
    return f"%{name} = bf16[8,128]{{1,0}} {kind}(bf16[8,128]{{1,0}} %p)"


def test_scope_seconds_by_path_component_and_kernel_by_name():
    devices = {"/device:TPU:0": {"ops": [
        (_op("fusion.1"), 0.0, 1.0), (_op("fusion.2"), 1.0, 3.0),
        (_op("mla_ragged_attention.3", "custom-call"), 3.0, 7.0),
        (_op("gmm.4", "custom-call"), 7.0, 9.0),
        (_op("fusion.5"), 9.0, 9.5), (_op("while.6", "while"), 0.0, 9.5)]}}
    names = {"/device:TPU:0": {
        _op("fusion.1"): "jit(step)/ragged_step/attn/mla/mla_proj/dot",
        _op("fusion.2"): "jit(step)/ragged_step/moe_shared/dot",
        _op("mla_ragged_attention.3", "custom-call"):
            "jit(step)/ragged_step/attn/mla/mla_attend/pallas_call",
        _op("gmm.4", "custom-call"): "jit(step)/moe/moe_experts/gmm",
        _op("fusion.5"): "jit(step)/ragged_step/attn/mla/mla_proj/wo",
        _op("while.6", "while"): "jit(step)/ragged_step/mla_proj"}}
    secs = mla_trace.scope_seconds(devices, names)
    assert secs == {"mla_attend": 4.0, "mla_proj": 1.5, "moe_shared": 2.0,
                    "kernel": 4.0, "kernel_calls": 1}


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_without_their_source(name):
    """A parent commit, a dense model or an untraced run: no scope, no
    counter, no span. The reader gives None and the line leaves it out."""
    reader = bench_run.load_py(os.path.join(BENCH, "metrics", name + ".py"))
    for src in ({}, {"xplane": None, "model": {}},
                {"metrics_delta": {"start": {}, "end": {}}, "model": {}},
                {"xplane": {"busy_s": 1.0, "window_s": 2.0, "mosaic_s": 0.5},
                 "model": {"num_experts": 64}, "peaks": V5E,
                 "span_export": {"traceEvents": []}}):
        assert reader.reduce(dict(src)) is None


def test_held_experts_readers_from_the_counters():
    """15.4 of 20 held experts read a layer call; the fullest holds 4 of a
    call's 24 pairs: 3.33 times the mean."""
    src = {"model": {"n_routed_experts": 20}, "metrics_delta": {
        "start": {"serving_moe_pairs_total": {"": 100.0},
                  "serving_moe_experts_touched_total": {"": 50.0},
                  "serving_moe_max_expert_pairs_total": {"": 10.0},
                  "serving_moe_layer_calls_total": {"": 7.0}},
        "end": {"serving_moe_pairs_total": {"": 100.0 + 700 * 24},
                "serving_moe_experts_touched_total": {"": 50.0 + 700 * 15.4},
                "serving_moe_max_expert_pairs_total": {"": 10.0 + 700 * 4},
                "serving_moe_layer_calls_total": {"": 707.0}}}}

    def read(name):
        return bench_run.load_py(os.path.join(
            BENCH, "metrics", name + ".py")).reduce(src)

    assert abs(read("moe_held_experts_touched_share") - 77.0) < 1e-9
    assert abs(read("moe_held_load_max_over_mean") - 4 * 20 / 24) < 1e-9


def test_chunk_step_share_from_the_step_spans():
    def step(ts, chunks):
        return {"name": "step", "ph": "X", "ts": ts, "dur": 5.0,
                "args": {"step": ts, "tokens": 32 + 512 * chunks,
                         "chunks": chunks}}

    events = [step(0, 1)] + [step(10 + i, int(i % 4 == 0)) for i in range(8)]
    src = {"span_export": {"traceEvents": events},
           "metrics_delta": {
               "start": {"serving_step_duration_seconds_count": {"": 1.0}},
               "end": {"serving_step_duration_seconds_count": {"": 9.0}}}}
    reader = bench_run.load_py(os.path.join(
        BENCH, "metrics", "chunk_step_share.py"))
    assert reader.reduce(src) == 25.0       # 2 of the window's 8 steps


def test_benchmark_lists_the_new_cell_where_the_issue_says():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "serve-dsv2-longctx-decode"
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", ())}
    assert set(NEW) <= listed
    assert not listed & {"moe_experts_roofline", "moe_experts_touched_share",
                         "moe_load_max_over_mean", "attn_kernel_share",
                         "ragged_attn_roofline",
                         "ragged_attn_roofline_counted"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
