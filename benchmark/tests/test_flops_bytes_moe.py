"""``flops_bytes_moe``: the routed FFN's required work at the published
OLMoE widths, against the figures of ISSUE 26 and PERF.md, and the readers
of ``moe_trace`` on hand-made traces and spans."""
import json
import os

import flops_bytes
import flops_bytes_moe
import moe_trace
import pytest
import run as bench_run

from conftest import BENCH

with open(os.path.join(BENCH, "configs",
                       "olmoe-1b-7b-0125-serve-8L.json")) as f:
    OLMOE = json.load(f)
with open(os.path.join(BENCH, "peaks.json")) as f:
    V5E = json.load(f)["devices"]["TPU v5 lite"]


def test_expert_sizes_at_published_widths():
    assert flops_bytes_moe.expert_params(OLMOE) == 3 * 2048 * 1024
    # 64 experts in bf16: 805 MB a layer (ISSUE 26, "96 % of a layer")
    assert 64 * flops_bytes_moe.expert_params(OLMOE) * 2 == 805306368


def test_a_full_decode_step_is_bound_by_the_weight_stream():
    # 24 rows x 8 picks a layer, 8 layers: about 61 experts a layer call
    touched = flops_bytes_moe.expected_experts_touched(64, 192)
    assert 60.5 < touched < 61.5
    flops, nbytes = flops_bytes_moe.routed_ffn_work(
        OLMOE, pairs=8 * 192, experts_touched=8 * touched)
    assert flops == 2 * 3 * 2048 * 1024 * 8 * 192
    assert 6.0e9 < nbytes < 6.3e9               # "about 6.1 GB"
    least, bound = flops_bytes.least_seconds(flops, nbytes, V5E)
    assert bound == "memory" and 7.3e-3 < least < 7.7e-3    # "7.5 ms"


def test_an_untouched_expert_costs_nothing_and_pairs_cost_flops():
    none = flops_bytes_moe.routed_ffn_work(OLMOE, 0, 0)
    assert none == (0, 0)
    a = flops_bytes_moe.routed_ffn_work(OLMOE, 100, 10)
    b = flops_bytes_moe.routed_ffn_work(OLMOE, 100, 11)
    assert b[0] == a[0]
    assert b[1] - a[1] == 2 * flops_bytes_moe.expert_params(OLMOE)
    c = flops_bytes_moe.routed_ffn_work(OLMOE, 200, 10)
    assert c[0] == 2 * a[0]


def test_a_prefill_chunk_is_bound_by_compute_only_past_many_rows():
    # 512 tokens x 8 picks over all 64 experts of one layer: 51.5 GFLOP,
    # 0.26 ms at the peak, under the 0.98 ms the weights take to stream
    flops, nbytes = flops_bytes_moe.routed_ffn_work(OLMOE, 4096, 64)
    assert 51e9 < flops < 52e9
    _, bound = flops_bytes.least_seconds(flops, nbytes, V5E)
    assert bound == "memory"


def _ops(scoped):
    return {"/device:TPU:0": {"ops": [(text, s, e)
                                      for text, _, s, e in scoped]}}, \
        {"/device:TPU:0": {text: name for text, name, _, _ in scoped}}


def test_scope_seconds_reads_path_components():
    devices, names = _ops([
        ("%gmm.1 = bf16[8] custom-call(%a)",
         "jit(f)/moe/moe_experts/jit(gmm)/x", 0, 3),
        ("%fusion.1 = bf16[8] fusion(%a)", "jit(f)/moe/moe_route/gather",
         3, 4),
        ("%fusion.2 = bf16[8] fusion(%a)", "jit(f)/attn/dot_general", 4, 9),
        ("%fusion.3 = bf16[8] fusion(%a)", "jit(f)/almoe/moe_routes/x",
         9, 10),
        ("%while.1 = f32[8] while(%a)", "jit(f)/moe/while", 0, 10)])
    secs = moe_trace.scope_seconds(devices, names)
    assert secs == {"moe": 4.0, "moe_route": 1.0, "moe_experts": 3.0}


def _span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


def test_sum_counts_takes_exactly_the_traced_steps():
    moe = dict(moe_pairs=10, moe_experts_touched=4, moe_max_expert_pairs=5,
               moe_layer_calls=2)
    events = [_span("step", 0, 10, step=7), _span("device-wait", 2, 5, **moe),
              _span("step", 20, 10, step=8),
              _span("prefill_launch", 21, 3, bucket=64, group=1, **moe),
              _span("device-wait", 25, 3, **moe),
              _span("step", 40, 10, step=9), _span("device-wait", 42, 5)]
    assert moe_trace.sum_counts(events)["moe_pairs"] == 30
    assert moe_trace.sum_counts(events, {8}) == {k: 2 * v
                                                  for k, v in moe.items()}
    assert moe_trace.sum_counts(events, {9}) is None


NEW_METRICS = ["moe_ffn_share", "moe_route_share", "moe_experts_roofline",
               "moe_experts_touched_share", "moe_load_max_over_mean"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_or_counters_gives_none(name):
    """What the parent commit gives a traced run: no ``moe`` scope, no
    ``serving_moe_*`` family, no span args. Nothing raises."""
    reader = bench_run.load_py(os.path.join(BENCH, "metrics", name + ".py"))
    empty = {"start": {}, "end": {}, "scrapes": []}
    for src in ({}, {"xplane": None, "metrics_delta": empty},
                {"xplane": {"busy_s": 1.0, "window_s": 2.0, "mosaic_s": 0.1},
                 "metrics_delta": empty, "span_export": {"traceEvents": []},
                 "model": OLMOE, "peaks": V5E}):
        assert reader.reduce(src) is None


def test_counter_metrics_from_a_scrape_delta():
    def scrape(pairs, touched, fullest, calls):
        return {"serving_moe_pairs_total": {"": pairs},
                "serving_moe_experts_touched_total": {"": touched},
                "serving_moe_max_expert_pairs_total": {"": fullest},
                "serving_moe_layer_calls_total": {"": calls}}
    src = {"model": OLMOE, "metrics_delta": {
        "start": scrape(100, 50, 9, 1), "end": scrape(100 + 1920,
                                                      50 + 610, 9 + 80, 11)}}
    share = bench_run.load_py(os.path.join(
        BENCH, "metrics", "moe_experts_touched_share.py")).reduce(src)
    assert abs(share - 100 * 61 / 64) < 1e-9
    load = bench_run.load_py(os.path.join(
        BENCH, "metrics", "moe_load_max_over_mean.py")).reduce(src)
    assert abs(load - 8 / 3) < 1e-9             # fullest 8 against mean 3
