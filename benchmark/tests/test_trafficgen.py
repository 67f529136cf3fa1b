import json
import os
import random
import statistics

import pytest
import trafficgen

from conftest import BENCH


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-steady", "batch-longprompt"])
def test_same_seed_same_schedule_other_seed_another(name):
    m = mix(name)
    a = trafficgen.serve_schedule(m, 7, 60.0, 32768)
    b = trafficgen.serve_schedule(m, 7, 60.0, 32768)
    c = trafficgen.serve_schedule(m, 8, 60.0, 32768)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    assert [r["max_tokens"] for r in a] != [r["max_tokens"] for r in c] \
        or m["output_tokens"]["dist"] == "constant"


@pytest.mark.parametrize("name", ["chat-steady", "batch-longprompt"])
def test_lengths_respect_the_clips(name):
    m = mix(name)
    for r in trafficgen.serve_schedule(m, 3, 120.0, 32768):
        p, o = m["prompt_tokens"], m["output_tokens"]
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert o.get("min", o.get("value")) <= r["max_tokens"] \
            <= o.get("max", o.get("value"))
        assert all(1 <= t < 32768 for t in r["prompt"])


def test_open_loop_offers_the_same_load_for_every_seed():
    m = mix("chat-steady")
    ends = []
    for seed in range(5):
        s = trafficgen.serve_schedule(m, seed, 200.0, 32768)
        dues = [r["due"] for r in s]
        assert dues == sorted(dues)
        ends.append(dues[-1] / len(dues))
    mean_gap = 1.0 / m["rate_per_s"]
    assert all(abs(e - mean_gap) / mean_gap < 0.1 for e in ends)


def test_stratified_blocks_cover_the_distribution():
    us = trafficgen.stratified_uniforms(64, random.Random(0), block=16)
    for i in range(0, 64, 16):
        strata = sorted(int(u * 16) for u in us[i:i + 16])
        assert strata == list(range(16))


def test_quantiles():
    assert trafficgen.quantile({"dist": "constant", "value": 8}, 0.3) == 8
    assert trafficgen.quantile(
        {"dist": "uniform", "min": 100, "max": 200}, 0.5) == 150
    ln = {"dist": "lognormal", "median": 192, "sigma": 0.6,
          "min": 32, "max": 512}
    assert trafficgen.quantile(ln, 0.5) == 192
    assert trafficgen.quantile(ln, 1e-9) == 32
    assert trafficgen.quantile(ln, 1 - 1e-9) == 512


def test_train_samples_repeat_by_seed_and_index():
    a = trafficgen.train_sample(1, 5, 64, 256)
    assert (a == trafficgen.train_sample(1, 5, 64, 256)).all()
    assert (a != trafficgen.train_sample(2, 5, 64, 256)).any()
    assert (a != trafficgen.train_sample(1, 6, 64, 256)).any()
    assert a.shape == (64,) and a.min() >= 0 and a.max() < 256


def test_stratified_arrivals_are_smoother_than_a_poisson_process():
    """A block of 8 stratified gaps takes 8 / rate = 4 s up to the jitter
    inside a stratum (the last stratum is the exponential's tail, so about
    0.5 s); 8 independent exponential gaps would take 4 s give or take
    sqrt(8) / rate = 1.4 s. The mix file names the process accordingly."""
    m = {"rate_per_s": 2.0, "arrivals": "stratified-exponential",
         "stratify_block": 8}
    dues = trafficgen.arrival_times(m, 400.0, random.Random(1))
    assert len(dues) == 800
    blocks = [dues[i + 7] - (dues[i - 1] if i else 0.0)
              for i in range(0, 800, 8)]
    assert statistics.pstdev(blocks) < 0.9
    assert abs(dues[-1] / 400.0 - 1.0) < 0.1
    assert mix("chat-steady")["arrivals"] == "stratified-exponential"


def test_an_unknown_arrival_process_is_an_error():
    with pytest.raises(ValueError):
        trafficgen.arrival_times({"rate_per_s": 1.0, "arrivals": "poisson"},
                                 10.0, random.Random(0))


def test_train_check_tokens_cover_rows_and_the_sequence():
    toks = trafficgen.train_check_tokens(5, 4, 4096, 16)
    assert toks == trafficgen.train_check_tokens(5, 4, 4096, 16)
    assert toks != trafficgen.train_check_tokens(6, 4, 4096, 16)
    assert [r for r, _ in toks] == [i % 4 for i in range(16)]
    for i, (_, pos) in enumerate(toks):
        assert i * 4095 // 16 <= pos < (i + 1) * 4095 // 16
    # a sequence shorter than the count still gives valid positions
    assert all(0 <= p < 7 for _, p in trafficgen.train_check_tokens(0, 2,
                                                                  8, 16))


# ------------------------------------------------- where a traced run traces
TRACE_S = 3.0
CHAT_SEEDS = [1949662630] + random.Random(41).sample(range(2 ** 31), 2000)
CLOSED = sorted(n[:-5] for n in os.listdir(os.path.join(BENCH, "traffic"))
                if mix(n[:-5]).get("loop") == "closed")


def chat_schedule(seed, seconds=40.0):
    """``serve_schedule`` as ``kinds/serve.py`` asks for it, prompts of one
    token: 2,000 seeds of whole prompts would take a minute, and the rule
    may read ``due`` and ``max_tokens`` only."""
    m = mix("chat-steady")
    m = {**m, "prompt_tokens": {"dist": "constant", "value": 1}}
    return trafficgen.serve_schedule(m, seed, m["ramp_s"] + seconds + 5.0, 2)


def overlap_s(start, req, s_per_token):
    end = req["due"] + 0.05 + s_per_token * req["max_tokens"]
    return min(start + TRACE_S, end) - max(start, req["due"])


def test_lengths_of_the_chat_schedule_do_not_depend_on_the_prompts():
    m = mix("chat-steady")
    whole = trafficgen.serve_schedule(m, 1949662630, 75.0, 32768)
    assert [(r["due"], r["max_tokens"]) for r in whole] \
        == [(r["due"], r["max_tokens"]) for r in chat_schedule(1949662630)]


@pytest.mark.parametrize("block", range(8))
def test_an_open_loop_traces_a_request_of_its_schedule(block):
    """Every seed's traced 3 s lie inside the window and hold the traced
    request for 0.1 s or more even at half of today's 11 ms a token, and
    for 0.25 s wherever a request is due in the rule's first interval."""
    m = mix("chat-steady")
    ramp, seconds = float(m["ramp_s"]), 40.0
    for seed in CHAT_SEEDS[block::8]:
        sched = chat_schedule(seed)
        start, req = trafficgen.trace_start_s(sched, m, ramp, seconds,
                                              TRACE_S)
        assert (start, req) == trafficgen.trace_start_s(
            chat_schedule(seed), m, ramp, seconds, TRACE_S)
        assert ramp <= start and start + TRACE_S <= ramp + seconds
        assert req is not None and req in sched
        first = [r for r in sched
                 if ramp + 2 <= r["due"] <= ramp + seconds - 10]
        assert overlap_s(start, req, 0.0055) >= (0.25 if first else 0.1), seed
        if first:
            assert req["max_tokens"] == max(r["max_tokens"] for r in first)
            assert start == max(req["due"] - trafficgen.TRACE_LEAD_S, ramp)


def test_the_seed_whose_middle_is_empty_traces_request_8():
    """Seed 1949662630 (PR 37's refused check): nothing is due between 44.6
    and 57.9 s, so the middle, 48.5-51.5 s, held no request."""
    m = mix("chat-steady")
    sched = chat_schedule(1949662630)
    assert not any(44.7 < r["due"] < 57.8 for r in sched)
    start, req = trafficgen.trace_start_s(sched, m, 30.0, 40.0, TRACE_S)
    assert (req["index"], req["max_tokens"]) == (8, 203)
    assert abs(req["due"] - 37.196) < 1e-3 and start == req["due"] - 0.5
    assert overlap_s(start, req, 0.011) > 2.2


@pytest.mark.parametrize("name", CLOSED)
def test_a_closed_loop_traces_the_middle_as_before(name):
    m = mix(name)
    sched = trafficgen.serve_schedule({**m, "max_requests": 8}, 5, 0.0, 64)
    for seconds in (40.0, 5.0, 37.3):
        ramp = float(m["ramp_s"])
        win = (ramp, ramp + seconds)
        old = (win[0] + win[1]) / 2.0 - TRACE_S / 2.0   # kinds/serve.py:281
        assert trafficgen.trace_start_s(sched, m, ramp, seconds,
                                        TRACE_S) == (old, None)
    assert len(CLOSED) >= 5


def test_an_open_loop_with_nothing_due_traces_the_middle():
    m = mix("chat-steady")
    late = [{"index": 0, "due": 12.0, "max_tokens": 64},
            {"index": 1, "due": 68.5, "max_tokens": 256}]
    assert trafficgen.trace_start_s(late, m, 30.0, 40.0, TRACE_S) \
        == (48.5, None)
    assert trafficgen.trace_start_s([], m, 30.0, 40.0, TRACE_S) \
        == (48.5, None)


def test_the_second_interval_and_the_ties():
    m = mix("chat-steady")
    # nothing due in [32, 60]: the wider interval [30, 67] takes its longest;
    # a request due before the lead fits starts the trace with the window
    edge = [{"index": 0, "due": 30.2, "max_tokens": 200},
            {"index": 1, "due": 66.0, "max_tokens": 100}]
    assert trafficgen.trace_start_s(edge, m, 30.0, 40.0, TRACE_S) \
        == (30.0, edge[0])
    # equal lengths: the earliest
    ties = [{"index": 0, "due": 40.0, "max_tokens": 90},
            {"index": 1, "due": 45.0, "max_tokens": 256},
            {"index": 2, "due": 50.0, "max_tokens": 256}]
    assert trafficgen.trace_start_s(ties, m, 30.0, 40.0, TRACE_S) \
        == (44.5, ties[1])
