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
