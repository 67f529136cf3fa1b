"""Each cell end to end at tiny sizes on the CPU backend (``--rehearse-cpu``;
four virtual devices for the four-chip cell), checking the last line's keys,
and the refusals: no result off the chip, none for an unknown device."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def run(args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py")] + args, cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=timeout)


def metrics_of(group, cell):
    return {m["name"] for m in BENCHMARK[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    p = run(["--workload", cell, "--seed", "3", "--seconds", "5",
             "--trace", str(trace), "--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} \
        <= set(last)
    assert last["device"]["platform"] == "cpu"      # never a measurement
    assert last["correct"] is True and last["attempted"] > 0
    assert last["failed"] == 0
    # what ``correct`` compared, each number beside its limit: the result's
    # last key and standard error's last lines
    assert list(last)[-1] == "compared" and last["compared"]
    said = p.stderr.strip().splitlines()[-len(last["compared"]):]
    for ln, (name, c) in zip(said, last["compared"].items()):
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
        assert ln == f"compared {name}: {c['value']} limit {c['limit']}"
    group = "per_layer" if trace else "end_to_end"
    assert set(last["metrics"]) <= metrics_of(group, cell)
    if not trace:
        assert set(last["metrics"]) == metrics_of(group, cell)
        assert all(v["value"] > 0 for v in last["metrics"].values())
    for name, v in last["metrics"].items():
        unit = next(m["unit"] for m in BENCHMARK[group] if m["name"] == name)
        assert v["unit"] == unit


def test_no_result_off_the_chip():
    p = run(["--workload", CELLS[0], "--seed", "0", "--seconds", "2",
             "--trace", "0"], timeout=300)
    assert p.returncode != 0
    assert not any(ln.startswith('{"correct"')
                   for ln in p.stdout.splitlines())


def test_unknown_device_kind_is_an_error():
    sys.path.insert(0, BENCH)
    from kinds import common
    with pytest.raises(SystemExit):
        common.load_peaks("TPU v9 imaginary")
    assert common.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_every_named_file_exists():
    for c in BENCHMARK["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(BENCH, "kinds",
                                           cfg["kind"] + ".py"))
    for w in BENCHMARK["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("twin, name", [
    ("train_device_idle_share", "device_idle_share"),
    ("train_hbm_peak_gb", "hbm_peak_gb"),
    ("train_attn_kernel_share", "attn_kernel_share")])
def test_a_twin_metric_reads_what_its_original_reads(twin, name):
    import run
    src = {"xplane": {"idle_share": 0.25, "busy_s": 2.0, "mosaic_s": 0.5},
           "device": {"memory_peak_bytes": 3 * 10 ** 9}}
    got = [run.load_py(os.path.join(BENCH, "metrics", n + ".py")).reduce(src)
           for n in (twin, name)]
    assert got[0] == got[1] and got[0] > 0
    assert run.load_py(os.path.join(BENCH, "metrics", twin + ".py")) \
        .reduce({"device": {}}) is None


def test_the_command_takes_no_mix_argument():
    p = run(["--workload", CELLS[0], "--seed", "0", "--seconds", "1",
             "--trace", "0", "--traffic-file", "x.json"], timeout=60)
    assert p.returncode == 2 and "unrecognized" in p.stderr
