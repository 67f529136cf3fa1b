"""Driver-flow contract for bench.py (no device; children are stubbed).

bench.py's parent imports no JAX and runs one child per leg. These tests
pin what a run on the chip must be able to rely on:

1. a leg that fails is named in the result line and makes the exit code 1;
   no number is replayed from an earlier run and no leg is retried on a
   different kernel or backend;
2. the default flow starts no child on a forced CPU platform;
3. the result line names the device the children reported.
"""
import contextlib
import importlib.util
import io
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def _run_main(bench):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = bench.main()
    return rc, json.loads(buf.getvalue().strip().splitlines()[-1])


def _fake_children(bench, calls, fail=()):
    """A stub for bench._run: every leg succeeds except those in `fail`."""
    def fake_run(args, timeout, env=None):
        leg = next(a for a in args if a.startswith("--"))
        calls.append((leg, list(args), env))
        if leg in fail:
            return 1, "", f"{leg} died: Mosaic said no"
        if leg == "--smoke":
            return 0, json.dumps({"kernel": "k", "ok": True,
                                  "device": DEVICE}), ""
        if leg == "--config":
            i = int(args[args.index("--config") + 1])
            return 0, json.dumps(
                {"name": bench.CONFIGS[i][0], "mfu": 0.30 + i * 0.001,
                 "tok_s": 1.0, "loss": 7.0, "n_params": 3.7e8,
                 "peak": 1.97e14, "step_ms": 1.0, "warm_s": 1.0,
                 "device": DEVICE}), ""
        if leg == "--layer7b":
            return 0, json.dumps({"layer7b_tok_s": 1, "layer7b_mfu": 0.5,
                                  "device": DEVICE}), ""
        if leg == "--trace":
            return 0, json.dumps({"name": "x", "mfu": 0.3, "top_ops": [],
                                  "device": DEVICE}), ""
        if leg == "--decode":
            return 0, json.dumps({"name": "decode[pallas]", "ok": True,
                                  "attn": "pallas", "decode_tok_s": 321.0,
                                  "decode_mbu": 0.4, "device": DEVICE}), ""
        raise AssertionError(args)
    bench._run = fake_run


class TestBenchDriverFlow:
    def test_success_names_device_and_runs_only_chip_legs(self):
        bench = _load_bench()
        calls = []
        _fake_children(bench, calls)
        rc, doc = _run_main(bench)
        assert rc == 0 and doc["failed"] == []
        assert doc["metric"] == bench.METRIC and doc["value"] > 0
        assert doc["device"] == DEVICE
        assert "decode[pallas] 321" in doc["unit"]
        legs = [leg for leg, _, _ in calls]
        assert legs[0] == "--smoke" and legs[-1] == "--decode"
        # no child is forced onto another platform
        assert all(env is None for _, _, env in calls)
        # decode goes through the Pallas kernel only: no jnp second try
        decodes = [a for leg, a, _ in calls if leg == "--decode"]
        assert len(decodes) == 1 and decodes[0][-1] == "pallas"
        # each config is tried once: no retry ladder
        assert legs.count("--config") == len(bench.CONFIGS)

    def test_failed_leg_is_named_and_exit_is_nonzero(self):
        bench = _load_bench()
        calls = []
        _fake_children(bench, calls, fail=("--decode",))
        rc, doc = _run_main(bench)
        assert rc == 1
        (f,) = doc["failed"]
        assert f["leg"] == "decode" and f["rc"] == 1
        assert "Mosaic said no" in f["stderr_tail"]
        assert doc["decode"] is None and "decode[" not in doc["unit"]
        # the legs that did run still report
        assert doc["value"] > 0 and doc["device"] == DEVICE

    def test_total_failure_reports_no_number(self):
        bench = _load_bench()
        bench._run = lambda args, timeout, env=None: (124, "", "dead")
        rc, doc = _run_main(bench)
        assert rc == 1
        assert doc["value"] is None and doc["device"] is None
        assert "not measured" in doc["unit"]
        assert {f["leg"] for f in doc["failed"]} >= {"smoke", "decode"}

    def test_parent_imports_no_jax(self):
        code = ("import sys; sys.path.insert(0, %r); import bench; "
                "assert 'jax' not in sys.modules, 'bench parent holds jax'"
                % REPO)
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=60)
        assert p.returncode == 0, p.stderr[-500:]
