#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is found by its name in ``BENCHMARK.json`` and nothing is registered
in code: its configuration is ``configs/<config>.json``, its mix is
``traffic/<traffic>.json``, the configuration's ``kind`` names the file
under ``kinds/`` that runs it, and every metric is the file
``metrics/<name>.py`` with one ``reduce(src)``. This process imports no JAX:
the kind starts one child that owns the chips and always reaps it.

Earlier stdout lines are JSON notes (sample counts, generator lateness,
the reference check); the last line is the result the driver reads. Any
failure exits non-zero and prints no result line.
"""
import time

T_PROCESS_START = time.monotonic()      # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import proc  # noqa: E402

SETUP_BUDGET_S = 1100       # a first run compiles; the contract allows 1200


def load_py(path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    with open(path) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def metrics_for(bench, group, cell):
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def note(obj):
    print(json.dumps(obj), flush=True)


def main(argv=None, mix_path=None):
    """``mix_path`` is for ``sweep.py`` alone: the cell under another mix
    file than its own. The command the driver runs has no such argument."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug the harness on the CPU backend at the tiny "
                         "sizes the data files give under 'rehearse'; the "
                         "result line names platform 'cpu' and is never a "
                         "measurement")
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(bench["workloads"], args.workload, "workload")
    config_path = os.path.join(
        ROOT, by_name(bench["configs"], cell["config"], "config")["file"])
    mix_path = mix_path or os.path.join(
        HERE, "traffic", cell["traffic"] + ".json")
    config, mix = load_json(config_path), load_json(mix_path)
    kind = load_py(os.path.join(HERE, "kinds", config["kind"] + ".py"))
    run_dir = os.path.join(proc.RUN_DIR, cell["name"])
    os.makedirs(run_dir, exist_ok=True)
    src = kind.drive({
        "args": args, "cell": cell, "chips": cell["chips"],
        "config": config, "config_path": config_path, "mix": mix,
        "mix_path": mix_path, "run_dir": run_dir, "log": note,
        "t_process_start": T_PROCESS_START,
        "setup_budget_s": SETUP_BUDGET_S})
    src["config"], src["seconds"] = config, args.seconds
    if src["device"]["platform"] == "tpu":
        src["peaks"] = load_json(os.path.join(HERE, "peaks.json"))[
            "devices"][src["device"]["kind"]]

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, group, cell["name"]):
        reader = load_py(os.path.join(HERE, "metrics", m["name"] + ".py"))
        value = reader.reduce(src)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if not args.trace:
        missing = [m["name"] for m in metrics_for(bench, group, cell["name"])
                   if m["name"] not in metrics]
        if missing:
            raise SystemExit(f"benchmark: no value for {missing}")
    result = {"correct": src["correct"], "attempted": src["attempted"],
              "failed": src["failed"], "metrics": metrics,
              "device": dict(src["device"])}
    if args.trace:
        x = src.get("xplane")
        if not args.rehearse_cpu and (not x or x["busy_s"] <= 0):
            raise SystemExit("benchmark: the trace shows no operation on "
                             "the device")
        if x:
            result["device"]["busy_s"] = x["busy_s"]
            result["device"]["window_s"] = x["window_s"]
            result["breakdown"] = {"device_ops": x["top_ops"][:10],
                                   "idle_gaps": x["gaps"][:10]}
            note({"event": "mosaic_calls", "calls": x["mosaic_calls"],
                  "collective_s": x["collective_s"],
                  "annotations": x["annotations"]})
    # every number ``correct`` compared, beside its limit: the last key of
    # the result and the last lines on standard error, which is what the
    # driver's record keeps of a run that is not correct
    # (a number that is not finite goes as its name: the line stays JSON)
    result["compared"] = {
        k: {"value": v if math.isfinite(v) else repr(v), "limit": lim}
        for k, (v, lim) in src["compared"].items()}
    for k, c in result["compared"].items():
        print(f"compared {k}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except (RuntimeError, OSError, KeyError) as e:
        print(f"benchmark FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        sys.exit(1)
