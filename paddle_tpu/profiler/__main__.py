"""CLI entry point: ``python -m paddle_tpu.profiler <trace.json>``.

The argument is a Chrome trace-event JSON FILE, exactly what ``GET
/debug/trace`` serves (README "Tracing & debugging"): per-lane span
SELF-time summary through :mod:`paddle_tpu.profiler.chrometrace`, so a
saved serving capture answers "where did the step go" without Perfetto.

    python -m paddle_tpu.profiler trace.json --top 25         # span table
    python -m paddle_tpu.profiler trace.json --json           # machine-readable

A ``jax.profiler`` (XPlane) trace DIRECTORY is not read here: the
benchmark's ``benchmark/xplane_reduce.py`` reduces one, offsets and all.
Exit status: 0 when events were parsed, 1 on unparseable input (a
directory, bad JSON, no traceEvents) so scripts can gate on it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _main_chrome(args):
    from .chrometrace import load_chrome_trace, span_self_times, \
        summarize_chrome
    if args.json:
        try:
            rows = span_self_times(load_chrome_trace(args.trace_dir))
        except ValueError as e:
            print(json.dumps({"error": str(e)}))
            return 1
        if args.top:
            rows = rows[:args.top]
        print(json.dumps({"trace": args.trace_dir, "rows": rows},
                         indent=1))
        return 0 if rows else 1
    try:
        out = summarize_chrome(args.trace_dir, top=args.top)
    except ValueError as e:
        print(f"unparseable trace: {e}")
        return 1
    print(out)
    return 0 if out != "no spans parsed" else 1


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.profiler",
        description="Per-lane span self-time over a Chrome trace-event "
                    "JSON file (as served by GET /debug/trace).")
    ap.add_argument("trace_dir", metavar="trace",
                    help="a Chrome trace-event JSON file")
    ap.add_argument("--top", type=int, default=10,
                    help="rows to report (0 = all)")
    ap.add_argument("--json", action="store_true",
                    help="emit the span table as JSON instead of text")
    args = ap.parse_args(argv)

    if os.path.isdir(args.trace_dir):
        print(f"{args.trace_dir} is a directory: a jax.profiler (XPlane) "
              f"trace is reduced by benchmark/xplane_reduce.py, not here")
        return 1
    return _main_chrome(args)


if __name__ == "__main__":
    sys.exit(main())
