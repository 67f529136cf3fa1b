#!/usr/bin/env python3
"""A builder's sweep, never run by the driver: one cell under another mix
file than its own, which is how the rate of an open-loop mix is found.

    python3 benchmark/sweep.py --mix <file.json> --workload <cell> --seed <n> --seconds <s> --trace 0

Copy the cell's mix to a scratch file, change ``rate_per_s``, and run this
once per rate, all rates in one ``chiprun`` call. Every other argument is
``run.py``'s, and so are the output lines: read ``backlog_mid_end`` and
``out_tokens_per_s`` on the ``window`` line (README.md, "How the rate of an
open-loop cell was found").
"""
import argparse
import sys

import run

if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mix", required=True)
    a, rest = ap.parse_known_args()
    try:
        sys.exit(run.main(rest, mix_path=a.mix))
    except (RuntimeError, OSError, KeyError) as e:
        print(f"benchmark FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        sys.exit(1)
