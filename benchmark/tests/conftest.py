"""``pytest benchmark/tests`` (CPU). The benchmark's modules are imported
by file name, as ``run.py`` does, so its directory goes on the path."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
