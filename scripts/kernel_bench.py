"""What the kernels' own benches share (``bench_ssd.py``, ``bench_gdn.py``,
``bench_ragged.py``): the arguments every one takes, the checkout whose
kernels run, the timing loop and the JSON line. Imported, not run.

A bench times on the chip. Off it, it runs only as a rehearsal that was asked
for (``--rehearse``: tiny sizes, interpreted kernels, to find a script's own
faults before a chip call), so that no line from a CPU is ever taken for a
measurement by mistake; every line says its ``platform`` besides.
"""
import argparse
import json
import os
import sys
import time

HBM_GBPS = 819.0        # one v5e chip (Google Cloud documentation, "TPU v5e")


def arguments(doc, iters):
    """The parser with what every bench takes; a script adds its own."""
    ap = argparse.ArgumentParser(
        description=doc, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=iters)
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout whose kernels run")
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip: tiny sizes, interpreted kernels")
    return ap


def start(a):
    """Puts ``a.repo`` first on the import path, touches JAX and returns
    ``(platform, tiny)``: ``tiny`` is a rehearsal, which is what a machine
    without the chip runs and only where ``--rehearse`` asked for it."""
    sys.path.insert(0, os.path.abspath(a.repo))
    import jax
    platform = jax.devices()[0].platform
    tiny = platform != "tpu"
    if tiny != a.rehearse:
        sys.exit(f"platform {platform!r}: " + (
            "a timing comes from the chip alone; --rehearse runs the script "
            "at tiny sizes, interpreted" if tiny
            else "--rehearse is for a machine without the chip"))
    return platform, tiny


def timed(step, state, iters):
    """``state = step(state)`` once (the compile: ``first`` seconds), then
    ``iters`` times; ``(state, ms an iteration, first)``."""
    import jax
    t0 = time.perf_counter()
    state = jax.block_until_ready(step(state))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        state = step(state)
    jax.block_until_ready(state)
    return state, 1e3 * (time.perf_counter() - t0) / iters, first


def line(case, platform, **out):
    """One JSON line a case, floats at five figures."""
    print(json.dumps({"case": case, "platform": platform, **{
        k: (float(f"{v:.5g}") if isinstance(v, float) else v)
        for k, v in out.items()}}), flush=True)
