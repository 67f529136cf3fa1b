#!/usr/bin/env python3
"""``preflight.py`` for a cell of kind ``serve_arch``: ``preflight.serve_step``
itself (the engine's unified step caught at its first call, compiled for a
described v5e, ``memory_analysis()`` printed) with the classes the
configuration names in ``LlamaConfig`` / ``LlamaForCausalLM``'s place and
every module of ``paddle_tpu.kernels`` told to compile.

    JAX_PLATFORMS=cpu python3 benchmark/preflight_arch.py --workload <cell>
"""
import importlib
import json
import os
import pkgutil
import sys

import preflight


def main():
    workload = sys.argv[sys.argv.index("--workload") + 1]
    with open(os.path.join(preflight.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    with open(os.path.join(preflight.ROOT, next(
            c["file"] for c in bench["configs"]
            if c["name"] == cell["config"]))) as f:
        cfg = json.load(f)
    devices = preflight._topology()
    import paddle_tpu.kernels as kernels
    from paddle_tpu.models import llama
    module = importlib.import_module(cfg["model"]["module"])
    llama.LlamaConfig = getattr(module, cfg["model"]["config"])
    llama.LlamaForCausalLM = getattr(module, cfg["model"]["class"])
    for info in pkgutil.iter_modules(kernels.__path__):
        mod = importlib.import_module(kernels.__name__ + "." + info.name)
        if hasattr(mod, "_interpret_mode"):
            mod._interpret_mode = lambda: False
    preflight.serve_step(cfg, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
