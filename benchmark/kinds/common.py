"""What every child does first and last: refuse a wrong device, place the
compile cache, name the device as JAX reports it, read the peaks."""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_peaks(device_kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: device kind {device_kind!r} is not in "
                         f"peaks.json; add it with its source")
    return table[device_kind]


def child_start(rehearse, chips):
    """Exit non-zero, with no result, unless JAX shows ``chips`` TPU devices
    of a kind peaks.json knows (or the rehearsal's CPU devices)."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if rehearse:
        if platform != "cpu":
            raise SystemExit("benchmark: --rehearse-cpu runs on the CPU "
                             f"backend, JAX reports {platform!r}")
    else:
        if platform != "tpu":
            raise SystemExit(f"benchmark: JAX platform is {platform!r}, "
                             "not 'tpu'")
        load_peaks(devs[0].device_kind)     # an unknown kind is an error
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"reports {len(devs)}")
    from paddle_tpu.utils import compile_cache
    stats = compile_cache.enable()
    return stats, devs[:chips]


def model_keys(cfg):
    """The keys of a configuration file that ``LlamaConfig`` takes."""
    return {k: cfg[k] for k in cfg["model_keys"]}


def serve_engine_kwargs(geometry):
    """What ``serve()`` would hand ``ContinuousBatchingEngine`` for this
    geometry: ``serve()``'s own defaults for every argument the engine
    takes, so an engine built here shares the server's programs."""
    import inspect

    from paddle_tpu.serving import ContinuousBatchingEngine
    from paddle_tpu.serving.server import serve
    accepted = inspect.signature(ContinuousBatchingEngine.__init__).parameters
    kw = {k: p.default for k, p in inspect.signature(serve).parameters.items()
          if k in accepted and p.default is not inspect.Parameter.empty}
    kw.update(geometry)
    return kw


def device_doc(devs):
    """The result line's ``device``: as JAX reports it; memory peak on the
    fullest chip (0 where the backend reports none, i.e. the CPU)."""
    peak = 0
    for d in devs:
        ms = d.memory_stats()
        if ms is not None:
            peak = max(peak, int(ms["peak_bytes_in_use"]))
    import jax
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def say(obj):
    """One JSON line to the parent."""
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()
