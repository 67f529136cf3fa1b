#!/usr/bin/env python3
"""Compile-only pre-flight: the cells' step programs, at the cells' real
shapes, compiled by the real XLA:TPU and Mosaic compilers for a *described*
v5e 2x2 host (no chip attached), printing ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmark/preflight.py --workload <cell>

A compile that passes here is a compile, not a run: it says the program
lowers, Mosaic accepts the kernels, and arguments + temp fit 15.75 GiB. It
says nothing about results or times. It costs sandbox minutes and host RAM
(the model is built on the CPU at its real size to get the real argument
trees), and no chip time.

How: the program's own objects are built on the CPU backend; the argument
trees they hand to their jitted step are turned into ``ShapeDtypeStruct``s
placed on the described devices, and that step is lowered and compiled for
them. The kernels are told to compile instead of interpreting, as
``tests/test_chip_bringup.py`` does. Serving: the engine's unified step is
caught at its first call (nothing runs at 7B widths on the CPU). Training:
``TrainStep``'s jitted step with its own parameter, optimizer-state and
batch trees; under a mesh the global mesh is rebuilt over the described
devices with the same axis degrees.
"""
import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]
GIB = 2.0 ** 30


def _topology():
    from jax.experimental import topologies

    from paddle_tpu.kernels import (flash_attention, pallas_flash,
                                    pallas_paged_decode,
                                    pallas_ragged_attention)
    devices = topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices
    for mod in (pallas_flash, pallas_paged_decode, pallas_ragged_attention):
        mod._interpret_mode = lambda: False
    flash_attention._use_pallas = lambda s: s >= 512
    return devices


def _report(name, compiled):
    m = compiled.memory_analysis()
    doc = {"program": name, "compiled_for": "v5e:2x2 (described, no chip)",
           "argument_gib": m.argument_size_in_bytes / GIB,
           "temp_gib": m.temp_size_in_bytes / GIB,
           "alias_gib": m.alias_size_in_bytes / GIB,
           "output_gib": m.output_size_in_bytes / GIB,
           "args_plus_temp_gib": (m.argument_size_in_bytes
                                  + m.temp_size_in_bytes) / GIB,
           "mosaic_custom_calls": compiled.as_text().count("tpu_custom_call")}
    print(json.dumps(doc), flush=True)
    return doc


def serve_step(cfg, devices):
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    import paddle_tpu.serving.engine as eng
    from kinds import common
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import GenerationRequest

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**common.model_keys(cfg),
                                         dtype=cfg["dtype"]))
    caught = {}

    class Caught(Exception):
        pass

    real_build = eng.build_ragged_step_fn

    def fake_build(**kw):
        caught["kw"] = kw

        def call(*args):
            caught["args"] = args
            raise Caught
        return call

    eng.build_ragged_step_fn = fake_build
    engine = eng.ContinuousBatchingEngine(
        model, **common.serve_engine_kwargs(cfg["engine"]))
    long_prompt = list(range(1, cfg["engine"]["prefill_chunk"] + 90))
    try:
        engine.generate([GenerationRequest(long_prompt, max_new_tokens=2)])
    except Caught:
        pass
    finally:
        eng.build_ragged_step_fn = real_build
    one = SingleDeviceSharding(devices[0])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                       if not hasattr(a, "dtype")
                                       else a.dtype, sharding=one),
        caught["args"])
    fn = real_build(**{**caught["kw"], "donate": True})
    with jax.default_matmul_precision("default"):
        compiled = fn.lower(*shapes).compile()
    g = cfg["engine"]
    return _report(f"unified serving step, {g['num_slots']} slots x "
                   f"{g['max_seq_len']}, chunk {g['prefill_chunk']}, "
                   f"{cfg['num_hidden_layers']} layers", compiled)


def train_step(cfg, mix, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, SingleDeviceSharding

    import paddle_tpu as paddle
    from kinds import train as train_kind
    from paddle_tpu.jit import _norm_batch, _norm_labels
    from paddle_tpu.parallel import mesh as mesh_mod

    mesh, replicas, model = train_kind.build_model(cfg, 0)
    batch = int(mix["sequences_per_replica"]) * replicas
    step = train_kind.build_step(model, cfg, mesh)
    ids = paddle.to_tensor(np.zeros((batch, int(mix["seq_len"])), np.int32))
    inputs = step._place_batch(_norm_batch((ids, ids)))
    labels = step._place_batch(_norm_labels((ids,)))
    if mesh is None:
        place = lambda s: SingleDeviceSharding(devices[0])  # noqa: E731
    else:
        tpu_mesh = mesh_mod.set_mesh(mesh_mod.build_mesh(
            {k: int(v) for k, v in mesh.shape.items()}, devices=devices))

        def place(s):
            spec = getattr(s, "spec", None)
            return NamedSharding(tpu_mesh, spec if spec is not None
                                 else jax.sharding.PartitionSpec())

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=place(a.sharding)), tree)

    scalar = lambda dt, shape=(): jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=place(None))
    with jax.default_matmul_precision("default"):
        compiled = step._compiled.lower(
            abstract(step._params), abstract(step._buffers),
            abstract(step._opt_state), abstract(inputs), abstract(labels),
            scalar(jnp.float32), scalar(jnp.uint32, (2,))).compile()
        # the forward alone, which the forward check of kinds/train.py runs
        forward = step._compiled_eval.lower(
            abstract(step._params), abstract(step._buffers),
            abstract(inputs), abstract(labels),
            scalar(jnp.uint32, (2,))).compile()
    shape = (f"B={batch} x S={mix['seq_len']}, {cfg['num_hidden_layers']} "
             f"layers, mesh {None if mesh is None else dict(mesh.shape)}")
    _report("forward of the train step (eval_step), " + shape, forward)
    return _report("train step, " + shape, compiled)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--num-slots", type=int, default=None,
                    help="serving: try another number of slots than the "
                         "configuration's (what fits?)")
    a = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
    cfile = next(c["file"] for c in bench["configs"]
                 if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfile)) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    devices = _topology()
    if a.num_slots:
        cfg["engine"]["num_slots"] = a.num_slots
    if cfg["kind"] == "serve":
        serve_step(cfg, devices)
    else:
        train_step(cfg, mix, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
