"""Child for the CP-inside-PP parity test: fresh interpreter with the
legacy partitioner from the start (mixing partitioners in one process
aborts XLA's CPU backend)."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax

jax.config.update("jax_platforms", "cpu")
# --shardy: run under the Shardy partitioner (the default going forward).
# Works since the ring body stopped calling jax.lax.axis_index inside the
# nested manual region (its position now arrives as a sharded iota input).
if "--shardy" in sys.argv:
    sys.argv.remove("--shardy")
    jax.config.update("jax_use_shardy_partitioner", True)
else:
    jax.config.update("jax_use_shardy_partitioner", False)

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.jit import TrainStep
from paddle_tpu.optimizer import AdamW
from paddle_tpu.parallel import mesh as mesh_mod


def losses(pp, sep, cp, micro):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    mesh_mod._STATE["mesh"] = None
    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"pp_degree": pp, "sep_degree": sep,
                        "dp_degree": 8 // (pp * sep)}
    fleet.init(is_collective=True, strategy=s)
    hcg = fleet.get_hybrid_communicate_group()
    paddle.seed(52)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=4, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=32,
                      use_recompute=False, context_parallel=cp,
                      pipeline_microbatches=micro)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda loss, _l: loss,
                     opt, mesh=hcg.mesh if (pp > 1 or sep > 1) else None)
    ids = paddle.to_tensor(np.random.RandomState(9).randint(
        0, 64, (8, 16)).astype(np.int32))
    return [float(step.step((ids, ids), (ids,)).value) for _ in range(3)]


if __name__ == "__main__":
    cp = sys.argv[1] if len(sys.argv) > 1 else "ring"
    serial = losses(pp=1, sep=1, cp="", micro=0)
    nested = losses(pp=2, sep=2, cp=cp, micro=2)
    np.testing.assert_allclose(serial, nested, rtol=2e-4, atol=2e-5)
    print(f"CP({cp})-inside-PP parity OK: {serial} == {nested}")
