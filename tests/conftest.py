"""Test environment: force the CPU backend with 8 virtual devices — the
reference's single-node multi-process test pattern (SURVEY.md §4) mapped to
a virtual device mesh. Must run before jax initializes its backend."""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
# EXPORTED (not just config.update) so multiprocessing-spawn children —
# DataLoader workers, launcher toys, shm-ring producers — inherit it: a
# chip belongs to one process at a time, so no child of the test run may
# reach for an accelerator backend.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# fp32 matmuls in tests compare against float64-free numpy oracles
jax.config.update("jax_default_matmul_precision", "highest")
