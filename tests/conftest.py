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

import gc  # noqa: E402

import pytest  # noqa: E402


def _memory_maps():
    """(this process's memory mappings, the kernel's limit on them), or
    ``(0, 0)`` where ``/proc`` does not say."""
    try:
        with open("/proc/self/maps") as f, \
                open("/proc/sys/vm/max_map_count") as limit:
            return sum(1 for _ in f), int(limit.read())
    except (OSError, ValueError):
        return 0, 0


@pytest.fixture(autouse=True, scope="module")
def _compiled_programs_stay_under_the_map_limit():
    """A worker process keeps every program it compiled, about 19 memory
    mappings each, and ``mmap`` fails at ``vm.max_map_count`` (65,530): the
    compiler then dies of SIGSEGV or SIGABRT inside ``backend_compile``, the
    worker goes down and the run hangs to its cap (seen at 86-97 % of the
    suite under six workers, a busy worker passing 35,000 mappings eight
    minutes in: PERF.md section 6, PR 53). So when a test module ends with
    the process past half the limit, JAX's caches are dropped, which unmaps
    their programs; what a later module shares with an earlier one
    (``serving_support.programs``) compiles again."""
    yield
    maps, limit = _memory_maps()
    if limit and maps > limit // 2:
        jax.clear_caches()
        gc.collect()
