"""One persistent XLA compile cache, placed from outside.

Every entry point that compiles for the chip (``python -m
paddle_tpu.serving.server``, ``chip_smoke.py``'s children) calls
:func:`enable` once, before its first compilation. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the directory from
it and this module sets no other; where it is not, the cache lives at
``<checkout>/.jax_cache``, a fixed path derived from this file's own (the
path is part of the cache key, so a directory that moves never hits).
"""
from __future__ import annotations

import os

import jax
from jax import monitoring

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The directory the cache is (or would be) kept in."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


class CompileStats:
    """Process-wide compile accounting read from JAX's own monitoring
    events: persistent-cache hits and misses, and seconds spent in the
    backend compiler (a hit spends none)."""

    def __init__(self):
        self.cache_hits = 0
        self.cache_misses = 0
        self.compile_seconds = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_seconds += seconds

    def snapshot(self) -> dict:
        return {"cache_dir": cache_dir(), "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "compile_seconds": round(self.compile_seconds, 3)}


def enable() -> CompileStats:
    """Turn the persistent cache on for this process and start counting.
    Call before the first compilation."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    # the serving path's small programs (the KV writers, the samplers)
    # compile in well under JAX's default one-second floor and would
    # otherwise be recompiled by every process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CompileStats()
