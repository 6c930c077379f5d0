"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
calls :func:`enable_compile_cache` once, before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is set in code — whoever runs the program owns the cache.
* unset: ``<checkout>/.jax_cache``, a fixed path.  The path is part of the
  cache's key, so a directory named after a pid, a timestamp or a temp
  dir would never hit.

:class:`CompileStats` counts what the cache did (hits, misses) and the
seconds spent in backend compiles, from JAX's own monitoring events.
"""

from __future__ import annotations

import os

import jax
from jax import monitoring

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_COMPILE = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileStats:
    """Live counters of cache hits / misses and backend-compile seconds
    (cache retrievals included) since construction."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_):
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **_):
        if event == _COMPILE:
            self.compile_s += duration

    def as_dict(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "compile_s": self.compile_s}
