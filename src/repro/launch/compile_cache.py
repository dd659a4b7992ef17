"""JAX's persistent compilation cache, configured in one place.

Every entry point (``launch.train``, ``launch.serve``, ``chip_smoke.py``,
the ``benchmarks/`` scripts) calls :func:`enable` before it compiles:

  * if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing at all: the environment decides the cache's policy;
  * otherwise the cache lives at the fixed ``<checkout>/.jax_cache/``
    (listed in ``.gitignore``; the path is part of the cache key, so it is
    never a temp name, a pid or a time) and programs are cached whatever
    their compile time, so a second identical run compiles nothing.

Only a TPU run is cached: on the CPU (tests, interpret mode) this sets
nothing, since XLA:CPU results read back from the cache are checked
against the host's CPU features and warn on every load. Call it after
argument parsing: it starts the backend.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_DIR", "enable"]

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str | None:
    """Turn the persistent cache on for a TPU run; returns the directory in
    use (None off the TPU)."""
    if jax.default_backend() != "tpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
