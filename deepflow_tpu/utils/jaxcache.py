"""Where JAX keeps its persistent compile cache for this repo's jobs.

One helper for the chip smoke, the example job and bench.py's device
phase. The agent never calls it: it must not change the profiled job's
JAX config. When JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself
and nothing here changes; otherwise the cache lives at a fixed path in
the checkout (the path is part of the cache key, so it must not move
between runs).
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory compiled programs land in (no JAX import)."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Point JAX at cache_dir() unless the environment already does."""
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return cache_dir()
