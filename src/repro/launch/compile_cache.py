"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the stencil server's
``main``) call :func:`enable_compile_cache` once at start-up; importing the
package never does, so tests stay cache-free.  ``JAX_COMPILATION_CACHE_DIR``,
when set, is the cache and nothing else is configured; otherwise the cache
lives at the fixed path ``<checkout>/.jax_cache`` (a fixed path, because the
path is part of the cache key).
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/``.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env           # JAX reads the variable itself
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
