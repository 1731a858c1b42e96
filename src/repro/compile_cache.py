"""One place that points JAX's persistent compilation cache somewhere.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` once at start-up;
importing ``repro`` never does. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already uses that directory and nothing else is set. Otherwise
the cache lives at a fixed path inside the checkout, ``.jax_cache/``
(git-ignored): the path is part of what a cached program is found by,
so it must not move between runs.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


__all__ = ["enable_compile_cache", "DEFAULT_DIR"]
