"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
:func:`enable_compile_cache` once before their first compile. The package
never calls it at import, so library users and the tests keep whatever
cache setting they chose.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed in-checkout path: the cache key includes the directory, so a cache
# that moves never hits
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here; otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]
