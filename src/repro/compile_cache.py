"""Where entry points keep JAX's persistent compilation cache.

Called by the entry points (``chip_smoke.py``, ``benchmarks/run.py``),
never at import time.  The cache key includes its path, so the directory
is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself, and nothing here overrides it), else ``.jax_cache/`` in
the checkout, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Point the persistent compile cache at its fixed directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
