"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` once, before their
first compile. Library imports and the tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory inside the checkout: the cache key includes the path,
# so a temporary or per-process directory would never be hit again
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set. Otherwise the cache goes to ``CHECKOUT_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
