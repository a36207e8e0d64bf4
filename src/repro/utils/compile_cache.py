"""JAX's persistent compilation cache, placed from outside the program.

Entry scripts (``chip_smoke.py``, ``examples/*``, ``benchmarks/kernels.py``)
call :func:`enable_compile_cache` once at start-up; the library never does,
so importing ``repro`` changes no JAX configuration.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it and caches there;
  nothing else is configured.
- Otherwise the cache lives at the fixed, git-ignored ``<checkout>/.jax_cache``.
  The directory is part of each entry's key, so it never moves between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
