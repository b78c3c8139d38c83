"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that compile large programs (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before their
first compile, so repeated runs on the same machine reuse executables.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — listed in .gitignore. Fixed, never a temporary
# name, a pid or a time, so that a later run finds what an earlier one kept.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is overridden here; otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
