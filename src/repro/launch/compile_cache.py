"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile. A full-width fused serving step takes tens of seconds to compile,
so a second run on the same machine should find it cached.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache goes to one fixed directory inside the
checkout (``<repo>/.jax_cache``, git-ignored): the path is part of what a
later run must find again, so it is never built from a temp name, a pid or
the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
