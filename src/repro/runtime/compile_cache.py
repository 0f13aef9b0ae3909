"""JAX's persistent compilation cache, switched on by the entry points.

A cold process compiles every program again: the embedding backbone,
the proxy trainer, the scoring kernels. The entry points
(``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train``,
``benchmarks/run.py``) call :func:`enable_compile_cache` at start-up —
never at import — so a later process on the same machine finds those
programs on disk.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this function sets nothing. Otherwise the cache goes to one fixed
directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored). The
path is part of the cache's key, so it never holds a PID, a timestamp or
a temp-dir name.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
