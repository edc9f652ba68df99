"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.mine``,
``repro.launch.serve``, ``benchmarks.run``) call
:func:`configure_compile_cache` once at start-up; importing this module
changes nothing.  The cache path is part of the cache key, so it is a
fixed directory — never a temporary path, a pid or a time.
"""
from __future__ import annotations

# repro: host-module
# Start-up configuration only; runs before anything is traced.

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def configure_compile_cache() -> str:
    """Return the directory compiled programs are cached in.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here.  Otherwise the cache goes to the fixed
    in-checkout directory ``.jax_cache`` (git-ignored).
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
