"""Where JAX keeps its persistent compilation cache for this repo's scripts.

The cache path is part of what JAX matches on, so it must not move between
runs: a temp name, a pid or a timestamp would make every run miss.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory used.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and
    nothing is changed. Otherwise the cache goes to ``.jax_cache/`` at the
    repository root. Call it from a script's ``main()``, never at import.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
