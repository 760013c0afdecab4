"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` from ``main()`` (never at
import).  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read
it and the helper sets nothing.  Otherwise the cache goes to the fixed
``.jax_cache/`` at the repo root: the directory is part of each entry's
key, so a path that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
