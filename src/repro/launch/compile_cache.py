"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``, ``repro.launch.train``) call :func:`enable_compile_cache` from ``main``; importing this module
changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
#: It is fixed, not per process: the path is part of what a later run has
#: to find again.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    that directory stays in charge; otherwise the cache goes to
    :data:`REPO_CACHE_DIR` (git-ignored)."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
