"""Persistent compilation cache for the entry points.

A cold full-width run compiles every prefill bucket and the decode step
of an unrolled layer stack; JAX's persistent cache keeps those programs
across processes.  Its key includes the cache path, so the path is
fixed: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself and
nothing is overridden here), else ``<repo>/.jax_cache``.

Only entry points call :func:`enable_compile_cache` — importing a module
never switches the cache on, so tests run without it.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<repo>/.jax_cache`` — listed in ``.gitignore``
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory;
    returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
