"""JAX's persistent compilation cache, at a path that does not move.

A cold process recompiles every jitted program; the whole bert-large
train step alone takes tens of seconds.  A later process finds a cached
program only where it was written, so the cache lives at one fixed place:
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), else ``<checkout>/.jax_cache``.
The launchers and ``chip_smoke.py`` call :func:`enable_compile_cache`;
tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory.

    Returns the directory in use.  With ``JAX_COMPILATION_CACHE_DIR`` set
    nothing is changed in code.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
