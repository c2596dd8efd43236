"""JAX's persistent compilation cache, kept at one fixed directory.

The directory is part of what the cache looks up, so a path built from a
temp name, a PID or the time never hits again.  ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself and nothing here overrides it);
otherwise the cache lives in ``.jax_cache`` at the root of the checkout,
which git ignores.  Entry points call :func:`use_compile_cache` once, before
their first compilation.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
