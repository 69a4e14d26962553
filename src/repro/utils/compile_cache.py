"""Where JAX keeps its persistent compilation cache.

A cache entry is found again only under the same directory path, so the
directory must not move between runs.  ``JAX_COMPILATION_CACHE_DIR``, when
set, wins: JAX reads it itself and nothing here overrides it.  Otherwise the
cache goes to ``<checkout>/.jax_cache`` (git-ignored), a path that depends
on neither a temporary name, a pid nor the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.  Call
    from entry points only, before the first compilation."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
