"""JAX's persistent compilation cache for the entry points.

A full-width train step takes minutes to compile.  The entry points
(``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile, so a second run of
the same programs loads them from disk.  Importing ``repro`` never turns
the cache on: tests keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed cache location inside the checkout; the directory is part of the
#: cache key, so a path that moved between runs would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken its
    directory from it and nothing is changed here.  Otherwise the cache
    goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
