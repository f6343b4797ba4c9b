"""JAX's persistent compilation cache for the entry points.

Each entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) calls :func:`enable_compile_cache` once, before it
compiles anything; importing ``repro`` sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Fixed in-checkout location (ignored by git). The cache key includes
#: the path, so a directory that moved between runs would never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and this
    changes nothing; otherwise the cache goes to :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
