"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call ``enable_compile_cache()`` before their first compile; importing
``repro`` never turns the cache on, so the tests stay silent.

 - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this sets
   nothing.
 - otherwise: the fixed ``<checkout>/.jax_cache`` (git-ignored).  The
   path is part of the cache key, so it never comes from a temporary
   name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
