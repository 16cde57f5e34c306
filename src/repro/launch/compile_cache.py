"""Where JAX keeps its persistent compile cache.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
``enable_compile_cache()`` once at start-up; importing this module does
nothing, so the tests run without a persistent cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no other path.
* Not set: the cache goes to ``<checkout>/.jax_cache``.  The path is part
  of what a cached entry is found by, so it is fixed: never built from a
  temporary name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
