"""Where entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so a directory that moves between
runs never hits.  :func:`enable_compile_cache` is called by the mains
(``chip_smoke.py``, ``benchmarks``, ``examples``); library modules never
call it, and importing this module configures nothing.
"""
from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (listed in .gitignore): src/repro/launch/cache.py
# sits three directories below the checkout root
_IN_CHECKOUT = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed in-checkout
    ``.jax_cache`` directory."""
    return os.environ.get(CACHE_ENV) or str(_IN_CHECKOUT)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    no other directory is configured here."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
