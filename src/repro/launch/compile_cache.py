"""JAX's persistent compilation cache for the entry points.

Called from the ``main`` of each entry point (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks/serve_bench.py``), never at import
time and never from tests.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = (pathlib.Path(__file__).resolve().parents[3]
                      / ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and no other directory is set. Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache``, where the next run of the same
    checkout finds what this one wrote.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
