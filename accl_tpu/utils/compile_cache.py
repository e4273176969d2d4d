"""JAX's persistent compilation cache, kept in one fixed place.

Shared by the repo's entry scripts (``chip_smoke.py``, ``bench.py``,
``benchmarks/mp_bandwidth.py``) so that every run of this checkout finds
what an earlier run compiled.
"""
from __future__ import annotations

import os

import jax


def enable(checkout: str) -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``): a fixed path,
    so that the next run of the same checkout hits it. Every compile is
    cached, however short: a Pallas kernel compiles in about a second.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
