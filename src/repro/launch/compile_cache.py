"""JAX's persistent compilation cache at a fixed place.

Entry points that drive the chip (``chip_smoke.py``, ``benchmarks.run``)
call ``enable_compile_cache()`` before their first compile; importing this
module changes nothing. The cache directory is part of the cache key, so it
never moves between runs: ``JAX_COMPILATION_CACHE_DIR`` where it is set
(JAX reads the variable itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
