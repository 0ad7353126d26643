"""JAX's persistent compilation cache for the repository's entry points.

``enable_compile_cache()`` is called by the programs a user runs
(``chip_smoke.py``, ``examples/traffic_speed_e2e.py``,
``benchmarks/run.py``) before their first compile; the library never calls
it on import and the tests never call it.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and no other directory is set here.  Otherwise
the cache lives at the fixed ``<checkout>/.jax_cache`` (ignored by git): the
directory is part of the cache's key, so it must never move between runs.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  The size and
    compile-time thresholds drop to nothing, so the fused kernels, which
    compile in a second or two each, are cached too."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
