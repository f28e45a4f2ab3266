"""Where JAX keeps its persistent compilation cache, for every entry point.

``enable_compile_cache()`` points the cache at ``$JAX_COMPILATION_CACHE_DIR``
when that is set, and otherwise at ``.jax_cache/`` in the checkout (listed
in ``.gitignore``).  The path is part of each entry's key, so it is fixed:
never a temporary, pid- or time-derived directory.  Every compile is
cached, however small — a DSE run is many medium compiles, and on the TPU
a single search kernel takes tens of seconds to build.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    cache_dir = os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
