"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on import and keeps
its cache there; nothing here overrides it.  Otherwise the cache goes to the
fixed directory ``<repo>/.jax_cache``: the path is part of what a cache hit
matches, so it is never derived from a temporary name, a pid or the time.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache`; importing ``repro`` never does, so library
users and the tests compile without it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
