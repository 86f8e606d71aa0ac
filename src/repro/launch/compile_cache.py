"""Where the entry points keep JAX's persistent compilation cache.

A cold TPU compile of the serve solve takes tens of seconds; the
persistent cache turns every later start of the same program into a file
read. The cache key includes the cache path, so the path must be fixed: a
temporary, per-process or time-stamped directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache (listed in .gitignore); this file is
# <repo>/src/repro/launch/compile_cache.py
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
    sets nothing. Otherwise the cache goes to `DEFAULT_CACHE_DIR`.
    Entry points (`launch/serve.py`, `benchmarks/run.py`, `chip_smoke.py`)
    call this before their first compile; library code and tests never do.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
