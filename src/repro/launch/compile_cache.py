"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` before its first compile, so their processes
share one cache. A cache directory is part of the cache key, so it must not
move between runs: never a temp, pid- or time-derived path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (gitignored); this file is
# <checkout>/src/repro/launch/compile_cache.py
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it, and no
    other directory is set here; otherwise the checkout's ``.jax_cache/``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
