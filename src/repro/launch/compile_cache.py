"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``) call :func:`enable_compile_cache` from ``main()``,
never at import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this sets nothing.  Otherwise the cache goes to ``.jax_cache/``
at the root of this checkout — a path fixed by the file's own location, so
a second run from the same checkout finds the first run's entries (the
directory is part of the cache key; a temporary, per-process or timestamped
path would never hit).  Git ignores the directory.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "checkout_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> Path:
    """``<checkout root>/.jax_cache`` (this file is
    ``<root>/src/repro/launch/compile_cache.py``)."""
    return Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory: the environment's when ``JAX_COMPILATION_CACHE_DIR`` is
    set (left to JAX), else :func:`checkout_cache_dir`."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    path = str(checkout_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
