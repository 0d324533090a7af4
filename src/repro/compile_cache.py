"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that run on an accelerator (``chip_smoke.py``,
``benchmarks/common.py``) call :func:`use_compile_cache` before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
the directory is part of the cache key, so it is a fixed path (never a
tempdir, a pid or a time) and a second run from the same checkout finds the
programs the first one compiled.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's compilation cache at ``JAX_COMPILATION_CACHE_DIR`` when
    set, else at :data:`CACHE_DIR`; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
