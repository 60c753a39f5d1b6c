"""Persistent XLA compile cache for the entry points that run on a chip.

A cold process compiles every kernel and fused wave program again; with
the cache on, a later run of the same checkout loads them instead.  The
chip smoke test and the benchmark runner call :func:`enable_compile_cache`
before their first compile.  The tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "enable_compile_cache"]

#: when set, JAX reads this variable itself and the code configures nothing
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, that directory is the cache
    and nothing is set in code.  Otherwise the cache is the fixed
    ``<checkout>/.jax_cache`` (a moving path would never be found again).
    JAX's own threshold decides which programs are kept: writing every
    small program costs a cold run more than a warm run saves.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
