"""Persistent JAX compilation cache for the program's entry points.

The hop loop compiles one executable per bucket width and the build a
handful more; on a fresh process that is most of a cold run. Entry
points (``chip_smoke.py``, the benchmarks) call :func:`enable` once at
start-up — never at library import — so repeated runs from one checkout
reuse what earlier runs compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    nothing else is set. Otherwise the cache goes to the fixed
    ``.jax_cache/`` at the checkout root: the path is part of the cache
    key, so it must not move between runs."""
    env = os.environ.get(ENV)
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
