"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/*.py``, ``launch/*.py``,
the supervisor's child) call :func:`enable` once, before their first
compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this sets nothing.  Otherwise the cache goes to ``.jax_cache`` at
the checkout root — a fixed path, because the directory is part of what
makes a cached entry findable again (``.gitignore`` lists it).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
