"""Persistent JAX compilation cache for the program's entry points.

The entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks.run``) call
:func:`enable_compile_cache` once at start-up; library modules and the
test suite never do. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this sets no other directory. Otherwise the cache
lives at one fixed path inside the checkout, ``<repo>/.jax_cache``
(git-ignored): the directory is part of what a later run must find, so
it is never derived from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    # every program, not only those that took over a second: a run on the
    # chip compiles hundreds of small ones, and together they dominate
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
