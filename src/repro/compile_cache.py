"""JAX persistent compilation cache for the device-touching entry points.

A cold predictor fit compiles for tens of seconds, so every entry point
that drives the engine (the edge CLI, the benchmark harness, the examples
and ``chip_smoke.py``) calls ``enable_compile_cache()`` before its first
compile.  Library modules never call it: importing the package must leave
JAX's configuration untouched.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads that variable
itself, so nothing is set here.  Otherwise the cache lives at a fixed
directory inside the checkout (``.jax_cache``, git-ignored) — a fixed
path, because the path is part of what a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: in-checkout cache directory used when the environment names none
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
