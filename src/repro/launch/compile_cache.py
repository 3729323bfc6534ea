"""Persistent compile cache for the programs that start on the chip.

``JAX_COMPILATION_CACHE_DIR``, where it is set, is read by JAX itself
and wins.  Otherwise the cache goes to ``.jax_cache/`` at the root of
the checkout: a fixed path, so a later run in the same checkout finds
what an earlier one compiled (the path is part of the cache key, so it
never comes from a temporary name, a process id or the time).

``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks/run.py`` call
``enable_compile_cache()`` at start-up.  Importing the package and the
tests never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
