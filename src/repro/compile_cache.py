"""Persistent XLA compilation cache for the entry points.

A cold run on a TPU recompiles every program (the k-means build alone
takes tens of seconds to compile), so the entry points (``chip_smoke.py``,
``repro.launch.serve``, the benchmarks) call ``enable()`` before their
first compile.  Importing ``repro`` does not: tests compile for chips
that are described, not attached, and those entries cannot be read back.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: fixed, gitignored cache directory at the checkout root.  The path is
#: part of the cache key, so it never carries a temp name, pid or time.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here; otherwise the cache goes to ``DEFAULT_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
