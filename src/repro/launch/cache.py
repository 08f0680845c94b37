"""Persistent compilation cache for the entry points that drive a device.

Entry points call :func:`enable_compile_cache` once, before their first
compile; library modules never call it, so importing them changes no JAX
setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself and
  this sets no other.
* Unset: the cache goes to ``.jax_cache/`` at the root of the checkout, a
  fixed (gitignored) path, so a second run of the same program finds it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
