"""Persistent compilation cache placement for the entry points.

A run finds what an earlier run compiled only if both use the same
directory, so it never moves: ``JAX_COMPILATION_CACHE_DIR`` when it is
set (JAX reads it itself, and nothing here overrides it), else one fixed
``.jax_cache/`` directory at the root of the checkout.  Entry
points (``chip_smoke.py``, ``benchmarks/run.py``) call this before they
compile anything; importing the library never touches the setting.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(root: str) -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV) or os.path.join(
        os.path.abspath(root), ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: a chip call starts cold, and the small eager
    # programs of set-up add up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
