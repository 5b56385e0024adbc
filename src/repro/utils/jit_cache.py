"""Persistent jit-compilation cache switch, shared by the launchers,
chip_smoke.py and the benchmark harness: compile each program once per
cache directory, not once per process.

The directory is JAX_COMPILATION_CACHE_DIR when that is set (CI restores
it between runs), and otherwise the fixed in-checkout
`<repo>/.jax-compilation-cache` (listed in .gitignore). The path is part of
the cache's key, so it is never derived from a temp dir, pid or time.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_CACHE_DIR = (pathlib.Path(__file__).resolve().parents[3]
                     / ".jax-compilation-cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.
    Safe to call repeatedly."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every trace, however small/fast — wall time here is dominated
    # by many short compiles, which the defaults would skip
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
