"""JAX's persistent compilation cache, for the process that holds the chip.

Every chip run otherwise compiles each program from cold. The cache
directory is part of what makes an entry findable again, so it never
moves between runs: ``JAX_COMPILATION_CACHE_DIR`` where the environment
sets it (JAX reads that variable itself), else :data:`CACHE_DIR`, a
fixed directory inside the checkout that ``.gitignore`` lists.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program this process
    compiles (no minimum compile time) and return its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
