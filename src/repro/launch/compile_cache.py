"""The persistent compilation cache every entry point shares.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it as its cache
directory and nothing here overrides it.  Otherwise the cache lives at one
fixed directory inside the checkout (listed in ``.gitignore``): the path
is part of each entry's key, so a directory that moved would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The in-checkout cache directory used when the environment names none.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory.  Every compile is kept, however short: a serving warmup is
    hundreds of sub-second compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
