"""JAX's persistent compilation cache for every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
module sets no other directory.  Otherwise the cache lives in
``<checkout>/.jax_cache``, found from this file's own path: a fixed
directory, so every run of the same checkout finds what an earlier run
compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir
