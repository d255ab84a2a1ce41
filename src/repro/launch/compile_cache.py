"""JAX's persistent compilation cache at a fixed path.

A cold 32-layer round program takes a minute or more to compile, and a
process that starts on a fresh machine has no compiled code.  The cache
key includes the cache's path, so the path must not move between runs:
it is ``<checkout>/.jax_cache`` (listed in ``.gitignore``), unless
``JAX_COMPILATION_CACHE_DIR`` is set, in which case JAX reads that
variable itself and nothing here overrides it.

Call :func:`enable_compile_cache` from an entry point before its first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
