"""JAX's persistent compilation cache, kept at one fixed place.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing. The cache key includes
the directory, so the directory must not move between runs: it is either
what ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that itself) or
``<checkout>/.jax_cache``, derived from this file's location.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Return the directory the compile cache lives in, setting it when the
    environment does not. JAX is not imported here: an entry point that
    never compiles (most CLI commands) keeps its fast start, and JAX reads
    the variable when it is first imported, here or in a child process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if "jax" in sys.modules:  # already imported: its config read the env then
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path
