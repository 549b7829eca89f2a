"""JAX's persistent compilation cache: one rule for every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other cache. Otherwise the cache lives at a fixed path
inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is
part of the cache key, so it is never derived from a temporary name, a
process id or the time. Call `enable_compile_cache` before the first
compilation — from a script's ``main``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: ``<repo>/.jax_cache`` — this file is ``<repo>/src/repro/compile_cache.py``
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(CACHE_ENV) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `compile_cache_dir`
    (a no-op where ``JAX_COMPILATION_CACHE_DIR`` is set) and return it."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
