"""Where JAX's persistent compilation cache lives.

Entry points that compile call ``enable_compile_cache()`` before their
first compile.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache goes to a fixed
directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the
directory is part of the cache key, so it is never built from a temp
name, a pid or the time — a later run in the same checkout finds it.
Tests never call this.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
