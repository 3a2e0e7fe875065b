"""Where JAX's persistent compilation cache lives.

Every entry point calls :func:`enable_compile_cache` before its first
compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins (JAX reads it
itself and this module sets nothing); otherwise the cache is the fixed
``.jax_cache`` directory of the checkout.  The path is part of the cache
key, so it never depends on a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> Path:
    return Path(os.environ[ENV]) if os.environ.get(ENV) else CHECKOUT_CACHE


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
