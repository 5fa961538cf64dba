"""Where compiled programs are kept — one rule for every entry point.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's persistent compilation
cache already points there and nothing is set in code. Otherwise the
cache is the fixed ``<checkout>/.jax_cache`` (the path is part of the
cache key, so a directory that moves never hits). The AOT executable
store (store.py) lives in the ``aot`` subdirectory of the same place.
"""
from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def aot_store_root() -> str:
    return os.path.join(compile_cache_dir(), "aot")


def ensure_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()`` unless the
    environment already did. Returns the directory in use."""
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return compile_cache_dir()
