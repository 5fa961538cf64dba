"""The system under test, as far as the harness itself touches it: where
its compile cache lives and what its compile manager counted. Everything
else of the program is called from a mode (benchmarks/modes/), through
the entry points a user calls.
"""
from __future__ import annotations


def ensure_compile_cache() -> str:
    """JAX's persistent cache at the program's one place:
    $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache. Returns it."""
    from lightgbm_tpu.compile import ensure_compile_cache
    return ensure_compile_cache()


def compile_counters() -> dict:
    from lightgbm_tpu.compile import get_manager
    return get_manager().snapshot()
