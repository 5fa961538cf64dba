"""AOT compile manager (docs/COMPILE_CACHE.md).

Makes compiled XLA executables first-class artifacts: a registry of the
stack's jit entry points, canonical shape bucketing so one executable
serves many datasets, a serialized executable store keyed by
(environment, compile signature, bucketed shapes), and parallel /
background warmup that takes compilation off the training critical
path.

Quick map:

- cachedir.py  — the one cache directory (JAX cache + AOT store root)
- signature.py — buckets, signatures, cache keys
- store.py     — on-disk serialized executables
- manager.py   — registration + AOT-first dispatch + counters
- warmup.py    — preload / background / CLI warmup drivers
"""
from __future__ import annotations

from .cachedir import (aot_store_root, compile_cache_dir,
                       ensure_compile_cache)
from .manager import (CompileManager, JitEntry, SharedEntry, get_manager,
                      reset_manager)
from .signature import (bucket_rows, bucketing_enabled, bucket_min_rows,
                        cache_key, config_signature, environment_key,
                        shape_signature, signature_digest)
from .store import CorruptBlobError, ExecutableStore, store_enabled
from .warmup import (background_warmup, preload_store_async, run_warmup,
                     warmup_entries, warmup_wanted)

__all__ = [
    "aot_store_root", "compile_cache_dir", "ensure_compile_cache",
    "CompileManager", "JitEntry", "SharedEntry", "get_manager",
    "reset_manager", "bucket_rows", "bucketing_enabled", "bucket_min_rows",
    "cache_key", "config_signature", "environment_key", "shape_signature",
    "signature_digest", "CorruptBlobError", "ExecutableStore",
    "store_enabled", "background_warmup", "preload_store_async",
    "run_warmup", "warmup_entries", "warmup_wanted",
]
