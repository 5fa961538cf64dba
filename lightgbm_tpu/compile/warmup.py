"""Ahead-of-time warmup: compile (or preload) executables off the
critical path.

Three entry points:

- `preload_store_async()` — fired by `engine.train()` before the
  Dataset/Booster build: a daemon thread deserializes every stored
  executable for the current environment into the manager's memory
  cache, overlapping with binning/quantization host work.
- `background_warmup(booster)` — fired after the Booster is built: a
  thread pool compiles every registered-but-uncompiled warmup spec
  concurrently with the first training iterations. Gated (rows >=
  LGBM_TPU_BUCKET_MIN or tpu_warmup=true / LGBM_TPU_WARMUP=1) so small
  jobs and tests don't spawn threads for sub-second compiles.
- `run_warmup(params)` — the `python -m lightgbm_tpu warmup` CLI: build
  the Dataset + Booster exactly as training would (registering every
  entry), compile all specs to completion, persist them, and report.
  A later `train()`/`bench.py` process with the same signature then
  deserializes instead of compiling.
"""
from __future__ import annotations

import atexit
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

from ..utils import log
from . import signature as S
from .manager import CompileManager, SharedEntry, get_manager

# Background threads must never be mid-XLA-call while the interpreter
# tears down its C++ state (PJRT client destruction aborts the process
# with "terminate called without an active exception"). Every thread
# checks `_shutdown` between work items, and the atexit hook set here
# joins them before teardown.
_shutdown = threading.Event()
_bg_threads: List[threading.Thread] = []
_bg_lock = threading.Lock()


def _track(th: threading.Thread) -> threading.Thread:
    with _bg_lock:
        _bg_threads.append(th)
        live = [t for t in _bg_threads if t.is_alive()]
        _bg_threads[:] = live
    return th


@atexit.register
def _join_background_threads() -> None:
    _shutdown.set()
    with _bg_lock:
        threads = list(_bg_threads)
    for th in threads:
        th.join()


def _pending_specs(mgr: CompileManager
                   ) -> List[Tuple[SharedEntry, str, Any, Dict[str, Any]]]:
    out = []
    seen = set()
    for entry in list(mgr.shared.values()):
        # snapshot under the entry lock: learners may still be
        # registering specs while a warmup thread walks the list
        with entry._lock:
            specs = list(entry.specs)
        for args, statics in specs:
            key = entry.key_for(args, statics)
            # dedupe across entries too: signature bucketing can
            # collide specs from different learners (serial/fused/MC
            # variants) onto one key — compile each shared signature
            # exactly once
            if key in seen:
                continue
            seen.add(key)
            if mgr.executables.get(key) is None:
                out.append((entry, key, args, statics))
    return out


def warmup_entries(jobs: Optional[int] = None) -> Dict[str, Any]:
    """Compile every registered warmup spec not already executable;
    blocks until done. Returns a summary dict."""
    mgr = get_manager()
    if not mgr.aot_enabled:
        return {"entries": 0, "compiled": 0, "seconds": 0.0,
                "disabled": True}
    pending = _pending_specs(mgr)
    t0 = time.perf_counter()
    compiled = 0
    if pending:
        workers = max(1, jobs or min(4, len(pending)))

        def _one(item):
            if _shutdown.is_set():
                return None
            entry, key, args, statics = item
            return mgr.acquire(entry, key, args, statics)

        with ThreadPoolExecutor(max_workers=workers) as pool:
            # a failed compile raises out of map() with the compiler's
            # message; only a shutdown skip yields None
            for exe in pool.map(_one, pending):
                compiled += exe is not None
    return {"entries": len(pending), "compiled": compiled,
            "seconds": time.perf_counter() - t0,
            "stats": mgr.snapshot()}


def preload_store_async() -> Optional[threading.Thread]:
    """Deserialize stored executables on a daemon thread; returns the
    thread (None when there is nothing to do)."""
    if os.environ.get("LGBM_TPU_AOT_PRELOAD", "1") == "0":
        return None
    mgr = get_manager()
    if not mgr.aot_enabled or not mgr.preload_keys():
        return None
    th = threading.Thread(
        target=lambda: mgr.preload(should_stop=_shutdown.is_set),
        name="lgbm-aot-preload", daemon=True)
    _track(th)
    th.start()
    return th


def warmup_wanted(config: Any, num_data: int) -> bool:
    env = os.environ.get("LGBM_TPU_WARMUP", "")
    if env in ("0", "false"):
        return False
    if env in ("1", "true") or getattr(config, "tpu_warmup", False):
        return True
    return num_data >= S.bucket_min_rows()


def background_warmup(jobs: Optional[int] = None
                      ) -> Optional[threading.Thread]:
    """Compile pending warmup specs on daemon threads, concurrent with
    the first training iterations."""
    mgr = get_manager()
    if not mgr.aot_enabled:
        return None

    def _run() -> None:
        try:
            summary = warmup_entries(jobs=jobs)
            if summary["entries"]:
                log.debug("Background warmup compiled %d/%d entries in "
                          "%.1fs", summary["compiled"], summary["entries"],
                          summary["seconds"])
        except Exception as exc:
            # the training thread compiles the same entry itself and
            # raises there; this thread only reports
            log.warning("Background warmup failed: %s", exc)

    th = threading.Thread(target=_run, name="lgbm-aot-warmup", daemon=True)
    _track(th)
    th.start()
    return th


def run_warmup(config: Any, params: Dict[str, str]) -> Dict[str, Any]:
    """CLI warmup task: construct the Dataset + Booster exactly as
    `task=train` would (which registers every jit entry point and its
    warmup specs), then compile + persist all of them."""
    import lightgbm_tpu as lgb

    if not config.data:
        raise ValueError("task=warmup requires data= (the dataset file "
                         "whose shapes/params define the executables)")
    clean = {k: v for k, v in params.items() if k not in ("task",)}
    train_set = lgb.Dataset(config.data, params=dict(clean))
    booster = lgb.Booster(params=dict(clean), train_set=train_set)
    summary = warmup_entries()
    mgr = get_manager()
    summary["store_dir"] = mgr.store.env_dir()
    summary["num_data"] = train_set.num_data()
    del booster
    return summary
