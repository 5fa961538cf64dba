"""Runtime trace timeline: bounded ring buffer -> Perfetto trace.json.

The registry (obs/registry.py) answers "how much, in total"; this
module answers "WHEN, and in what order" — the per-iteration timeline
that docs/ROADMAP.md item 5 (async pipelined boosting) needs to judge
where the host actually blocks. Mirrors the reference's per-phase
`Common::Timer` breakdown (common.h:1054), but as structured events
rather than an end-of-run table.

Design constraints, in order:

- **Bounded memory.** Events land in a `collections.deque(maxlen=N)`
  ring: a million-iteration run keeps the LAST N events and counts the
  evictions (`dropped`), so the tracer can stay on for the whole run.
- **Low overhead.** One module-global load + `is None` check on the
  disabled path (same discipline as the active registry); an enabled
  append is two `perf_counter_ns` reads and a tuple append — no dict
  churn, no locks (deque.append is atomic under the GIL).
- **Attribution.** Sync events record the innermost *package* call
  site via the same stack-walk the tpulint runtime cross-check uses
  (`analysis.runtime_check.package_site`), so every runtime host block
  maps onto the static sync-point inventory.

Event kinds (Chrome/Perfetto trace-event JSON, `ph` field):

- "X" complete events: phases (cat "phase"), iterations (cat
  "iteration"), syncs (cat "sync"), collectives (cat "collective"),
- "C" counter events: memory samples (cat "mem"),
- "i" instant events: markers (cat "mark").

`export()` writes `{"traceEvents": [...]}` — loadable directly in
https://ui.perfetto.dev or chrome://tracing. Timestamps are in
microseconds relative to tracer construction (monotonic clock).
"""
from __future__ import annotations

import contextlib
import json
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

# one row (track) per event family so the Perfetto view groups them
_TID_COUNTER = 0     # counter tracks render separately anyway
_TID_PHASE = 1
_TID_SYNC = 2
_TID_COLLECTIVE = 3
_TID_ITERATION = 4
_TRACK_NAMES = {
    _TID_PHASE: "phases",
    _TID_SYNC: "host syncs",
    _TID_COLLECTIVE: "collectives",
    _TID_ITERATION: "iterations",
}

_CAT_TID = {
    "phase": _TID_PHASE,
    "sync": _TID_SYNC,
    "collective": _TID_COLLECTIVE,
    "iteration": _TID_ITERATION,
}


# tpulint: thread-ok(deque.append with maxlen is atomic; dropped/events_total are loose tallies)
class Tracer:
    """Bounded ring buffer of trace events.

    Events are stored as plain tuples
    ``(ph, name, cat, ts_ns, dur_ns, iteration, args)`` — `ph` is the
    Chrome trace-event phase ("X" complete / "C" counter / "i"
    instant), timestamps are `time.perf_counter_ns()` relative to the
    tracer's `t0_ns`, `args` is a small dict or None.
    """

    def __init__(self, capacity: int = 262144) -> None:
        self.capacity = max(16, int(capacity))
        self.buf: deque = deque(maxlen=self.capacity)
        self.dropped = 0
        self.t0_ns = time.perf_counter_ns()
        self.iteration = -1          # set by TelemetrySession per iter
        # when set, sync events are attributed to THIS iteration instead
        # of the current one — trailing fetches (pipelined boosting)
        # resolve during iteration t+1 but belong to the dispatch at t
        self.sync_attr_iteration: Optional[int] = None
        self.events_total = 0

    # -- recording ------------------------------------------------------
    def _append(self, ev: Tuple) -> None:
        if len(self.buf) == self.capacity:
            self.dropped += 1
        self.events_total += 1
        self.buf.append(ev)

    def now_ns(self) -> int:
        return time.perf_counter_ns() - self.t0_ns

    def complete(self, name: str, cat: str, t0_ns: int, t1_ns: int,
                 args: Optional[Dict[str, Any]] = None) -> None:
        """One finished [t0, t1] scope (ph "X"). t0/t1 are `now_ns()`
        values captured by the caller — begin/end pairing happens in
        the caller's locals, so an exception between begin and end can
        drop the event but can never leave an unpaired begin in the
        buffer."""
        self._append(("X", name, cat, t0_ns, max(0, t1_ns - t0_ns),
                      self.iteration, args))

    def counter(self, name: str, value: float,
                series: str = "value") -> None:
        self._append(("C", name, "mem", self.now_ns(), 0,
                      self.iteration, {series: value}))

    def instant(self, name: str, cat: str = "mark",
                args: Optional[Dict[str, Any]] = None) -> None:
        self._append(("i", name, cat, self.now_ns(), 0,
                      self.iteration, args))

    def sync(self, func: str, site: Optional[Tuple[str, int]],
             t0_ns: int, t1_ns: int, nbytes: int = -1) -> None:
        """One host-blocking call (device_get / block_until_ready),
        attributed to its package call site so runtime events join the
        tpulint static inventory (analysis/sync_points.py)."""
        if site is not None:
            name = f"{func}@{site[0]}:{site[1]}"
            args: Dict[str, Any] = {"site": f"{site[0]}:{site[1]}"}
        else:
            name, args = func, {}
        if nbytes >= 0:
            args["bytes"] = nbytes
        it = self.iteration if self.sync_attr_iteration is None \
            else self.sync_attr_iteration
        self._append(("X", name, "sync", t0_ns, max(0, t1_ns - t0_ns),
                      it, args))

    # -- export ---------------------------------------------------------
    def to_perfetto(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (dict form). Process id 0 is used
        single-host; multi-host runs export per-process files whose pid
        is the jax process index."""
        pid = 0
        try:
            import jax
            pid = int(jax.process_index())  # tpulint: sync-ok(export-time only: to_perfetto runs once at session close, never inside the iteration loop — the hot edge is a name-collision on close() via JsonlSink._disable)
        except Exception:
            pass
        events: List[Dict[str, Any]] = [
            {"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
             "args": {"name": f"lightgbm_tpu host {pid}"}},
        ]
        for tid, tname in _TRACK_NAMES.items():
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": tname}})
        for ph, name, cat, ts_ns, dur_ns, it, args in self.buf:
            ev: Dict[str, Any] = {
                "ph": ph, "name": name, "cat": cat, "pid": pid,
                "tid": _CAT_TID.get(cat, _TID_COUNTER),
                "ts": ts_ns / 1e3,          # Perfetto wants microseconds
            }
            if ph == "X":
                ev["dur"] = dur_ns / 1e3
            if ph == "i":
                ev["s"] = "t"               # thread-scoped instant
            a = dict(args) if args else {}
            if ph != "C" and it >= 0:
                a["iteration"] = it
            if a:
                ev["args"] = a
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "events_total": self.events_total}}

    def export(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.to_perfetto(), fh)

    def __len__(self) -> int:
        return len(self.buf)


# -- process-global active tracer (mirrors registry.activate/active) ----
_ACTIVE: Optional[Tracer] = None


def activate_tracer(tr: Tracer) -> Tracer:
    global _ACTIVE
    _ACTIVE = tr
    return tr


def deactivate_tracer(tr: Optional[Tracer] = None) -> None:
    """Deactivate the active tracer (or only `tr`, when given and still
    active — nested sessions unwind safely)."""
    global _ACTIVE
    if tr is None or _ACTIVE is tr:
        _ACTIVE = None


def active_tracer() -> Optional[Tracer]:
    return _ACTIVE


@contextlib.contextmanager
def sync_attribution(iteration: Optional[int]):
    """Attribute sync events recorded in this scope to `iteration`
    (the DISPATCH iteration of a trailing fetch), not the iteration the
    fetch happens to resolve in. No-op when no tracer is active or
    `iteration` is None."""
    tr = _ACTIVE
    if tr is None or iteration is None or iteration < 0:
        yield
        return
    prev = tr.sync_attr_iteration
    tr.sync_attr_iteration = int(iteration)
    try:
        yield
    finally:
        tr.sync_attr_iteration = prev


# -- runtime sync tracing ------------------------------------------------
# Patches jax.device_get / jax.block_until_ready for the session so
# every hot-loop host block is timed and attributed. Reuses the
# package_site stack walk of analysis/runtime_check.py (the runtime
# cross-check that validates the static sync classification), with this
# obs subpackage skipped the same way analysis/ skips itself. Implicit
# np.asarray/__array__ transfers cannot be patched on pybind array
# types (same limitation as record_device_gets).
_SYNC_PATCH: Optional[Tuple[Any, Any]] = None


def _payload_bytes(tree: Any) -> int:
    """Best-effort payload size of a device_get argument. Guarded per
    leaf: a donated (deleted) buffer raises from `.nbytes`, and one bad
    leaf must not zero out the whole payload attribution — nor, worse,
    force a sync by touching buffer contents (metadata only here)."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:
        return -1
    total = 0
    for x in leaves:
        try:
            total += int(getattr(x, "nbytes", 0) or 0)
        except Exception:
            continue
    return total


def install_sync_tracing() -> bool:
    """Monkeypatch the explicit sync channel; no-op when already
    installed. Returns True when the patch is active after the call."""
    global _SYNC_PATCH
    if _SYNC_PATCH is not None:
        return True
    try:
        import jax
        from ..analysis.runtime_check import package_site
    except Exception:
        return False

    real_get, real_block = jax.device_get, jax.block_until_ready
    from . import registry as _registry
    reg_active = _registry.active

    def traced_device_get(*args, **kwargs):
        tr = _ACTIVE
        reg = reg_active()
        if tr is None and reg is None:
            return real_get(*args, **kwargs)
        t0 = time.perf_counter_ns()
        try:
            return real_get(*args, **kwargs)
        finally:
            t1 = time.perf_counter_ns()
            if reg is not None:
                # fetch-latency histogram (schema minor 11) — fed even
                # without a tracer, so `obs_port`-only sessions still
                # expose lat.fetch.* percentiles
                reg.observe_latency("lat.fetch.device_get", (t1 - t0) / 1e6)
            if tr is not None:
                tr.sync("device_get",
                        package_site(skip_dirs=("analysis", "obs")),
                        t0 - tr.t0_ns, t1 - tr.t0_ns,
                        _payload_bytes(args[0] if args else None))

    def traced_block_until_ready(*args, **kwargs):
        tr = _ACTIVE
        reg = reg_active()
        if tr is None and reg is None:
            return real_block(*args, **kwargs)
        t0 = time.perf_counter_ns()
        try:
            return real_block(*args, **kwargs)
        finally:
            t1 = time.perf_counter_ns()
            if reg is not None:
                reg.observe_latency("lat.fetch.block_until_ready",
                                    (t1 - t0) / 1e6)
            if tr is not None:
                tr.sync("block_until_ready",
                        package_site(skip_dirs=("analysis", "obs")),
                        t0 - tr.t0_ns, t1 - tr.t0_ns)

    jax.device_get = traced_device_get
    jax.block_until_ready = traced_block_until_ready
    _SYNC_PATCH = (real_get, real_block)
    return True


def uninstall_sync_tracing() -> None:
    global _SYNC_PATCH
    if _SYNC_PATCH is None:
        return
    real_get, real_block = _SYNC_PATCH
    try:
        import jax
        jax.device_get = real_get
        jax.block_until_ready = real_block
    except Exception:
        pass
    _SYNC_PATCH = None


# -- multi-rank trace merge ----------------------------------------------
def merge_trace_events(per_rank_events: List[List[Dict[str, Any]]]
                       ) -> Dict[str, Any]:
    """Merge per-rank trace-event lists into ONE Perfetto timeline with
    per-rank process tracks: input r becomes pid r (whatever pid the
    producing host wrote — files exported on different hosts can all
    carry their own process_index, or all carry 0 when each host thought
    itself alone), and the per-category track machinery (`_TRACK_NAMES`)
    is re-emitted per pid so every rank gets its own named phase / sync /
    collective / iteration rows."""
    merged: List[Dict[str, Any]] = []
    for rank, events in enumerate(per_rank_events):
        merged.append({"ph": "M", "pid": rank, "tid": 0,
                       "name": "process_name",
                       "args": {"name": f"lightgbm_tpu rank {rank}"}})
        merged.append({"ph": "M", "pid": rank, "tid": 0,
                       "name": "process_sort_index",
                       "args": {"sort_index": rank}})
        for tid, tname in _TRACK_NAMES.items():
            merged.append({"ph": "M", "pid": rank, "tid": tid,
                           "name": "thread_name", "args": {"name": tname}})
        for ev in events:
            if ev.get("ph") == "M":
                continue            # replaced by the per-rank metadata
            ev = dict(ev)
            ev["pid"] = rank
            merged.append(ev)
    return {"traceEvents": merged, "displayTimeUnit": "ms",
            "otherData": {"merged_ranks": len(per_rank_events)}}


def merge_trace_files(paths: List[str], out_path: str) -> Dict[str, Any]:
    """`trace-report --merge r0.json r1.json ...`: load each rank's
    exported trace (traceEvents dict or bare event array), merge, write
    `out_path`, return the merged document."""
    per_rank = []
    for path in paths:
        with open(path) as fh:
            doc = json.load(fh)
        per_rank.append(doc["traceEvents"] if isinstance(doc, dict)
                        else doc)
    merged = merge_trace_events(per_rank)
    with open(out_path, "w") as fh:
        json.dump(merged, fh)
    return merged


# -- device memory sampling ----------------------------------------------
def live_array_bytes() -> int:
    """Total bytes of live jax arrays in this process — the one
    HBM-footprint estimator every consumer shares (TelemetrySession
    per-iteration sampling, scripts/sparse_scale.py accounting). It
    counts this process's jax arrays on every backend; the allocator's
    own view (compiler scratch included) is
    `device.memory_stats()["peak_bytes_in_use"]`, which chip_smoke.py
    prints. Returns -1 when jax is unavailable."""
    try:
        import jax
        return int(sum(int(getattr(a, "nbytes", 0) or 0)
                       for a in jax.live_arrays()))
    except Exception:
        return -1
