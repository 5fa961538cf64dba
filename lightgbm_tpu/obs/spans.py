"""span(): one scope, five consumers.

A `span` feeds (a) the `utils/timer.py` global table — same names, so
the LGBM_TPU_TIMETAG phase table is unchanged, (b) the active
`MetricsRegistry` phase times when a `phase=` is given, (c) a
`jax.profiler.TraceAnnotation` range named `lgbm:<name>`, on every
call, so a profiler session started by anyone (`profile_dir`, a
benchmark, an operator) shows the program's host scopes on the clock of
the device ops, (d) a complete event in the active runtime `Tracer`
(obs/trace.py), so the Perfetto timeline shows every instrumented scope
in order, and (e) the process-global set-up stage table when a
`stage=` is given (`stage_seconds()`; `setup_line()` is the operator's
view of it). When none of (a), (b), (d), (e) is on, a span is the
annotation around a bare `yield` — a TraceMe with no profiler session
running is a flag test, and no clock is read.

Exception safety: the annotation is the outermost `with`, so it ALWAYS
closes; the consumer writes run in the finally block inside their own
try/finally, and a tracer event is only appended as a fully-formed
[t0, t1] tuple. Spans nest re-entrantly: all pairing state lives in the
generator's locals.

`instrument_kernel` wraps a jitted callable once (at lru-cache build
time) so every dispatch call site is annotated and timed without editing
each call; the disabled fast path is the annotation, one global load
and one `is None` check.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional, Tuple

import jax.profiler as _profiler

from ..utils import timer as _timer
from . import registry as _registry
from . import trace as _trace

ANNOTATION_PREFIX = "lgbm:"

# set-up stage -> [seconds, calls], for the life of the process. Written
# at set-up sites only (O(10) per Booster, never inside update()).
_stages: Dict[str, list] = {}
_stages_lock = threading.Lock()
# what the last `set-up:` line had seen, so that the next reports the gain
_reported: Dict[str, float] = {}


def stage_seconds() -> Dict[str, Tuple[float, int]]:
    """{stage: (seconds, calls)} of every `span(..., stage=...)` this
    process has closed: `construct/*` (io/dataset.py), `state/*` (the
    fused growers). Always on; compile seconds are the compile manager's
    (`compile.get_manager().snapshot()`)."""
    with _stages_lock:
        return {k: (v[0], v[1]) for k, v in _stages.items()}


def _add_stage(stage: str, seconds: float) -> None:
    with _stages_lock:
        slot = _stages.setdefault(stage, [0.0, 0])
        slot[0] += seconds
        slot[1] += 1


def setup_line() -> str:
    """`set-up: construct … (sample …, …), state … (…), compile … (…)`:
    what the stage table and the compile manager's always-on counters
    gained since the last call, in seconds; then `; built: <entry>
    <source> <seconds>` for each build since of a second or more."""
    from ..compile import get_manager
    now = {k: v[0] for k, v in stage_seconds().items()}
    snap = get_manager().snapshot()
    lower = float(snap.get("lowering_s", 0.0))
    now["compile/lower"] = lower
    now["compile/xla"] = float(snap.get("compile_s", 0.0)) - lower
    now["compile/aot_load"] = float(snap.get("aot_load_s", 0.0))
    groups: Dict[str, list] = {}
    with _stages_lock:
        for key, total in now.items():
            group, _, part = key.partition("/")
            groups.setdefault(group, []).append(
                (part, total - _reported.get(key, 0.0)))
        _reported.update(now)
        since = _reported.get("built", 0.0)
        _reported["built"] = time.perf_counter()
    built = [f"{name} {b['source']} {secs:.1f}"
             for name, row in get_manager().snapshot_entries().items()
             for b in row["builds"] if b["at"] > since and (secs := sum(
                 v for k, v in b.items() if k.endswith("_s"))) >= 1.0]
    return "set-up: " + ", ".join(
        f"{group} {sum(dt for _, dt in parts):.1f} s ("
        + ", ".join(f"{part} {dt:.1f}" for part, dt in parts) + ")"
        for group, parts in groups.items()) + (
            "; built: " + ", ".join(built) if built else "")


@contextlib.contextmanager
def span(name: str, phase: Optional[str] = None,
         stage: Optional[str] = None):
    reg = _registry.active()
    gt = _timer.global_timer
    tr = _trace.active_tracer()
    with _profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
        if reg is None and not gt.enabled and tr is None and stage is None:
            yield
            return
        tr_t0 = tr.now_ns() if tr is not None else 0
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            try:
                if stage is not None:
                    _add_stage(stage, dt)
                if gt.enabled:
                    gt.acc[name] += dt
                    gt.cnt[name] += 1
                if reg is not None and phase is not None:
                    reg.add_time(phase, dt)
                    reg.observe_latency(f"lat.phase.{phase}", dt * 1e3)
            finally:
                if tr is not None:
                    tr.complete(name, "phase", tr_t0, tr.now_ns(),
                                {"phase": phase} if phase else None)


@contextlib.contextmanager
def step_span(iteration: int):
    """StepTraceAnnotation wrapper: marks one boosting iteration as an
    XProf "step" so the trace viewer groups device activity per
    iteration, aligned with the JSONL records."""
    with _profiler.StepTraceAnnotation("boosting_iteration",
                                       step_num=int(iteration)):
        yield


def instrument_kernel(fn, phase: str, name: Optional[str] = None,
                      collective: Optional[Tuple] = None):
    """Wrap a (jitted) callable with per-call phase timing + a call
    counter, and optionally collective accounting (`collective` is
    (op_name, payload_bytes_per_call[, mesh_axis]) — bytes are computed
    at wrap time because the op runs inside traced code). Timing is
    host-side dispatch latency: under async dispatch it covers enqueue,
    on the synchronous test path it covers the compute too."""
    label = name or f"kernel/{phase}"
    annotation = ANNOTATION_PREFIX + label
    if getattr(fn, "annotation", None) == annotation:
        fn.annotation = None    # a manager entry: this wrapper names it
    if collective is not None:
        coll_op, coll_bytes = collective[0], int(collective[1])
        coll_axis = collective[2] if len(collective) > 2 else ""

    def wrapper(*args, **kwargs):
        reg = _registry.active()
        tr = _trace.active_tracer()
        if reg is None and not _timer.global_timer.enabled \
                and tr is None:
            with _profiler.TraceAnnotation(annotation):
                return fn(*args, **kwargs)
        tr_t0 = tr.now_ns() if tr is not None else 0
        t0 = time.perf_counter()
        with span(label, phase=phase):
            out = fn(*args, **kwargs)
        if reg is not None:
            reg.inc(f"kernel.{phase}.calls")
            if collective is not None:
                # full collective accounting (latency histogram, axis
                # counters) — same path network.collective_span takes
                reg.record_collective(coll_op, coll_bytes,
                                      time.perf_counter() - t0,
                                      axis=coll_axis)
        if tr is not None and collective is not None:
            args_d = {"bytes": coll_bytes}
            if coll_axis:
                args_d["axis"] = coll_axis
            tr.complete(coll_op, "collective", tr_t0, tr.now_ns(), args_d)
        return out

    wrapper.__name__ = getattr(fn, "__name__", label)
    wrapper.__wrapped__ = fn
    lower = getattr(fn, "lower", None)
    if lower is not None:       # keep AOT .lower() introspection usable
        wrapper.lower = lower
    return wrapper


# -- jax.profiler programmatic trace capture ----------------------------
_PROFILING = False


def start_profiler(profile_dir: str) -> bool:
    global _PROFILING
    if _PROFILING or not profile_dir:
        return False
    try:
        _profiler.start_trace(profile_dir)
        _PROFILING = True
        return True
    except Exception as exc:
        from ..utils import log
        log.warning("profile_dir=%s: could not start jax profiler: %s",
                    profile_dir, exc)
        return False


def stop_profiler() -> None:
    global _PROFILING
    if not _PROFILING:
        return
    try:
        _profiler.stop_trace()
    except Exception:
        pass
    _PROFILING = False
