"""What the program says about itself, read by the layer metrics that came
after the harness: the `lgbm.*` scope of every device op, the `lgbm:` host
spans, and the set-up stage table.

A device op's scope is not in what `jax.profiler.ProfileData` shows of the
op line (an event's stats there are its device offset and duration, its
name the HLO text without `metadata={...}`). It is the stat `tf_op` of the
plane's event metadata, e.g.

    jit(_entry_train_iter)/while/body/lgbm.partition/jit(partition_pallas2)/partition_pallas2/pallas_call:

and is read here from the raw `.xplane.pb` with a walk over the protobuf
wire format (varints and length-delimited fields are all it meets), so
that the measuring machine needs no protobuf package. Field numbers, from
tsl/profiler/protobuf/xplane.proto: XSpace.planes=1; XPlane: name=2,
event_metadata=4, stat_metadata=5 (maps: key=1, value=2); XEventMetadata:
name=2, stats=5; XStatMetadata: id=1, name=2; XStat: metadata_id=1,
str_value=5, ref_value=7 (a ref points into stat_metadata).

A program that has no scopes, no `lgbm:` spans or no stage table (the
parent of the PR that added them) gives every reader here None.
"""
from __future__ import annotations

import os

from . import xplane

SCOPE_STAT = "tf_op"
SCOPE_PREFIX = "lgbm."
SPAN_PREFIX = "lgbm:"
LOOP_STAGES = ("lgbm.partition", "lgbm.hist", "lgbm.split_scan")


# ------------------------------------------------------- the wire format

def _varint(b, i):
    v = s = 0
    while True:
        c = b[i]
        i += 1
        v |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return v, i


def fields(b):
    """(field number, wire type, value) of one message."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_values(plane, field: int):
    """The values of a map<int64, Message> field of the plane."""
    for num, wt, entry in fields(plane):
        if num == field and wt == 2:
            for k, kwt, v in fields(entry):
                if k == 2 and kwt == 2:
                    yield v


def _plane_scopes(plane) -> tuple:
    """(plane name, {instruction: tf_op or None}, [colliding names])."""
    name = ""
    stat_names = {}
    for num, wt, v in fields(plane):
        if num == 2 and wt == 2:
            name = _text(v)
    for meta in _map_values(plane, 5):
        sid, sname = None, ""
        for num, wt, v in fields(meta):
            if num == 1 and wt == 0:
                sid = v
            elif num == 2 and wt == 2:
                sname = _text(v)
        stat_names[sid] = sname
    scope_ids = {i for i, n in stat_names.items() if n == SCOPE_STAT}
    scopes, collisions = {}, []
    for meta in _map_values(plane, 4):
        op, scope = "", None
        for num, wt, v in fields(meta):
            if num == 2 and wt == 2:
                op = xplane.op_name(_text(v))
            elif num == 5 and wt == 2:
                stat = {n: x for n, _, x in fields(v)}
                if stat.get(1) in scope_ids:
                    if 5 in stat:
                        scope = _text(stat[5])
                    elif 7 in stat:
                        scope = stat_names.get(stat[7])
        if op in scopes and scopes[op] != scope:
            collisions.append(op)
            scope = scopes[op] or scope     # the first that names one
        scopes[op] = scope
    return name, scopes, collisions


def op_scopes(path: str, say=None) -> dict:
    """{plane: {instruction name: tf_op or None}} of every plane that has
    event metadata. The instruction name is `xplane.op_name`'s, so the map
    joins `DeviceTrace.self_ns`. Two programs of one trace can give one
    instruction name two scopes; `say` is told which, and the first kept."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for num, wt, plane in fields(space):
        if num != 1 or wt != 2:
            continue
        name, scopes, collisions = _plane_scopes(plane)
        if scopes:
            out[name] = scopes
        if collisions and say is not None:
            say(f"scopes: plane {name}: {len(collisions)} instruction "
                f"names carry two scopes, the first is kept: "
                f"{sorted(set(collisions))[:8]}")
    return out


# ------------------------------------------------- predicates and buckets

def segments(scope) -> list:
    return scope.rstrip(":").split("/") if scope else []


def stages(segs: list) -> list:
    """The program's own segments, outermost first."""
    return [s for s in segs if s.startswith(SCOPE_PREFIX)]


def in_loop(name: str, segs: list) -> bool:
    """Inside the grower's `while_loop` (a `scan` lowers to one too), or
    the loop itself."""
    return "while" in segs or name.startswith("while")


def is_split_scan(name: str, segs: list) -> bool:
    return "lgbm.split_scan" in segs


def is_loop_overhead(name: str, segs: list) -> bool:
    """In the loop and in none of its three working stages: bookkeeping,
    pick-leaf, the pool, the `while` itself and what XLA adds at loop
    level (the copies of a loop-carried buffer carry the `while` op's own
    scope and no stage)."""
    return in_loop(name, segs) and not any(s in segs for s in LOOP_STAGES)


def is_unscoped(name: str, segs: list) -> bool:
    return not in_loop(name, segs) and not stages(segs)


def bucket(name: str, segs: list) -> str:
    """One label per op, so that the labels' shares sum to the busy time;
    the three predicates above are unions of these labels."""
    for stage in LOOP_STAGES:
        if stage in segs:
            if stage == "lgbm.split_scan":
                return stage + ("/loop" if in_loop(name, segs) else "/root")
            return stage
    if in_loop(name, segs):
        return "loop_overhead"
    own = stages(segs)
    return own[0] if own else "unscoped"


# ------------------------------------------------------- from the Evidence

_CACHE: dict = {}


def _trace_file(ev) -> str:
    from . import runner
    return xplane.find_xplane(os.path.join(runner.TRACE_DIR, ev.cell["name"]))


def for_evidence(ev) -> dict:
    """{instruction name: tf_op or None} over the device planes of the
    run's trace, read once per process. The first call prints the device
    time by scope on an earlier line."""
    path = _trace_file(ev)
    if path not in _CACHE:
        merged: dict = {}
        for plane, scopes in op_scopes(path, say=print).items():
            if plane.startswith(xplane.DEVICE_PLANE):
                for op, scope in scopes.items():
                    merged[op] = merged.get(op) or scope
        _CACHE[path] = merged
        by = shares_by(ev, merged)
        if by:
            print("device time by scope, % of busy: " + ", ".join(
                f"{k} {v:.2f}" for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])), flush=True)
        else:
            print("scopes: no lgbm.* scope on any device op of the trace "
                  "(a program without them, or an executable that a "
                  "compile cache kept from one)", flush=True)
    return _CACHE[path]


def _has_scopes(scopes: dict) -> bool:
    return any(SCOPE_PREFIX in (s or "") for s in scopes.values())


def _sums(ev, scopes: dict, label) -> tuple:
    """({label(name, segments): ns}, busy ns), summed over the devices."""
    total, busy = {}, 0.0
    for dev in ev.trace.devices:
        busy += dev.busy_ns
        for name, ns in dev.self_ns.items():
            key = label(name, segments(scopes.get(name)))
            total[key] = total.get(key, 0.0) + ns
    return total, busy


def shares_by(ev, scopes: dict) -> dict:
    """{bucket: % of busy time}; empty when no op carries a scope."""
    if ev.trace is None or not _has_scopes(scopes):
        return {}
    total, busy = _sums(ev, scopes, bucket)
    return {k: 100.0 * ns / busy for k, ns in total.items()}


def share(ev, pred):
    """Own time of the device ops with pred(name, segments), summed over
    the devices, over their busy time, in %. None off the chip and for a
    program whose trace carries no scope at all."""
    if ev.trace is None:
        return None
    scopes = for_evidence(ev)
    if not _has_scopes(scopes):
        return None
    total, busy = _sums(ev, scopes, pred)
    return 100.0 * total.get(True, 0.0) / busy


# ------------------------------------------------------------ host spans

def span_events(path: str, window: str = xplane.WINDOW_SPAN) -> dict:
    """{span name: [(start_ns, dur_ns)]} of the program's `lgbm:` spans on
    the host planes, inside the benchmark's last `bench:<window>` span
    where the trace has one. The profiler's clock, the device ops' own."""
    from jax.profiler import ProfileData
    from .clock import SPAN_PREFIX as BENCH
    found, windows = {}, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    found.setdefault(e.name[len(SPAN_PREFIX):], []).append(
                        (float(e.start_ns), float(e.duration_ns)))
                elif e.name == BENCH + window:
                    windows.append((float(e.start_ns), float(e.duration_ns)))
    if windows:
        t0, dur = windows[-1]
        found = {n: [(s, d) for s, d in evs if t0 <= s and s + d <= t0 + dur]
                 for n, evs in found.items()}
    return {n: evs for n, evs in found.items() if evs}


def span_ms_per_iter(ev, name: str):
    """Milliseconds inside the program's span `name` per traced iteration;
    None without a traced sub-window or where the program wrote none."""
    if not ev.traced or not ev.traced["units"].get("iters"):
        return None
    try:
        path = _trace_file(ev)
    except FileNotFoundError:
        return None
    if ("spans", path) not in _CACHE:
        _CACHE["spans", path] = span_events(path)
    events = _CACHE["spans", path].get(name)
    if not events:
        return None
    return sum(d for _, d in events) / 1e6 / ev.traced["units"]["iters"]


# ----------------------------------------------------------- set-up stages

def stage_s(name: str):
    """Seconds of one set-up stage from the program's always-on table
    (`lightgbm_tpu.obs.stage_seconds()`), None where the program has no
    such table or never entered the stage. The first call prints the
    whole table on an earlier line."""
    from lightgbm_tpu import obs
    table = getattr(obs, "stage_seconds", None)
    if table is None:
        return None
    stages_now = table()
    if "stages" not in _CACHE:
        _CACHE["stages"] = True
        print("program set-up stages: " + " ".join(
            f"{k}={s:.3f}s/{n}" for k, (s, n) in stages_now.items()),
            flush=True)
    return stages_now[name][0] if name in stages_now else None
