"""Own device time by program scope, booked op event by op event.

harness/scopes.py joins a trace's op events to their scopes by the
instruction's name. XLA names a fusion by its kind and numbers it within
its own program, so two programs of one trace can give one name to ops of
two scopes (the per-tree grow program and a small program that builds a
tree's replay table share dozens), and that join keeps one scope a name:
which one can change from trace to trace. The device plane's event
metadata has an entry for each program's instruction and each op event
points at its own entry, so here every event is booked to the scope of
the instruction it ran. Own time and the window are harness/xplane.py's:
inside the benchmark's `traced` span, an op's duration less what its
children cover.

The raw `.xplane.pb` is walked with harness/scopes.py's wire-format
reader. Field numbers, from tsl/profiler/protobuf/xplane.proto: XPlane
lines=3; XLine name=2, timestamp_ns=3, events=4; XEvent metadata_id=1,
offset_ps=2, duration_ps=3; XEventMetadata id=1, name=2, stats=5.
"""
from __future__ import annotations

from . import scopes, xplane
from .clock import SPAN_PREFIX

_CACHE: dict = {}


def _stat_names(plane) -> dict:
    out = {}
    for meta in scopes._map_values(plane, 5):
        sid, name = None, ""
        for num, wt, v in scopes.fields(meta):
            if num == 1 and wt == 0:
                sid = v
            elif num == 2 and wt == 2:
                name = scopes._text(v)
        out[sid] = name
    return out


def _metadata(plane) -> dict:
    """{event metadata id: (name, tf_op or None)} of one plane."""
    stat_names = _stat_names(plane)
    scope_ids = {i for i, n in stat_names.items() if n == scopes.SCOPE_STAT}
    out = {}
    for meta in scopes._map_values(plane, 4):
        mid, name, scope = None, "", None
        for num, wt, v in scopes.fields(meta):
            if num == 1 and wt == 0:
                mid = v
            elif num == 2 and wt == 2:
                name = xplane.op_name(scopes._text(v))
            elif num == 5 and wt == 2:
                stat = {n: x for n, _, x in scopes.fields(v)}
                if stat.get(1) in scope_ids:
                    if 5 in stat:
                        scope = scopes._text(stat[5])
                    elif 7 in stat:
                        scope = stat_names.get(stat[7])
        out[mid] = (name, scope)
    return out


def _line_events(line) -> tuple:
    """(line name, [(metadata id, start_ns, dur_ns)])."""
    name, t0_ns, events = "", 0, []
    for num, wt, v in scopes.fields(line):
        if num == 2 and wt == 2:
            name = scopes._text(v)
        elif num == 3 and wt == 0:
            t0_ns = v
        elif num == 4 and wt == 2:
            ev = {n: x for n, w, x in scopes.fields(v) if w == 0}
            events.append((ev.get(1, -1), ev.get(2, 0), ev.get(3, 0)))
    return name, [(m, t0_ns + off / 1e3, dur / 1e3)
                  for m, off, dur in events]


def load(path: str) -> dict:
    """{"devices": {plane: ([(metadata id, start_ns, dur_ns)] of the op
    line, {metadata id: (name, tf_op)})}, "window": (t0, t1) of the last
    `traced` span or None}."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    devices, window = {}, None
    for num, wt, plane in scopes.fields(space):
        if num != 1 or wt != 2:
            continue
        name = next((scopes._text(v) for n, w, v in scopes.fields(plane)
                     if n == 2 and w == 2), "")
        meta = _metadata(plane)
        lines = [_line_events(v) for n, w, v in scopes.fields(plane)
                 if n == 3 and w == 2]
        if name.startswith(xplane.DEVICE_PLANE):
            ops = [e for ln, evs in lines if ln == xplane.OP_LINE
                   for e in evs]
            if ops:
                devices[name] = (ops, meta)
        elif name.startswith("/host:"):
            want = SPAN_PREFIX + xplane.WINDOW_SPAN
            for _, evs in lines:
                for m, s, d in evs:
                    if meta.get(m, ("",))[0] == want:
                        window = (s, s + d)
    return {"devices": devices, "window": window}


def by_scope(raw: dict) -> tuple:
    """({outermost lgbm.* scope or "unscoped": own ns}, busy ns, [names
    that ran under two scopes]), summed over the devices; the scopes
    empty where no op carries one."""
    total, busy, names = {}, 0.0, {}
    for plane, (ops, meta) in sorted(raw["devices"].items()):
        if raw["window"] is not None:
            t0, t1 = raw["window"]
        else:
            t0 = min(s for _, s, _ in ops)
            t1 = max(s + d for _, s, d in ops)
        dev = xplane._reduce_device(plane, ops, t0, t1)
        busy += dev.busy_ns
        for mid, ns in dev.self_ns.items():
            name, tf_op = meta.get(mid, ("", None))
            own = scopes.stages(scopes.segments(tf_op))
            key = own[0] if own else "unscoped"
            total[key] = total.get(key, 0.0) + ns
            names.setdefault(name, set()).add(key)
    if not any(k.startswith(scopes.SCOPE_PREFIX) for k in total):
        total = {}
    return total, busy, sorted(n for n, k in names.items() if len(k) > 1)


def _for_evidence(ev):
    """by_scope of the run's trace, read once per process (the first
    read prints the time by scope); None off the chip, for a trace
    without scopes, or where the trace file is not there."""
    if ev.trace is None:
        return None
    try:
        path = scopes._trace_file(ev)
    except FileNotFoundError:
        return None
    if path not in _CACHE:
        total, busy, apart = by_scope(load(path))
        _CACHE[path] = (total, busy) if total and busy else None
        if _CACHE[path]:
            print(f"device time by outermost scope, booked by event, % of "
                  f"busy: " + ", ".join(
                      f"{k} {100.0 * v / busy:.2f}" for k, v in
                      sorted(total.items(), key=lambda kv: -kv[1]))
                  + f"; {len(apart)} instruction names ran under two "
                  f"scopes, booked apart: {apart[:8]}", flush=True)
    return _CACHE[path]


def share(ev, scope: str):
    """Own time of the op events whose outermost program scope is
    `scope`, over the busy time, in %; None where no event carries it."""
    got = _for_evidence(ev)
    if got is None or scope not in got[0]:
        return None
    return 100.0 * got[0][scope] / got[1]
