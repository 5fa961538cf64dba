"""The program's executable table, as the readers of `exe_call_*`,
`host_python_ms_per_iter` and `first_call_*` see it.

`lightgbm_tpu.compile.get_manager().snapshot_entries()` holds, by entry
name, every call's wall and calling-thread CPU seconds and a row per
build; `.marks` holds the call totals and the calling thread's CPU
seconds at the end of each `update()`, on `time.perf_counter()`, which
is the benchmark's clock too (`harness/clock.py`). So the calls of an
interval are the difference of two marks, picked by the clock readings
the harness keeps anyway: the traced sub-window's `update` spans, and
the end of set-up for the untraced window. A program without the table (the parent of the PR that
brought it) gives None everywhere.

The first reader to ask prints two lines: `program builds:` (every build
of a second or more as `name phase source seconds`, and what jax built
outside any entry) and `program calls:` (per entry and iteration, both
intervals).
"""
from __future__ import annotations

from . import xplane

UNREGISTERED = "(unregistered)"
_SECONDS = ("trace_lower_s", "xla_s", "load_s", "call_s")
_CACHE: dict = {}


def _fresh(ev) -> None:
    """One run's Evidence at a time (a rehearsal runs several cells)."""
    if _CACHE.get("ev") is not ev:
        _CACHE.clear()
        _CACHE["ev"] = ev


def _manager():
    from lightgbm_tpu.compile import get_manager
    mgr = get_manager()
    return mgr if hasattr(mgr, "snapshot_entries") else None


def build_seconds(build: dict) -> float:
    return float(sum(build.get(k, 0.0) for k in _SECONDS))


def _between(marks: list, t_lo: float, t_hi: float):
    """(updates, {name: (calls, wall s, cpu s)}, wall s, thread CPU s) of
    the update()s that ended in (t_lo, t_hi]: the last mark at or before
    t_hi less the last at or before t_lo; the two last are of the whole
    stretch between the two marks. None where the marks do not reach back
    to t_lo."""
    before = [m for m in marks if m[0] <= t_lo]
    inside = [m for m in marks if t_lo < m[0] <= t_hi]
    if not before or not inside:
        return None
    (w0, c0, base), (w1, c1, last) = before[-1], inside[-1]
    zero = (0, 0.0, 0.0)
    return len(inside), {
        name: tuple(a - b for a, b in zip(now, base.get(name, zero)))
        for name, now in last.items()
        if now[0] > base.get(name, zero)[0]}, w1 - w0, c1 - c0


def _intervals(ev) -> dict:
    """{"window" | "traced": (updates, per-entry deltas)}, and
    "window_opens": the end of the first update() of the window (no
    build is later than that in a run whose `window_compiles` is 0)."""
    _fresh(ev)
    if "intervals" in _CACHE:
        return _CACHE["intervals"]
    out: dict = {}
    mgr = _manager()
    marks = list(mgr.marks) if mgr is not None else []
    events = ev.spans.events
    setup_end = max((t1 for n, _, t1 in events if n.startswith("setup:")),
                    default=None)
    if marks and setup_end is not None:
        traced = [(t0, t1) for n, t0, t1 in events
                  if n == xplane.WINDOW_SPAN]
        updates = [(t0, t1) for n, t0, t1 in events if n == "update"]
        window_end = traced[0][0] if traced else float("inf")
        after = [m[0] for m in marks if m[0] > setup_end]
        out["window_opens"] = after[0] if after else float("inf")
        out["window"] = _between(marks, setup_end, window_end)
        if updates:
            out["traced"] = _between(marks, updates[0][0], updates[-1][1])
    _CACHE["intervals"] = out
    return out


def calls_ms_per_iter(ev, interval: str = "traced"):
    """(wall ms, calling-thread CPU ms) inside the calls of every
    registered executable, per update() of the interval; None where the
    program has no table or the interval no update()."""
    _say(ev)
    found = _intervals(ev).get(interval)
    if not found:
        return None
    updates, rows = found[:2]
    return (1e3 * sum(r[1] for r in rows.values()) / updates,
            1e3 * sum(r[2] for r in rows.values()) / updates)


def builds_before_window(ev):
    """[(name, build row)] of every build that ended before the window
    opened; None on a program without the table."""
    mgr = _manager()
    if mgr is None:
        return None
    _say(ev)
    opens = _intervals(ev).get("window_opens", float("inf"))
    return [(name, b) for name, row in mgr.snapshot_entries().items()
            for b in row["builds"] if b["at"] <= opens]


def _say(ev) -> None:
    _fresh(ev)
    if "said" in _CACHE:
        return
    _CACHE["said"] = True
    mgr = _manager()
    if mgr is None:
        return
    parts = []
    for name, row in mgr.snapshot_entries().items():
        for b in row["builds"]:
            if name == UNREGISTERED:
                parts.append(
                    f"{name} {b['phase']} {b['source']} {b['xla_s']:.2f} "
                    f"({b['count']} executables after {b['updates']} "
                    "update()s, the slowest " + ", ".join(
                        f"{what} {secs:.2f}"
                        for secs, what in reversed(b["slowest"])) + ")")
            elif build_seconds(b) >= 1.0:
                parts.append(f"{name} {b['phase']} {b['source']} "
                             f"{build_seconds(b):.2f}")
    print("program builds: " + ("; ".join(parts) or "none over 1 s"),
          flush=True)
    for interval, found in _intervals(ev).items():
        if interval == "window_opens" or not found:
            continue
        updates, rows, wall, cpu = found
        print(f"program calls ({interval}, {updates} update()s, "
              f"{1e3 * wall / updates:.3f} ms each end to end, the thread on "
              f"a CPU {1e3 * cpu / updates:.3f} ms of it; per update: calls, "
              "wall ms, of it blocked ms): " + "; ".join(
                  f"{name} {r[0] / updates:g} {1e3 * r[1] / updates:.3f} "
                  f"{1e3 * (r[1] - r[2]) / updates:.3f}"
                  for name, r in sorted(rows.items())), flush=True)
