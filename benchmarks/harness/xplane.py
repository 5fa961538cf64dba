"""From the profiler's `.xplane.pb` to busy time, idle gaps and kernel time.

Two steps, so that the arithmetic is checked on a small recorded trace
without a chip (tests/test_xplane.py):

  load(path)    `.xplane.pb` -> plain events, through jax.profiler.ProfileData
  reduce(raw)   plain events  -> TraceSummary

A device plane's op line nests: a `while` event spans the events of its
body. Busy time is therefore the UNION of the op intervals, and an op's
own time is its duration less what its children cover; summing durations
would count a loop body twice.
"""
from __future__ import annotations

import dataclasses
import glob
import os

from .clock import SPAN_PREFIX

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
WINDOW_SPAN = "traced"
NO_SPAN = "between"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(event_name: str) -> str:
    """The op line names an event by the whole HLO instruction,
    `%partition_pallas2.12 = (s32[16,21004288]{...}) custom-call(...)`:
    the instruction's own name is what identifies it."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(path: str) -> dict:
    """{"devices": {plane: [(name, start_ns, dur_ns), ...]},
        "spans": [(name, start_ns, dur_ns), ...]}: the op line of every
    device plane, and the benchmark's own spans from the host planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name[len(SPAN_PREFIX):], float(e.start_ns),
                     float(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


@dataclasses.dataclass
class DeviceTrace:
    plane: str
    busy_ns: float                  # union of op intervals in the window
    self_ns: dict                   # op name -> its own time, children out
    gaps: list                      # [(start_ns, end_ns)] idle in the window


@dataclasses.dataclass
class TraceSummary:
    t0_ns: float
    t1_ns: float
    devices: list                   # [DeviceTrace]
    spans: list                     # [(name, start_ns, end_ns)]

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    @property
    def busy_s(self) -> float:
        """Mean over the devices of the seconds in which an op ran."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def idle_share(self) -> float:
        """Of the most idle device."""
        window = self.t1_ns - self.t0_ns
        return max(1.0 - d.busy_ns / window for d in self.devices)

    def op_seconds(self, prefix: str) -> list:
        """Per device, the own seconds of the ops whose name starts so."""
        return [sum(ns for name, ns in d.self_ns.items()
                    if name.startswith(prefix)) / 1e9 for d in self.devices]

    def op_share(self, prefix: str) -> float:
        """Those seconds over the busy seconds, both as means over the
        devices."""
        spent = self.op_seconds(prefix)
        return sum(spent) / len(spent) / self.busy_s

    def top_ops(self, n: int = 10) -> list:
        """[[name, seconds]]: own time, mean over the devices."""
        total: dict = {}
        for d in self.devices:
            for name, ns in d.self_ns.items():
                total[name] = total.get(name, 0.0) + ns
        k = len(self.devices) * 1e9
        return [[name, ns / k] for name, ns in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 5) -> list:
        """[[span, seconds]]: the longest idle gaps of any device, each
        named by the benchmark span that covers most of it."""
        gaps = sorted(((e - s, s, e) for d in self.devices
                       for s, e in d.gaps), reverse=True)[:n]
        return [[self._span_at(s, e), ns / 1e9] for ns, s, e in gaps]

    def _span_at(self, s: float, e: float) -> str:
        best, cover = NO_SPAN, 0.0
        for name, a, b in self.spans:
            if name == WINDOW_SPAN:
                continue
            c = min(e, b) - max(s, a)
            if c > cover:
                best, cover = name, c
        return best


def _reduce_device(plane: str, events: list, t0: float, t1: float
                   ) -> DeviceTrace:
    # parents before their children: by start, the longer first
    clipped = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            clipped.append((a, -(b - a), name, b))
    clipped.sort()
    self_ns: dict = {}
    stack: list = []                # [(end, name)] of the open ancestors
    busy, gaps, cursor = 0.0, [], t0
    for a, _, name, b in clipped:
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:                   # a child: its span comes off the parent
            b = min(b, stack[-1][0])
            self_ns[stack[-1][1]] -= b - a
        self_ns[name] = self_ns.get(name, 0.0) + (b - a)
        stack.append((b, name))
        if a > cursor:
            gaps.append((cursor, a))
        if b > cursor:
            busy += b - max(a, cursor)
            cursor = b
    if t1 > cursor:
        gaps.append((cursor, t1))
    return DeviceTrace(plane, busy, self_ns, gaps)


def reduce(raw: dict):
    """TraceSummary of the traced window, or None when no device op was
    recorded (the CPU backend of a rehearsal has no device plane). The
    window is the benchmark's own `traced` span where the trace has it,
    else the extent of the device ops."""
    devices = {p: ev for p, ev in raw["devices"].items() if ev}
    if not devices:
        return None
    spans = [(n, s, s + d) for n, s, d in raw["spans"]]
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if window:
        t0, t1 = window[-1]
    else:
        t0 = min(s for ev in devices.values() for _, s, _ in ev)
        t1 = max(s + d for ev in devices.values() for _, s, d in ev)
    return TraceSummary(t0, t1,
                        [_reduce_device(p, devices[p], t0, t1)
                         for p in sorted(devices)], spans)
