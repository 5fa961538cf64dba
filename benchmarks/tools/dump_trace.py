"""python3 benchmarks/tools/dump_trace.py <file.xplane.pb> <out.json>

A trace, looked at by hand: every plane and line with its event count and
extent, the names that take most time on each line, and the first events
of each line with their stats. Read this before writing a reader against
a trace; a reader matches what the trace prints, not what one hopes for.
"""
import json
import sys
from collections import defaultdict


def dump(path: str, first: int = 40, top: int = 40) -> dict:
    from jax.profiler import ProfileData
    out = {"file": path, "planes": []}
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            total, count = defaultdict(float), defaultdict(int)
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            lines.append({
                "line": line.name, "events": len(events),
                "start_ns": min((e.start_ns for e in events), default=None),
                "end_ns": max((e.start_ns + e.duration_ns for e in events),
                              default=None),
                "top": [[n, total[n] / 1e9, count[n]] for n in
                        sorted(total, key=total.get, reverse=True)[:top]],
                "first": [{"name": e.name, "start_ns": e.start_ns,
                           "dur_ns": e.duration_ns,
                           "stats": {k: (v if isinstance(v, (int, float))
                                         else str(v)[:300])
                                     for k, v in e.stats}}
                          for e in events[:first]]})
        out["planes"].append({"plane": plane.name, "lines": lines})
    return out


if __name__ == "__main__":
    with open(sys.argv[2], "w") as fh:
        json.dump(dump(sys.argv[1]), fh, indent=1)
