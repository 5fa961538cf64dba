"""python3 benchmarks/tools/cut_scopes_rank.py <file.xplane.pb> <cell> <out.json>

tools/cut_scopes.py for a cell of the per-tree tier under a ranking
objective, where an iteration is several programs: cuts ONE iteration of
the traced run, from the start of the middle execution of the gradient
program (`jit__gradients_device` on the first device's `XLA Modules`
line) to the start of the next one, so the grow program and the score add
that follow it are inside: every op's own nanoseconds, the busy
nanoseconds, and each of those ops' `tf_op`. Adds or replaces <cell> in
<out.json> (benchmarks/tests/test_rank_scopes.py reads it).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import scopes, xplane  # noqa: E402

PROGRAM = "jit__gradients_device"


def cut(path: str) -> dict:
    from jax.profiler import ProfileData
    first = min((p for p in ProfileData.from_file(path).planes
                 if p.name.startswith(xplane.DEVICE_PLANE)),
                key=lambda p: p.name)
    plane = first.name
    starts = sorted(e.start_ns for line in first.lines
                    if line.name == "XLA Modules" for e in line.events
                    if e.name.startswith(PROGRAM))
    if len(starts) < 2:
        raise SystemExit(f"{len(starts)} executions of {PROGRAM} in {path}")
    at = (len(starts) - 1) // 2
    t0, t1 = starts[at], starts[at + 1]
    dev = xplane._reduce_device(plane, xplane.load(path)["devices"][plane],
                                float(t0), float(t1))
    tf_op = scopes.op_scopes(path)[plane]
    return {"what": f"one iteration ({(t1 - t0) / 1e6:.1f} ms: from execution "
                    f"{at} of {PROGRAM} to the next, of {len(starts)}) on "
                    f"{plane} of {os.path.basename(path)}: own ns of every "
                    "op, and the tf_op of its event metadata",
            "busy_ns": dev.busy_ns,
            "self_ns": dict(sorted(dev.self_ns.items(),
                                   key=lambda kv: -kv[1])),
            "scopes": {op: tf_op.get(op) for op in dev.self_ns}}


if __name__ == "__main__":
    src, cell, out = sys.argv[1:4]
    recorded = {}
    if os.path.exists(out):
        with open(out) as fh:
            recorded = json.load(fh)
    recorded[cell] = cut(src)
    with open(out, "w") as fh:
        json.dump(recorded, fh, indent=0, sort_keys=True)
        fh.write("\n")
