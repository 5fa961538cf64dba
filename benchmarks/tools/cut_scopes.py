"""python3 benchmarks/tools/cut_scopes.py <file.xplane.pb> <cell> <out.json>

Cuts what benchmarks/tests/test_scopes.py checks the scope readers on
from a cell's traced run: of ONE iteration (the middle execution of the
iteration program on the first device's `XLA Modules` line) every op's
own nanoseconds, the busy nanoseconds, and each of those ops' `tf_op`.
Adds or replaces <cell> in <out.json>.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import scopes, xplane  # noqa: E402

PROGRAM = "jit__entry_train_iter"


def cut(path: str) -> dict:
    from jax.profiler import ProfileData
    first = min((p for p in ProfileData.from_file(path).planes
                 if p.name.startswith(xplane.DEVICE_PLANE)),
                key=lambda p: p.name)
    plane = first.name
    runs = [(e.start_ns, e.start_ns + e.duration_ns)
            for line in first.lines if line.name == "XLA Modules"
            for e in line.events if e.name.startswith(PROGRAM)]
    t0, t1 = sorted(runs)[len(runs) // 2]
    dev = xplane._reduce_device(plane, xplane.load(path)["devices"][plane],
                                float(t0), float(t1))
    tf_op = scopes.op_scopes(path)[plane]
    return {"what": f"one execution of {PROGRAM} ({(t1 - t0) / 1e6:.1f} ms, "
                    f"the middle one of {len(runs)}) on {plane} of "
                    f"{os.path.basename(path)}: own ns of every op, and "
                    "the tf_op of its event metadata",
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
