"""python3 benchmarks/tools/cut_scopes_dp.py <file.xplane.pb> <cell> <out.json>

tools/cut_scopes.py for a cell on several chips: of ONE iteration (the
middle execution of the iteration program on each device's `XLA Modules`
line) every chip's own nanoseconds by op and its busy nanoseconds, and
the ops' `tf_op`, which the chips share (one program). What
benchmarks/tests/test_collectives.py checks the collectives' readers on.
Adds or replaces <cell> in <out.json>.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import scopes, xplane  # noqa: E402
from benchmarks.tools.cut_scopes import PROGRAM  # noqa: E402


def cut(path: str) -> dict:
    from jax.profiler import ProfileData
    planes = sorted((p for p in ProfileData.from_file(path).planes
                     if p.name.startswith(xplane.DEVICE_PLANE)),
                    key=lambda p: p.name)
    events = xplane.load(path)["devices"]
    tf_op = scopes.op_scopes(path)
    chips, merged, said = [], {}, ""
    for plane in planes:
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines if line.name == "XLA Modules"
                      for e in line.events if e.name.startswith(PROGRAM))
        t0, t1 = runs[len(runs) // 2]
        dev = xplane._reduce_device(plane.name, events[plane.name],
                                    float(t0), float(t1))
        chips.append({"plane": plane.name, "busy_ns": dev.busy_ns,
                      "self_ns": dict(sorted(dev.self_ns.items(),
                                             key=lambda kv: -kv[1]))})
        for op in dev.self_ns:
            merged[op] = merged.get(op) or tf_op[plane.name].get(op)
        said = said or (f"{(t1 - t0) / 1e6:.1f} ms, the middle one of "
                        f"{len(runs)}")
    return {"what": f"one execution of {PROGRAM} ({said}) on each of "
                    f"{len(chips)} device planes of "
                    f"{os.path.basename(path)}: every chip's own ns by "
                    "op, and the tf_op of the ops' event metadata",
            "chips": chips, "scopes": merged}


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
