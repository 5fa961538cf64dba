"""kernels (ops/plane.py forest replay): device nanoseconds of the forest
replay kernel, by the name its Pallas call gives its custom call in the
trace (`replay_forest_pallas*`, mean over the chips), per row and split
of the trees the traced iterations dropped, counted from the drop sets
the run recorded and the final model's trees (harness/work_dart.py), so
it reads the same work whatever implements it. None where the kernel,
the drop sets or the model are missing, or where no traced iteration
dropped a tree."""
from benchmarks.harness import work_dart


def read(ev):
    drops = ev.artifacts.get("traced_drops")
    splits = ev.artifacts.get("tree_splits")
    if ev.trace is None or drops is None or splits is None:
        return None
    spent = ev.trace.op_seconds(work_dart.KERNEL)
    spent = sum(spent) / len(spent)
    work = work_dart.lane_splits(int(ev.config["shape"]["rows"]), drops,
                                 splits)
    return 1e9 * spent / work if spent and work else None
