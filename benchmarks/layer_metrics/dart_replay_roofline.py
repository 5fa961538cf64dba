"""kernels (ops/plane.py forest replay): share of its memory roofline the
forest replay kernel reaches: the least a dropping round's kernel moves,
the code planes read once and the dropped trees' sum written
(harness/work_dart.py), over the chip's HBM bandwidth, over the kernel's
own time in the traced sub-window, by the name its Pallas call gives its
custom call (`replay_forest_pallas*`, mean over the chips). The splits
are compute, so this reads low by design: how far from free a tree's
replay is."""
from benchmarks.harness import work_dart


def read(ev):
    drops = ev.artifacts.get("traced_drops")
    if ev.trace is None or drops is None or not ev.peaks:
        return None
    spent = ev.trace.op_seconds(work_dart.KERNEL)
    spent = sum(spent) / len(spent)
    if not spent:
        return None
    shape, params = ev.config["shape"], ev.config["params"]
    moved = work_dart.replay_bytes(
        int(shape["rows"]), int(shape["cols"]), int(params["max_bin"]),
        sum(1 for d in drops if d))
    return 100.0 * moved / ev.peaks["hbm_bytes_per_s"] / spent
