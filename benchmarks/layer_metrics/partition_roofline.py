"""kernels (ops/plane.py): share of its roofline the partition kernel
reaches. It is bound by memory: the least it can move is every plane of
every row of each leaf it splits, once in and once out (harness/work.py,
from the internal_count of the traced trees in the model the run wrote);
that over the chip's HBM bandwidth, over the kernel's time in the traced
sub-window. The rows are spread evenly over the chips."""

KERNEL = "partition_pallas"


def read(ev):
    if ev.trace is None or "traced_trees" not in ev.artifacts:
        return None
    first, stop = ev.artifacts["traced_trees"]
    trees = ev.artifacts["trees"][first:stop]
    params, shape = ev.config["params"], ev.config["shape"]
    planes = ev.work.planar_planes(
        shape["cols"], ev.work.code_bits(params["max_bin"]))
    moved = ev.work.partition_bytes(
        [n for t in trees for n in t.internal_count], planes)
    spent = ev.trace.op_seconds(KERNEL)
    if not moved or not sum(spent):
        return None
    return 100.0 * (moved / len(spent) / ev.peaks["hbm_bytes_per_s"]
                    / (sum(spent) / len(spent)))
