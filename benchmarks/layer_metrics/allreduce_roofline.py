"""collectives (treelearner/parallel.py): share of the interconnect's peak
that the allreduces of the traced trees reach. The least a chip's links
carry is the root's and each split's `[cols, max_bin + 1, 2]` float32
histogram with its count, 2 (D - 1) / D times for a ring over D chips
(harness/work_dp.py, splits from the internal_count of the traced trees in
the model the run wrote); that over the chip's interconnect bandwidth
(peaks.json, bits a second), over the own time of the ops under
`lgbm.allreduce`, mean over the chips. A few percent is expected: 254
small allreduces a tree are bound by latency, not by bandwidth, and the
metric is there to say so."""
from benchmarks.harness import collectives, work_dp


def read(ev):
    spent = collectives.allreduce_seconds(ev)
    if not spent or "traced_trees" not in ev.artifacts:
        return None
    first, stop = ev.artifacts["traced_trees"]
    trees = ev.artifacts["trees"][first:stop]
    moved = work_dp.allreduce_bytes(
        [len(t.internal_count) for t in trees],
        int(ev.config["shape"]["cols"]), int(ev.config["params"]["max_bin"]),
        len(ev.trace.devices))
    return 100.0 * moved / (ev.peaks["ici_bits_per_s"] / 8.0) / spent
