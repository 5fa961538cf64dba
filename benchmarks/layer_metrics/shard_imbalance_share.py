"""collectives (treelearner/parallel.py): the slowest chip's own device
time outside `lgbm.allreduce` less the mean chip's, over the mean busy
time: rows are sharded contiguously, so the shards' leaf windows differ,
and a collective ends when the slowest shard arrives
(harness/collectives.py)."""
from benchmarks.harness import collectives


def read(ev):
    return collectives.imbalance_share(ev)
