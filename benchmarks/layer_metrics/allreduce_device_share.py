"""collectives (treelearner/parallel.py, `_psum` of treelearner/fused.py):
share of the device's busy time spent in the ops under `lgbm.allreduce`,
the histogram and count allreduces of data-parallel growth, means over
the chips (harness/collectives.py). It holds the transfer and the wait
for the slowest shard alike; `shard_imbalance_share` says how much of it
is the wait."""
from benchmarks.harness import collectives


def read(ev):
    return collectives.allreduce_share(ev)
