"""grower (treelearner/fused.py): share of the device's busy time spent
inside the tree's `while_loop` and under none of `lgbm.partition`,
`lgbm.hist`, `lgbm.split_scan`: bookkeeping, pick-leaf, the histogram
pool, the `while` op itself and what XLA adds at loop level, such as the
copies of a loop-carried buffer (harness/scopes.py); summed over the
chips."""
from benchmarks.harness import scopes


def read(ev):
    return scopes.share(ev, scopes.is_loop_overhead)
