"""grower (treelearner/fused.py, per-tree tier): share of the device's busy
time under the program's `lgbm.row_traverse` scope — once
per tree, the leaf of every row, out-of-bag ones included, by replaying the
tree's splits over the resident planar codes;
None on a program or a trace that has no such scope
(harness/scope_shares.py); summed over the chips."""
from benchmarks.harness import scope_shares


def read(ev):
    return scope_shares.share(ev, "lgbm.row_traverse")
