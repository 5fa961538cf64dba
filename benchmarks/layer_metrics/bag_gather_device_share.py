"""grower (treelearner/fused.py, per-tree tier): share of the device's busy
time under the program's `lgbm.bag_gather` scope — once per
tree, the bag rows' codes, gradients and hessians gathered by the permutation
into lanes of the planar state;
None on a program or a trace that has no such scope
(harness/scope_shares.py); summed over the chips."""
from benchmarks.harness import scope_shares


def read(ev):
    return scope_shares.share(ev, "lgbm.bag_gather")
