"""grower (treelearner/fused.py, per-tree tier, every row in the tree):
share of the device's busy time under the program's `lgbm.build_state`
scope — once per tree, `plane.build_data`: the resident code planes, the
row-order gradients and hessians and the row ids laid out as the tree's
fresh planar state;
None on a program or a trace that has no such scope
(harness/scope_shares.py); summed over the chips."""
from benchmarks.harness import scope_shares


def read(ev):
    return scope_shares.share(ev, "lgbm.build_state")
