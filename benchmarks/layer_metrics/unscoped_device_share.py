"""grower (treelearner/fused.py): share of the device's busy time in ops
outside the tree's loop that carry no `lgbm.*` scope: the guard that the
program's scopes cover the program (harness/scopes.py); summed over the
chips."""
from benchmarks.harness import scopes


def read(ev):
    return scopes.share(ev, scopes.is_unscoped)
