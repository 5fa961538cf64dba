"""grower (treelearner/fused.py): share of the device's busy time spent
under the program's `lgbm.split_scan` scope, the root's scan and the two
children's of every step, by the scope the trace gives each op
(harness/scopes.py); summed over the chips."""
from benchmarks.harness import scopes


def read(ev):
    return scopes.share(ev, scopes.is_split_scan)
