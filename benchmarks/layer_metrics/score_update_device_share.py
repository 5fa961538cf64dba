"""boosting_loop (boosting/gbdt.py): share of the device's busy
time under the program's `lgbm.score_update` scope — the
score add of every tree (the per-tree tier's own program, the persistent
program's last stage);
None on a program or a trace that has no such scope
(harness/scope_shares.py); summed over the chips."""
from benchmarks.harness import scope_shares


def read(ev):
    return scope_shares.share(ev, "lgbm.score_update")
