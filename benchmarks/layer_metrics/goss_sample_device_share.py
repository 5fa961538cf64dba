"""boosting_loop (boosting/gbdt.py, GOSS): share of the device's busy
time under the program's `lgbm.goss_sample` scope — one sampling round per
iteration: the two top-k selections, the weighting of the drawn rows and the
[bag | out-of-bag] permutation;
None on a program or a trace that has no such scope
(harness/scope_shares.py); summed over the chips."""
from benchmarks.harness import scope_shares


def read(ev):
    return scope_shares.share(ev, "lgbm.goss_sample")
