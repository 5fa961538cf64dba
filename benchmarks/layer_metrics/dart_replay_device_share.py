"""boosting_loop (boosting/gbdt.py DART): share of the device's busy time
under the program's `lgbm.dart_replay` scope: the dropped trees' output
replayed over the resident code planes and taken off the training score,
and the share put back after the new tree. Booked op event by op event
(harness/scope_events.py), since the small programs around the replay
share instruction names with the grow program and the score add; None on
a program or a trace that has no such scope; summed over the chips."""
from benchmarks.harness import scope_events


def read(ev):
    return scope_events.share(ev, "lgbm.dart_replay")
