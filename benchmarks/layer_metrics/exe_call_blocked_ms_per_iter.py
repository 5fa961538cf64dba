"""boosting_loop (boosting/gbdt.py down to the dispatch): of
`exe_call_ms_per_iter`, the milliseconds the calling thread was NOT on a
CPU (wall less `time.thread_time()` around each executable's call): the
host blocked inside the runtime, waiting for the device it ran ahead of.
What is left of `exe_call_ms_per_iter` is the runtime's host work."""
from benchmarks.harness import exe_table


def read(ev):
    found = exe_table.calls_ms_per_iter(ev)
    return None if found is None else max(found[0] - found[1], 0.0)
