"""boosting_loop (boosting/gbdt.py down to the dispatch): host
milliseconds inside the calls of ALL the program's registered executables
per update(), from the compile manager's always-on table (wall clock
around each executable's call, `harness/exe_table.py`), over the traced
sub-window's update()s: the interval of `host_dispatch_ms_per_iter`, of
which it is a part, and of `jit_call_ms_per_iter`, which it reproduces on
the persistent tier from the counter side. The untraced window's figure
is on the `program calls (window, ...)` line."""
from benchmarks.harness import exe_table


def read(ev):
    found = exe_table.calls_ms_per_iter(ev)
    return None if found is None else found[0]
