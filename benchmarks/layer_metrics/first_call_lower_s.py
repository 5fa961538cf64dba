"""compile (compile/): seconds the program spent tracing and lowering
its registered programs before the window opened, whatever served the
executable afterwards: what a warm jax cache does not save (the AOT store
does)."""
from benchmarks.harness import exe_table


def read(ev):
    builds = exe_table.builds_before_window(ev)
    if builds is None:
        return None
    return sum(b.get("trace_lower_s", 0.0) for _, b in builds)
