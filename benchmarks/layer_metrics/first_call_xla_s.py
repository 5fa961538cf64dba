"""compile (compile/): seconds XLA spent compiling for this run before
the window opened: over the build rows of the program's executable table
whose source is `compiled` (neither jax's cache nor the AOT store served
them), `xla_s`, plus the whole first call of a plain-jit entry, plus what
jax compiled outside any entry (`(unregistered)`). The `program builds:`
line names them."""
from benchmarks.harness import exe_table


def read(ev):
    builds = exe_table.builds_before_window(ev)
    if builds is None:
        return None
    return sum(b.get("xla_s", 0.0) + b.get("call_s", 0.0)
               for _, b in builds if b["source"] == "compiled")
