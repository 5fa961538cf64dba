"""entry (engine, basic.Booster.update): `host_dispatch_ms_per_iter`'s
own span less `exe_call_ms_per_iter`, over the same update()s: the
program's own Python an update() (trees, pending records, sentinel, the
stop check), with no executable's call in it."""
from benchmarks.harness import exe_table


def read(ev):
    spans = ev.spans.seconds("update")
    found = exe_table.calls_ms_per_iter(ev)
    if not spans or found is None:
        return None
    return max(1e3 * sum(spans) / len(spans) - found[0], 0.0)
