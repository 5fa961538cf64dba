"""boosting_loop (boosting/gbdt.py down to the dispatch): milliseconds
inside the program's `lgbm:fused/train_iter` span, the call of the
iteration's executable, per traced iteration, on the profiler's clock.
`host_dispatch_ms_per_iter` less this is the program's own Python per
`update()`."""
from benchmarks.harness import scopes


def read(ev):
    return scopes.span_ms_per_iter(ev, "fused/train_iter")
