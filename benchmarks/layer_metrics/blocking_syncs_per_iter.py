"""boosting_loop (boosting/gbdt.py): device_get / block_until_ready calls
the program itself made per traced iteration. A count, from the program's
own sync tracing (obs.install_sync_tracing + obs.Tracer)."""


def read(ev):
    if not ev.traced or "blocking_syncs" not in ev.traced["counters"]:
        return None
    return ev.traced["counters"]["blocking_syncs"] / ev.traced["units"]["iters"]
