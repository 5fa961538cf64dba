"""device: share of the traced sub-window in which no op ran, on the most
idle chip."""


def read(ev):
    if ev.trace is None:
        return None
    return 100.0 * ev.trace.idle_share()
