"""Peak bytes in use on the fullest chip after the window, in GiB: what
shape fits."""


def read(ev):
    peak = ev.device["memory_peak_bytes"]
    return peak / 2**30 if peak else None
