"""Boosting iterations completed in the measured window, per second."""


def read(ev):
    iters = ev.window["units"].get("iters")
    return iters / ev.window["seconds"] if iters else None
