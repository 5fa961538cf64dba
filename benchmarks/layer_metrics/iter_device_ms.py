"""grower (treelearner/fused.py, parallel.py): device milliseconds per
iteration, the union of the device-op intervals of the traced sub-window
over its iterations, mean over the chips."""


def read(ev):
    if ev.trace is None or not ev.traced["units"].get("iters"):
        return None
    return 1e3 * ev.trace.busy_s / ev.traced["units"]["iters"]
