"""kernels (ops/histogram.py): share of the device's busy time spent in
the planar histogram kernel, `histogram_planar_pallas`, by the name the
Pallas call gives its custom call in the trace; mean over the chips."""

KERNEL = "histogram_planar_pallas"


def read(ev):
    if ev.trace is None:
        return None
    return 100.0 * ev.trace.op_share(KERNEL) or None
