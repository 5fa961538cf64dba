"""kernels (ops/plane.py): share of the device's busy time spent in the
partition kernel, `partition_pallas2` or the single-scratch
`partition_pallas`, by the name the Pallas call gives its custom call in
the trace; mean over the chips."""

KERNEL = "partition_pallas"


def read(ev):
    if ev.trace is None:
        return None
    return 100.0 * ev.trace.op_share(KERNEL) or None
