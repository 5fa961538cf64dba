"""dataset (io/dataset.py, native/): host seconds the program spent pushing
every row through the bin mappers into the code matrix, from its own
always-on stage table (`obs.stage_seconds()["construct/bin_rows"]`)."""
from benchmarks.harness import scopes


def read(ev):
    return scopes.stage_s("construct/bin_rows")
