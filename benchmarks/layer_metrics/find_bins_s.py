"""dataset (io/dataset.py, io/binning.py): host seconds the program spent
finding the bin boundaries of every column over the sample, from its own
always-on stage table (`obs.stage_seconds()["construct/find_bins"]`)."""
from benchmarks.harness import scopes


def read(ev):
    return scopes.stage_s("construct/find_bins")
