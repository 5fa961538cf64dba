"""grower (treelearner/fused.py, ops/plane.py): seconds from the upload of
the code matrix to the packed code planes being ready on the device, the
eager ops' compile or cache fetch included, from the program's own
always-on stage table (`obs.stage_seconds()["state/pack_codes"]`)."""
from benchmarks.harness import scopes


def read(ev):
    return scopes.stage_s("state/pack_codes")
