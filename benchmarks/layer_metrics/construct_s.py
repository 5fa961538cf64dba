"""dataset (io/dataset.py, io/binning.py, native/): host seconds of
lgb.Dataset(...).construct()."""


def read(ev):
    return ev.stages.get("construct")
