"""On-demand-built native host kernels (ctypes over a g++-compiled
shared object — no pybind11 dependency).

The TPU compute path is JAX/XLA; these kernels cover the host-side
runtime work the reference implements in C++ (bin boundary search,
column bin conversion — src/io/bin.cpp) where Python-loop cost is
material at load time. The built library is named by a hash of
binning.cpp, so only the committed source decides what is loaded — a
stale or foreign .so in the tree is never picked up. Without a working
compiler the pure-Python implementations are used, with a warning
(LIGHTGBM_TPU_NO_NATIVE=1 selects them deliberately).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

import numpy as np

from ..utils import log

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "binning.cpp")

_lib = None
_tried = False


def library_path() -> str:
    """`_native_<sha256(binning.cpp)[:12]>.so` next to the source."""
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"_native_{digest}.so")


def implementation() -> str:
    """Which binning implementation this process uses."""
    return f"native ({os.path.basename(library_path())})" \
        if _load() is not None else "python"


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
        log.info("Host binning: python (LIGHTGBM_TPU_NO_NATIVE is set)")
        return None
    try:
        so = library_path()
        if not os.path.exists(so):
            tmp = f"{so}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["g++", "-O3", "-std=c++17", "-fopenmp", "-shared",
                     "-fPIC", _SRC, "-o", tmp],
                    check=True, capture_output=True, timeout=120)
            except subprocess.CalledProcessError:
                subprocess.run(  # toolchains without libgomp
                    ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", _SRC,
                     "-o", tmp],
                    check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)
        lib = ctypes.CDLL(so)
        lib.lgbt_greedy_find_bin.restype = ctypes.c_int
        lib.lgbt_greedy_find_bin.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int, ctypes.c_int64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_double)]
        lib.lgbt_values_to_bins.restype = None
        lib.lgbt_values_to_bins.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint16)]
        _lib = lib
        log.info("Host binning: native (%s)", os.path.basename(so))
    except (OSError, subprocess.SubprocessError) as exc:
        detail = getattr(exc, "stderr", b"") or b""
        log.warning("Host binning: python loops — building %s failed: %s %s",
                    os.path.basename(_SRC), exc,
                    detail.decode(errors="replace")[-400:])
        _lib = None
    return _lib


def greedy_find_bin_native(distinct_values: np.ndarray, counts: np.ndarray,
                           max_bin: int, total_cnt: int,
                           min_data_in_bin: int):
    """C++ GreedyFindBin; returns a list of bounds or None (no native)."""
    lib = _load()
    if lib is None:
        return None
    dv = np.ascontiguousarray(distinct_values, dtype=np.float64)
    cn = np.ascontiguousarray(counts, dtype=np.int64)
    out = np.empty(max_bin + 2, dtype=np.float64)
    n = lib.lgbt_greedy_find_bin(
        dv.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        cn.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(dv), int(max_bin), int(total_cnt), int(min_data_in_bin),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return out[:n].tolist()


def values_to_bins_native(values: np.ndarray, bounds: np.ndarray):
    """C++ binary-search column conversion; None when no native lib.
    Caller handles NaN masking."""
    lib = _load()
    if lib is None:
        return None
    v = np.ascontiguousarray(values, dtype=np.float64)
    b = np.ascontiguousarray(bounds, dtype=np.float64)
    out = np.empty(len(v), dtype=np.uint16)
    lib.lgbt_values_to_bins(
        v.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(v),
        b.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), len(b),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)))
    return out
