"""On-demand-built native host kernels (ctypes over a g++-compiled
shared object — no pybind11 dependency).

The TPU compute path is JAX/XLA; these kernels cover the host-side
runtime work the reference implements in C++ (bin boundary search,
column bin conversion — src/io/bin.cpp — and the pass that bins every
row of a dense matrix at once) where Python-loop cost is material at
load time. The built library is named by a hash of
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
from typing import List, NamedTuple, Optional, Sequence

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
        lib.lgbt_bin_rows.restype = ctypes.c_int
        lib.lgbt_bin_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_double), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
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


def row_pass_input(data) -> bool:
    """Whether `bin_rows_native` can read `data` where it lies: a 2-D
    aligned float32 / float64 ndarray of native byte order, any strides,
    and the library loaded."""
    return (isinstance(data, np.ndarray) and data.ndim == 2
            and data.dtype in (np.dtype(np.float32), np.dtype(np.float64))
            and data.flags.aligned
            and all(s % data.itemsize == 0 for s in data.strides)
            and _load() is not None)


# a row-pass feature's place in its code column (binning.cpp enum Mode):
# it owns the column, or it is the first / a later member of a bundle's
DIRECT, FIRST_MEMBER, LATER_MEMBER = 0, 1, 2


class RowPassFeature(NamedTuple):
    """One used feature of the row pass (binning.cpp enum Field)."""
    src: int                              # column of the input
    dst: int                              # column of the code matrix
    mode: int = DIRECT
    skip: int = 0                         # a bundle member's most frequent bin
    offset: int = 0                       # its first code in the bundle column
    bounds: Optional[np.ndarray] = None   # numerical: the bounds searched
    nan_bin: int = -1                     # NaN's bin; -1 bins NaN as 0.0
    table: Optional[np.ndarray] = None    # categorical: category -> bin


def bin_rows_native(data: np.ndarray, features: Sequence[RowPassFeature],
                    out: np.ndarray, num_threads: int = 0):
    """Every row of `data` through `features`, in order, into `out`
    ([n, columns] uint8 / uint16, C order) in one native pass
    (binning.cpp lgbt_bin_rows). Returns (each feature's count of +-inf,
    threads used). The caller checks `row_pass_input(data)` first."""
    k, n = len(features), data.shape[0]
    width = 1 << max((len(f.bounds) - 1 for f in features
                      if f.table is None), default=0).bit_length()
    bounds = np.full((k, width), np.inf)
    meta = np.empty((k, 8), dtype=np.int32)
    tables: List[np.ndarray] = []
    at = 0
    for j, f in enumerate(features):
        if f.table is None:
            length, table_at = len(f.bounds), -1
            bounds[j, :length] = f.bounds
        else:
            length, table_at = len(f.table), at
            tables.append(f.table)
            at += length
        meta[j] = (f.src, f.dst, f.mode, f.skip, f.offset, length,
                   f.nan_bin, table_at)
    table = np.concatenate(tables).astype(np.int32) if tables \
        else np.zeros(1, dtype=np.int32)
    src, dst, length = meta[:, 0], meta[:, 1], meta[:, 5]
    if (out.ndim != 2 or out.shape[0] != n or not out.flags.c_contiguous
            or out.dtype not in (np.dtype(np.uint8), np.dtype(np.uint16))
            or np.any((src < 0) | (src >= data.shape[1]))
            or np.any((dst < 0) | (dst >= out.shape[1]))
            or np.any(length < 1)):
        raise ValueError("bin_rows_native: the features do not fit the "
                         "input or the output")
    inf = np.zeros(k, dtype=np.int64)
    threads = _load().lgbt_bin_rows(
        data.ctypes.data, int(data.dtype == np.float64), n,
        data.strides[0] // data.itemsize, data.strides[1] // data.itemsize,
        k, meta.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), width,
        table.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out.ctypes.data, int(out.dtype == np.uint16), out.shape[1],
        inf.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), int(num_threads))
    return inf, threads
