"""Native C++ binning kernels must be bit-identical to the Python
reference implementations (the package's GPU_DEBUG_COMPARE analogue
for host kernels)."""
import os

import numpy as np
import pytest

from lightgbm_tpu.native import greedy_find_bin_native, values_to_bins_native


def _python_greedy(dv, cnts, max_bin, total, mdb):
    """Call the pure-Python path by staying under the native threshold
    indirectly: import the function and run its body via a small copy of
    the dispatch-free logic — easiest is to call greedy_find_bin with
    native disabled."""
    import lightgbm_tpu.native as native
    saved = native._lib, native._tried
    native._lib, native._tried = None, True
    try:
        from lightgbm_tpu.io.binning import greedy_find_bin
        return greedy_find_bin(dv, cnts, max_bin, total, mdb)
    finally:
        native._lib, native._tried = saved


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("max_bin,mdb", [(255, 3), (63, 3), (15, 20),
                                         (255, 1)])
def test_greedy_find_bin_native_matches_python(seed, max_bin, mdb):
    rng = np.random.RandomState(seed)
    n = rng.randint(300, 5000)
    dv = np.sort(rng.randn(n) * 10)
    dv = np.unique(dv)
    cnts = rng.randint(1, 50, size=len(dv)).astype(np.int64)
    total = int(cnts.sum())
    native = greedy_find_bin_native(dv, cnts, max_bin, total, mdb)
    if native is None:
        pytest.skip("no native toolchain")
    python = _python_greedy(dv, cnts, max_bin, total, mdb)
    np.testing.assert_array_equal(np.asarray(native), np.asarray(python))


def test_greedy_find_bin_few_distinct():
    dv = np.asarray([1.0, 2.0, 3.0, 10.0])
    cnts = np.asarray([5, 5, 5, 5], dtype=np.int64)
    native = greedy_find_bin_native(dv, cnts, 255, 20, 3)
    if native is None:
        pytest.skip("no native toolchain")
    python = _python_greedy(dv, cnts, 255, 20, 3)
    np.testing.assert_array_equal(np.asarray(native), np.asarray(python))


def test_values_to_bins_native_matches_searchsorted():
    rng = np.random.RandomState(7)
    bounds = np.sort(rng.randn(100))
    bounds[-1] = np.inf
    vals = rng.randn(10000) * 2
    native = values_to_bins_native(vals, bounds)
    if native is None:
        pytest.skip("no native toolchain")
    expect = np.searchsorted(bounds, vals, side="left")
    np.testing.assert_array_equal(native, expect)


def test_full_binning_parity_native_vs_python(monkeypatch):
    """End-to-end: BinMapper.find_bin boundaries identical with and
    without the native kernel."""
    from lightgbm_tpu.io.binning import BinMapper
    import lightgbm_tpu.native as native

    rng = np.random.RandomState(3)
    vals = rng.randn(50000) * 5
    vals[rng.rand(50000) < 0.1] = 0.0

    m1 = BinMapper()
    m1.find_bin(vals[np.abs(vals) > 1e-35], 50000, 255)
    if native._load() is None:
        pytest.skip("no native toolchain")

    saved = native._lib, native._tried
    native._lib, native._tried = None, True
    try:
        m2 = BinMapper()
        m2.find_bin(vals[np.abs(vals) > 1e-35], 50000, 255)
    finally:
        native._lib, native._tried = saved
    np.testing.assert_array_equal(m1.bin_upper_bound, m2.bin_upper_bound)
    assert m1.num_bin == m2.num_bin


def test_loader_keys_library_by_source_hash(tmp_path, monkeypatch):
    """Only binning.cpp decides what is loaded: a stale `_native.so`, or
    a library built from another source, is never picked up."""
    import hashlib
    import shutil
    import lightgbm_tpu.native as native

    if native._load() is None:
        pytest.skip("no native toolchain")
    src = tmp_path / "binning.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    # decoys: loading either would raise (not an ELF file)
    (tmp_path / "_native.so").write_bytes(b"stale")
    (tmp_path / "_native_000000000000.so").write_bytes(b"foreign")

    def fresh_load():
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", False)
        return native._load()

    want = hashlib.sha256(src.read_bytes()).hexdigest()[:12]
    assert fresh_load() is not None
    assert native.library_path() == str(tmp_path / f"_native_{want}.so")
    assert os.path.exists(native.library_path())
    assert native.implementation() == f"native (_native_{want}.so)"

    # the source changes: the old build no longer matches and a new
    # library is built under the new hash
    with open(src, "a") as fh:
        fh.write("\n// edited\n")
    want2 = hashlib.sha256(src.read_bytes()).hexdigest()[:12]
    assert want2 != want
    assert fresh_load() is not None
    assert os.path.basename(native.library_path()) == f"_native_{want2}.so"
    assert os.path.exists(native.library_path())
