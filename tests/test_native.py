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


# --- the row pass (lgbt_bin_rows) against the per-column path ----------

def _per_column(monkeypatch):
    """Send `_bin_rows` down today's per-column path."""
    import lightgbm_tpu.native as native
    monkeypatch.setattr(native, "row_pass_input", lambda data: False)


def _train_table(n=4000, seed=0):
    """A dense column with NaN, one without, a zero-heavy one with NaN,
    a constant (trivial) column, a categorical one, three mutually
    exclusive sparse columns (one EFB bundle) and one of ties."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, 9))
    X[:, 0] = rng.randn(n)
    X[rng.rand(n) < 0.05, 0] = np.nan
    X[:, 1] = rng.randn(n) * 3
    X[:, 2] = np.where(rng.rand(n) < 0.6, 0.0, rng.randn(n))
    X[rng.rand(n) < 0.05, 2] = np.nan
    X[:, 3] = 7.0
    X[:, 4] = rng.randint(0, 12, n)
    X[rng.rand(n) < 0.03, 4] = np.nan
    owner = rng.randint(0, 10, n)
    for k in range(3):
        on = owner == k
        X[on, 5 + k] = rng.rand(on.sum()) * (k + 1) + 0.1
    X[:, 8] = rng.randint(0, 40, n) * 0.25
    return X


def _probe_rows(ds, n_cols, seed=1):
    """Rows that land on every edge of the fitted mappers: each bound
    and one ulp either side, +-0.0, NaN, the zero threshold, huge
    values; categories below, inside, between and past the table; and
    rows where the bundled columns conflict."""
    from lightgbm_tpu.io.binning import BIN_NUMERICAL, K_ZERO_THRESHOLD
    probes = {}
    for i, f in enumerate(ds.real_feature_index):
        m = ds.bin_mappers[i]
        if m.bin_type == BIN_NUMERICAL:
            b = m.bin_upper_bound[np.isfinite(m.bin_upper_bound)]
            vals = np.concatenate([
                b, np.nextafter(b, np.inf), np.nextafter(b, -np.inf),
                [0.0, -0.0, np.nan, K_ZERO_THRESHOLD, -K_ZERO_THRESHOLD,
                 3e38, -3e38]])
        else:
            top = max(m.bin_2_categorical)
            vals = np.concatenate([
                np.arange(-2, top + 3), np.arange(-2, top + 3) + 0.5,
                [-0.0, -0.5, np.nan, 1e10, -1e19, 3e19]])
        probes[f] = vals
    rows = max(len(v) for v in probes.values())
    rng = np.random.RandomState(seed)
    P = np.zeros((rows, n_cols))
    for f, vals in probes.items():
        P[:, f] = np.resize(vals[rng.permutation(len(vals))], rows)
    conflict = np.zeros((64, n_cols))
    conflict[:, 5:8] = rng.rand(64, 3) + 0.1
    conflict[::3, 6] = 0.0
    return np.concatenate([P, conflict])


def _layouts(X):
    wide = np.repeat(X, 2, axis=1)
    tall = np.repeat(X, 2, axis=0)
    return {"C": np.ascontiguousarray(X), "F": np.asfortranarray(X),
            "strided": wide[:, ::2], "rows": tall[::2]}


# the per-column path casts the probes past int64's range on purpose
@pytest.mark.filterwarnings("ignore:invalid value encountered in cast")
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("max_bin", [15, 63, 255, 300])
@pytest.mark.parametrize("missing", ["nan", "zero_as_missing",
                                     "use_missing_false"])
def test_row_pass_codes_equal_per_column_codes(monkeypatch, missing,
                                               max_bin, dtype):
    """The row pass gives today's per-column codes byte for byte, over
    dtypes, layouts, missing-value handling, bin widths (uint16 past
    256), trivial features, a categorical column and an EFB bundle
    whose members conflict, at 1 and 4 threads."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    import lightgbm_tpu.native as native

    if native._load() is None:
        pytest.skip("no native toolchain")
    params = {"max_bin": max_bin, "categorical_feature": "4",
              "zero_as_missing": missing == "zero_as_missing",
              "use_missing": missing != "use_missing_false"}
    X = _train_table()
    ds = BinnedDataset.from_matrix(X, Config.from_params(params))
    assert 3 not in ds.real_feature_index            # trivial: dropped
    assert ds.bundles is not None and any(
        len(g) > 1 for g in ds.bundles.groups)
    assert ds.bins.dtype == (np.uint16 if max_bin > 256 else np.uint8)
    P = np.concatenate([X, _probe_rows(ds, X.shape[1])]).astype(dtype)
    want = {}
    with monkeypatch.context() as mp:
        _per_column(mp)
        for name, data in _layouts(P).items():
            want[name] = ds._bin_rows(data)
    for name, data in _layouts(P).items():
        for threads in (1, 4):
            reg = obs.activate(obs.MetricsRegistry())
            try:
                got = ds._bin_rows(data, threads)
            finally:
                obs.deactivate(reg)
            assert reg.counters["dataset.bin_rows_fused_cols"] == \
                ds.num_features, name
            assert got.dtype == want[name].dtype
            np.testing.assert_array_equal(got, want[name],
                                          err_msg=f"{name}, {threads}")


def test_row_pass_names_the_first_infinite_column(monkeypatch):
    """An infinite value raises the per-column path's fatal, for the
    same (first) column and count."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.utils.log import LightGBMError
    import lightgbm_tpu.native as native

    if native._load() is None:
        pytest.skip("no native toolchain")
    X = _train_table()
    ds = BinnedDataset.from_matrix(
        X, Config.from_params({"categorical_feature": "4"}))
    Xi = X.astype(np.float32)
    Xi[[5, 17], 6] = np.inf          # a bundle member
    Xi[9, 8] = -np.inf               # a later column
    with pytest.raises(LightGBMError) as row:
        ds._bin_rows(Xi)
    with monkeypatch.context() as mp:
        _per_column(mp)
        with pytest.raises(LightGBMError) as col:
            ds._bin_rows(Xi)
    assert str(row.value) == str(col.value)
    assert "'Column_6' (column 6) contains 2 infinite" in str(row.value)


@pytest.mark.parametrize("case", ["dense", "large_category", "csr",
                                  "int_matrix", "no_native"])
def test_bin_rows_counts_columns_by_path(monkeypatch, case):
    """`dataset.bin_rows_fused_cols` / `dataset.bin_rows_column_cols`
    split the used columns by the path that binned them, and the Info
    line names the threads and the split."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.utils import log
    import lightgbm_tpu.native as native

    if native._load() is None:
        pytest.skip("no native toolchain")
    X = _train_table()
    params = {"categorical_feature": "4", "num_threads": 3}
    if case == "large_category":
        X[:, 4] = np.where(X[:, 4] == 5, 70000, X[:, 4])
    data = X
    if case == "csr":
        sp = pytest.importorskip("scipy.sparse")
        data = sp.csr_matrix(np.nan_to_num(X))
    elif case == "int_matrix":
        data = np.nan_to_num(X).astype(np.int64)
    elif case == "no_native":
        _per_column(monkeypatch)
    lines = []
    log.register_log_callback(lines.append)
    reg = obs.activate(obs.MetricsRegistry())
    try:
        ds = BinnedDataset.from_matrix(data, Config.from_params(params))
    finally:
        obs.deactivate(reg)
        log.register_log_callback(None)
    used = ds.num_features
    fused = {"dense": used, "large_category": used - 1}.get(case, 0)
    assert reg.counters["dataset.bin_rows_fused_cols"] == fused
    assert reg.counters["dataset.bin_rows_column_cols"] == used - fused
    said = [ln for ln in lines if "rows in one native pass" in ln]
    if fused:
        assert said == [f"[LightGBM-TPU] [Info] Host binning: rows in one "
                        f"native pass (3 threads, {fused} of {used} "
                        "columns)\n"]
    else:
        assert said == []


def test_validation_set_codes_equal_through_either_path(monkeypatch):
    """A validation set built with `reference=` bins its rows through
    the row pass to the per-column path's codes."""
    import lightgbm_tpu as lgb
    import lightgbm_tpu.native as native

    if native._load() is None:
        pytest.skip("no native toolchain")
    X = _train_table(seed=0)
    Xv = _train_table(n=3000, seed=5).astype(np.float32)
    params = {"categorical_feature": "4", "verbose": -1}
    train = lgb.Dataset(X, label=X[:, 1] > 0, params=params).construct()
    got = lgb.Dataset(Xv, label=Xv[:, 1] > 0, reference=train,
                      params=params).construct()._handle.bins
    _per_column(monkeypatch)
    want = lgb.Dataset(Xv, label=Xv[:, 1] > 0, reference=train,
                       params=params).construct()._handle.bins
    assert train._handle.bundles is not None
    np.testing.assert_array_equal(got, want)


# --- the bin-finding pass (lgbt_find_bins) against BinMapper.find_bin ---

_MAPPER_FIELDS = ("num_bin", "missing_type", "default_bin", "most_freq_bin",
                  "sparse_rate", "is_trivial", "min_val", "max_val",
                  "bin_type", "categorical_2_bin", "bin_2_categorical")


def _python_bins(monkeypatch):
    """Send this process to the pure-Python paths, as
    LIGHTGBM_TPU_NO_NATIVE does at start-up."""
    import lightgbm_tpu.native as native
    monkeypatch.setenv("LIGHTGBM_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)


def _bin_table(kind, seed=0):
    """(sample, categorical columns). "edges": a column with NaN, one
    without, zero-heavy with NaN, constant, all NaN, all negative, all
    positive, few distinct values (<= 15), values within the zero
    threshold, values one and three ulps apart, ties around zero, a +-inf
    column, a categorical one and one whose most frequent value is a
    bin's upper bound. "distinct": 200,000 distinct values,
    alone and with NaN."""
    from lightgbm_tpu.io.binning import K_ZERO_THRESHOLD
    rng = np.random.RandomState(seed)
    if kind == "distinct":
        n = 200_000
        X = np.empty((n, 2))
        X[:, 0] = rng.randn(n) * 3
        X[:, 1] = rng.randn(n)
        X[rng.rand(n) < 0.02, 1] = np.nan
        assert len(np.unique(X[:, 0])) == n
        return X, set()
    n = 3000
    X = np.empty((n, 15))
    X[:, 0] = rng.randn(n)
    X[rng.rand(n) < 0.05, 0] = np.nan
    X[:, 1] = rng.randn(n) * 3
    X[:, 2] = np.where(rng.rand(n) < 0.6, 0.0, rng.randn(n))
    X[rng.rand(n) < 0.05, 2] = np.nan
    X[:, 3] = 7.0
    X[:, 4] = np.nan
    X[:, 5] = -np.abs(rng.randn(n)) - 0.01
    X[:, 6] = np.abs(rng.randn(n)) + 0.01
    X[:, 7] = rng.randint(0, 12, n) - 4.0
    X[:, 8] = rng.choice([0.0, -0.0, K_ZERO_THRESHOLD, -K_ZERO_THRESHOLD,
                          np.nextafter(K_ZERO_THRESHOLD, 1), 2e-35,
                          -3e-35, 1.0], n)
    # x, x + 1 ulp (joins x's run) and x + 3 ulps: a run of its own, and
    # the bound between the two runs is that value itself
    up = np.nextafter
    x = rng.choice([-1.5, 0.25, 2.0], n)
    X[:, 9] = np.choose(rng.randint(0, 3, n),
                        [x, up(x, np.inf), up(up(up(x, 9), 9), 9)])
    X[:, 10] = rng.randint(-3, 4, n) * 1e-3
    X[:, 11] = rng.randn(n)
    X[rng.rand(n) < 0.01, 11] = np.inf
    X[rng.rand(n) < 0.01, 11] = -np.inf
    X[:, 12] = rng.randint(0, 9, n)
    X[:, 13] = rng.randn(n) * 1e6
    X[rng.rand(n) < 0.5, 13] = 0.0
    # most of the column at a value that is its bin's upper bound
    X[:, 14] = np.where(rng.rand(n) < 0.85, up(up(up(2.0, 9), 9), 9),
                        up(2.0, 9))
    return X, {12}


def _mappers(sample, config, cat_set):
    from lightgbm_tpu import obs
    from lightgbm_tpu.io.dataset import BinnedDataset
    n, cols = sample.shape

    def col_nonzeros(f):
        return np.arange(n), np.asarray(sample[:, f], dtype=np.float64)

    reg = obs.activate(obs.MetricsRegistry())
    try:
        mappers = BinnedDataset._find_bin_mappers_local(
            sample, col_nonzeros, cols, n, config, cat_set)
    finally:
        obs.deactivate(reg)
    return mappers, reg.counters


@pytest.mark.parametrize("pre_filter", [True, False])
@pytest.mark.parametrize("min_data_in_bin", [1, 3])
@pytest.mark.parametrize("max_bin", [15, 63, 255, 300, "by_feature"])
@pytest.mark.parametrize("missing", ["nan", "use_missing_false",
                                     "zero_as_missing"])
@pytest.mark.parametrize("table", ["edges", "distinct"])
def test_find_bins_native_equals_find_bin(monkeypatch, table, missing,
                                          max_bin, min_data_in_bin,
                                          pre_filter):
    """The native pass gives `BinMapper.find_bin`'s mappers to the bit,
    every field, over missing-value handling, bin budgets (per feature
    too), `min_data_in_bin`, the pre-filter, float32 / float64 and C /
    Fortran / strided input at 1 and 4 threads; the categorical column
    stays on the Python path."""
    from lightgbm_tpu.config import Config
    import lightgbm_tpu.native as native

    if native._load() is None:
        pytest.skip("no native toolchain")
    X, cat_set = _bin_table(table)
    params = {"min_data_in_bin": min_data_in_bin, "min_data_in_leaf": 20,
              "feature_pre_filter": pre_filter,
              "zero_as_missing": missing == "zero_as_missing",
              "use_missing": missing != "use_missing_false"}
    if max_bin == "by_feature":
        params["max_bin_by_feature"] = [
            (2, 300, 15, 63, 255, 4, 1)[f % 7] for f in range(X.shape[1])]
    else:
        params["max_bin"] = max_bin
    config = Config.from_params(params)
    with monkeypatch.context() as mp:
        _python_bins(mp)
        want, counted = _mappers(X, config, cat_set)
    assert counted["dataset.find_bins_native_cols"] == 0
    F = X.shape[1]
    by_feature = max_bin == "by_feature"
    n_native = sum(1 for f in range(F) if f not in cat_set and not (
        by_feature and params["max_bin_by_feature"][f] < 2))
    if table == "edges":
        X32 = X.astype(np.float32)
        inputs = {f"{dt}/{name}": data
                  for dt, T in (("f64", X), ("f32", X32))
                  for name, data in _layouts(T).items()}
        # float32 input is held to the oracle on the same float32 values
        oracle = {"f64": want}
        with monkeypatch.context() as mp:
            _python_bins(mp)
            oracle["f32"] = _mappers(X32.astype(np.float64), config,
                                     cat_set)[0]
    else:
        inputs = {"f64/" + k: v for k, v in _layouts(X).items()
                  if k in ("C", "F")}
        oracle = {"f64": want}
    for name, data in inputs.items():
        for threads in (1, 4):
            config.num_threads = threads
            got, counted = _mappers(data, config, cat_set)
            assert counted["dataset.find_bins_native_cols"] == n_native
            assert counted["dataset.find_bins_python_cols"] == F - n_native
            for f, (g, w) in enumerate(zip(got, oracle[name[:3]])):
                where = f"{name}, {threads} threads, column {f}"
                assert g.bin_upper_bound.dtype == np.float64, where
                assert g.bin_upper_bound.tobytes() == \
                    w.bin_upper_bound.tobytes(), where
                for k in _MAPPER_FIELDS:
                    a, b = getattr(g, k), getattr(w, k)
                    assert type(a) is type(b) and a == b, (where, k, a, b)


def test_from_matrix_finds_bins_natively_and_trains_the_same_model(
        monkeypatch):
    """A dense `from_matrix` counts its numerical columns on
    `dataset.find_bins_native_cols` and says so on its Info line, and
    the model it trains is byte-identical to the Python path's, with
    NaN and a categorical column in the table."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.utils import log
    import lightgbm_tpu.native as native

    if native._load() is None:
        pytest.skip("no native toolchain")
    X = _train_table()
    y = (X[:, 1] + np.nan_to_num(X[:, 0]) > 0).astype(np.float64)
    params = {"objective": "binary", "categorical_feature": "4",
              "num_leaves": 7, "num_threads": 2}

    def train():
        lines = []
        log.register_log_callback(lines.append)
        reg = obs.activate(obs.MetricsRegistry())
        try:
            bst = lgb.train(params, lgb.Dataset(X, label=y),
                            num_boost_round=3)
        finally:
            obs.deactivate(reg)
            log.register_log_callback(None)
        said = [ln for ln in lines if "Host bin finding" in ln]
        return bst.model_to_string(), reg.counters, said

    model, counted, said = train()
    assert counted["dataset.find_bins_native_cols"] == 8
    assert counted["dataset.find_bins_python_cols"] == 1
    assert said == ["[LightGBM-TPU] [Info] Host bin finding: columns in "
                    "one native pass (2 threads, 8 of 9 columns)\n"]
    with monkeypatch.context() as mp:
        _python_bins(mp)
        want, counted, said = train()
    assert counted["dataset.find_bins_native_cols"] == 0
    assert counted["dataset.find_bins_python_cols"] == 9
    assert said == []
    assert model == want
