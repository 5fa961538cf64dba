"""Row-wise multi-value histogram layout (ops/multival.py) + the
occupancy-driven dispatcher.

Oracle strategy mirrors test_kernels.py: the pallas kernels run in
interpret mode on CPU and must match the XLA scatter-add oracle
(histogram_multival_xla) — allclose at f32 (Precision.HIGHEST, only
summation-order noise) and BIT-EXACT for the quantized integer path.
One level up, the reconstructed group/feature histograms must match the
column-major scatter oracle on the same leaf window, and a full CPU
training run through the serial learner's multival entry must
reproduce the planar run's predictions.

Everything here is tiny-shape (<=640 rows, <=48 bundle groups) so the
whole file stays in the low seconds — the tier-1 suite grazes its
timeout.
"""
import importlib.util
import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import multival as MV
from lightgbm_tpu.ops import plane

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- fixtures

def make_wide_sparse(n=512, nvars=48, ncats=8, seed=0):
    """Allstate-like one-hot design: nvars categorical variables with a
    dominant level -> EFB bundles each variable, the dominant bin is the
    sampled default code, and mean present codes/row ~ 0.1 * nvars."""
    rng = np.random.RandomState(seed)
    p = np.full(ncats, 0.1 / (ncats - 1))
    p[0] = 0.9
    X = np.zeros((n, nvars * ncats))
    for v in range(nvars):
        cat = rng.choice(ncats, size=n, p=p)
        X[np.arange(n), v * ncats + cat] = 1.0
    y = (X[:, 1] + X[:, ncats + 1] + rng.randn(n) * 0.3 > 0.2)
    return X, y.astype(np.float64)


def make_codes_fixture(n=512, G=40, k_present=4, seed=0):
    """Direct [n, G] bin matrix with EXACTLY k_present non-default codes
    per row (default bin 0 everywhere) — small enough that row_capacity
    stays at the 8-slot floor."""
    rng = np.random.RandomState(seed)
    gnb = rng.randint(2, 8, size=G).astype(np.int32)
    bins = np.zeros((n, G), np.uint8)
    for i in range(n):
        cols = rng.choice(G, size=k_present, replace=False)
        bins[i, cols] = [rng.randint(1, gnb[c]) for c in cols]
    return bins, gnb, np.zeros(G, np.int32)


def occ_like(num_groups, row_nnz_mean, row_nnz_max=4):
    """A dataset-shaped namespace carrying synthetic occupancy stats —
    hist_layout only reads `.occupancy`."""
    return types.SimpleNamespace(occupancy=MV.OccupancyStats(
        num_groups=num_groups, row_nnz_mean=row_nnz_mean,
        row_nnz_max=row_nnz_max,
        default_code=np.zeros(num_groups, np.int32),
        group_density=np.zeros(num_groups, np.float32),
        sample_rows=1000))


# ----------------------------------------------- layout building blocks

def test_bucket_row_capacity_properties():
    prev = 0
    for nnz in range(0, 300, 7):
        cap = MV.bucket_row_capacity(nnz)
        assert cap % 8 == 0, (nnz, cap)          # mv planes need no pad
        assert cap >= nnz + 1, (nnz, cap)        # room for the sentinel
        assert cap >= prev                        # monotone ladder
        prev = cap
    assert MV.bucket_row_capacity(0) == 8
    assert MV.bucket_row_capacity(7) == 8


def test_build_rowwise_codes_roundtrip():
    bins, gnb, default = make_codes_fixture(n=256)
    codes, lay = MV.build_rowwise_codes(bins, gnb, default)
    T = int(gnb.sum())
    assert lay.total_bins == T and lay.nnz_max == 4
    assert lay.row_capacity == 8 and codes.shape == (256, 8)
    # slot 0 is the sentinel (flat cell T = leaf totals), pads are -1
    np.testing.assert_array_equal(codes[:, 0], T)
    assert ((codes[:, 1:] == -1) | (codes[:, 1:] >= 0)).all()
    # decode every present code back to its (group, bin) cell
    off = MV.flat_offsets(gnb)
    decoded = np.zeros_like(bins)
    for i in range(256):
        for c in codes[i, 1:]:
            if c < 0:
                continue
            g = int(np.searchsorted(off, c, side="right")) - 1
            decoded[i, g] = c - off[g]
    np.testing.assert_array_equal(decoded, bins)
    # a too-small explicit capacity is a hard error, never truncation
    with pytest.raises(ValueError):
        MV.build_rowwise_codes(bins, gnb, default, row_capacity=4)


def test_measure_occupancy_on_fixture():
    bins, gnb, _ = make_codes_fixture()
    occ = MV.measure_occupancy(bins)
    assert occ.num_groups == bins.shape[1]
    np.testing.assert_array_equal(occ.default_code, 0)
    assert occ.row_nnz_mean == pytest.approx(4.0)
    assert occ.row_nnz_max == 4


# ------------------------------------------------------- kernel parity

def _rand_gh(n, seed=1):
    rng = np.random.RandomState(seed)
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    h = jnp.asarray((rng.rand(n) + 0.5).astype(np.float32))
    return g, h


def test_pallas_kernel_matches_xla_oracle_f32():
    bins, gnb, default = make_codes_fixture()
    codes, lay = MV.build_rowwise_codes(bins, gnb, default)
    g, h = _rand_gh(bins.shape[0])
    codes_j = jnp.asarray(codes)
    oracle = MV.histogram_multival_xla(codes_j, g, h, lay.total_bins)
    out = MV.histogram_multival_pallas(
        MV.slot_major(codes_j), MV.gh_planes(g, h),
        total_bins=lay.total_bins, rows_per_block=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)
    # sentinel cell T carries the leaf totals
    np.testing.assert_allclose(np.asarray(out[-1]),
                               [float(g.sum()), float(h.sum())],
                               rtol=1e-5)


def test_pallas_kernel_matches_xla_oracle_quantized_exact():
    bins, gnb, default = make_codes_fixture(seed=2)
    codes, lay = MV.build_rowwise_codes(bins, gnb, default)
    rng = np.random.RandomState(3)
    qg = jnp.asarray(rng.randint(-2000, 2000, bins.shape[0]), jnp.int32)
    qh = jnp.asarray(rng.randint(0, 3000, bins.shape[0]), jnp.int32)
    codes_j = jnp.asarray(codes)
    oracle = MV.histogram_multival_xla(codes_j, qg, qh, lay.total_bins)
    out = MV.histogram_multival_pallas(
        MV.slot_major(codes_j), MV.gh_planes(qg, qh, quant=True),
        total_bins=lay.total_bins, rows_per_block=128, interpret=True,
        quant=True)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


@pytest.mark.parametrize("quant", [False, True])
def test_planar_state_variant_dynamic_grid(quant):
    """histogram_multival_planar reads the [P, R] planar state directly;
    the leaf window rides the PR 10 dynamic grid, so partial blocks and
    non-block-aligned starts must mask exactly."""
    n = 512
    bins, gnb, default = make_codes_fixture(n=n, seed=4)
    codes, lay = MV.build_rowwise_codes(bins, gnb, default)
    rng = np.random.RandomState(5)
    if quant:
        g = rng.randint(-2000, 2000, n).astype(np.int32)
        h = rng.randint(0, 3000, n).astype(np.int32)
        gh_rows = np.asarray(MV.gh_planes(jnp.asarray(g), jnp.asarray(h),
                                          quant=True))
    else:
        g = rng.randn(n).astype(np.float32)
        h = (rng.rand(n) + 0.5).astype(np.float32)
        gh_rows = np.asarray(MV.gh_planes(jnp.asarray(g), jnp.asarray(h)))
    # hand-built planar state: gh planes at grad_plane=2 (non-zero
    # in-block offset), mv planes at 8
    grad_plane = 2
    data = np.zeros((16, n), np.int32)
    data[grad_plane] = gh_rows[0]          # bitcast grad / packed word
    data[grad_plane + 1] = gh_rows[1]      # bitcast hess (zeros if quant)
    data[8:16] = np.asarray(MV.slot_major(jnp.asarray(codes)))
    data_j = jnp.asarray(data)
    for start, count in ((0, n), (96, 130), (384, 128), (200, 1)):
        out = MV.histogram_multival_planar(
            data_j, start, count, mv_start=8, mv_planes=8,
            total_bins=lay.total_bins, grad_plane=grad_plane,
            rows_per_block=128, interpret=True, quant=quant)
        sel = slice(start, start + count)
        oracle = MV.histogram_multival_xla(
            jnp.asarray(codes[sel]), jnp.asarray(g[sel]),
            jnp.asarray(h[sel]), lay.total_bins)
        if quant:
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(oracle))
        else:
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(oracle),
                                       rtol=1e-5, atol=1e-5)


def test_leaf_entry_quant_pallas_matches_xla_path():
    bins, gnb, default = make_codes_fixture(n=256, seed=6)
    codes, lay = MV.build_rowwise_codes(bins, gnb, default)
    rng = np.random.RandomState(7)
    qg = jnp.asarray(rng.randint(-500, 500, 256), jnp.int32)
    qh = jnp.asarray(rng.randint(0, 900, 256), jnp.int32)
    perm = jnp.asarray(rng.permutation(256).astype(np.int32))
    kw = dict(capacity=256, total_bins=lay.total_bins)
    ref = MV.leaf_histogram_multival(jnp.asarray(codes), perm, 32, 150,
                                     qg, qh, use_pallas=False, **kw)
    out = MV.leaf_histogram_multival(jnp.asarray(codes), perm, 32, 150,
                                     qg, qh, use_pallas=True,
                                     interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_group_hist_reconstruction_matches_column_scatter():
    """flat [T+1, 2] -> group [G, Bg, 2] reconstruction (absent default
    cells rebuilt from the sentinel totals) against the column-major
    scatter oracle on the raw bin matrix."""
    bins, gnb, default = make_codes_fixture(n=300, seed=8)
    codes, lay = MV.build_rowwise_codes(bins, gnb, default)
    g, h = _rand_gh(300, seed=9)
    flat = MV.histogram_multival_xla(jnp.asarray(codes), g, h,
                                     lay.total_bins)
    ghist = MV.group_hist_from_flat(flat, MV.group_tables(gnb, default))
    oracle = H.histogram_scatter(jnp.asarray(bins.astype(np.int32)),
                                 g, h, int(gnb.max()))
    np.testing.assert_allclose(np.asarray(ghist),
                               np.asarray(oracle)[:, :int(gnb.max())],
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------- occupancy-driven dispatch

def test_hist_layout_auto_thresholds():
    cfg = Config.from_params({})
    # wide AND sparse -> multival
    assert H.hist_layout(cfg, occ_like(48, 4.8)) == "multival"
    assert H.hist_layout(cfg, occ_like(32, 8.0)) == "multival"
    # too few groups (HIGGS-like narrow shape) -> planar
    assert H.hist_layout(cfg, occ_like(31, 2.0)) == "planar"
    # too dense -> planar
    assert H.hist_layout(cfg, occ_like(64, 17.0)) == "planar"
    # no measured occupancy (or no dataset handle at all) -> planar
    assert H.hist_layout(cfg, types.SimpleNamespace(occupancy=None)) \
        == "planar"
    assert H.hist_layout(cfg, None) == "planar"


def test_hist_layout_override_wins():
    wide, dense = occ_like(48, 4.8), occ_like(16, 14.0)
    cfg_p = Config.from_params({"tpu_hist_layout": "planar"})
    cfg_m = Config.from_params({"tpu_hist_layout": "multival"})
    assert H.hist_layout(cfg_p, wide) == "planar"
    assert H.hist_layout(cfg_m, dense) == "multival"


def test_hist_layout_on_real_datasets():
    Xw, _ = make_wide_sparse(n=320)
    dsw = BinnedDataset.from_matrix(Xw, Config.from_params(
        {"min_data_in_leaf": 5}))
    assert dsw.occupancy is not None
    assert dsw.occupancy.num_groups >= MV.MULTIVAL_MIN_GROUPS
    assert H.hist_layout(Config.from_params({}), dsw) == "multival"
    # dense-narrow (HIGGS-like): every column dense, 28 features
    Xd = np.random.RandomState(0).randn(256, 28)
    dsd = BinnedDataset.from_matrix(Xd, Config.from_params(
        {"min_data_in_leaf": 5}))
    assert dsd.occupancy is not None
    assert H.hist_layout(Config.from_params({}), dsd) == "planar"


def test_hist_method_dispatch(monkeypatch):
    Xw, _ = make_wide_sparse(n=320)
    cfg = Config.from_params({"min_data_in_leaf": 5})
    dsw = BinnedDataset.from_matrix(Xw, cfg)
    # off-TPU every learner keeps the exact scatter path
    assert H.hist_method(cfg, dsw) is None
    monkeypatch.setattr(H, "_use_tpu", lambda: True)
    assert H.hist_method(cfg, dsw) == "multival_pallas"
    # no dataset handle (host-loop parallel learners) -> planar kernels
    assert H.hist_method(cfg, None) == "radix_pallas_bf16"
    cfg32 = Config.from_params({"min_data_in_leaf": 5,
                                "tpu_hist_dtype": "float32"})
    assert H.hist_method(cfg32, None) == "radix_pallas"
    # the column-major dispatch refuses the row-wise method outright
    with pytest.raises(ValueError):
        H.histogram(jnp.zeros((4, 2), jnp.int32), jnp.zeros(4),
                    jnp.zeros(4), 4, method="multival_pallas")


def test_hist_method_values_are_all_accepted(monkeypatch):
    """Whatever hist_method() can return is something a consumer takes:
    the column-major dispatch for None / radix_pallas[_bf16], the fused
    grower (with the dataset handle) for multival_pallas. Anything else
    — a method of a deleted kernel, a typo — raises: it used to fall
    through to the scatter oracle in silence."""
    import itertools
    Xw, _ = make_wide_sparse(n=320)
    dsw = BinnedDataset.from_matrix(Xw, Config.from_params(
        {"min_data_in_leaf": 5}))
    args = (jnp.zeros((4, 2), jnp.int32), jnp.ones(4), jnp.ones(4), 4)
    for bad in ("radix", "radix_bf16", "pallas", "radix_palas"):
        with pytest.raises(ValueError):
            H.histogram(*args, method=bad)
    taken = []
    monkeypatch.setattr(
        H, "histogram_radix_pallas",
        lambda bins, grad, hess, num_bins, dtype=jnp.float32:
        taken.append(dtype))
    seen = set()
    for use_tpu, dev, dt, layout, ds in itertools.product(
            (False, True), ("tpu", "cpu"), ("bfloat16", "float32"),
            ("auto", "planar", "multival"), (None, dsw)):
        monkeypatch.setattr(H, "_use_tpu", lambda v=use_tpu: v)
        m = H.hist_method(Config.from_params(
            {"device_type": dev, "tpu_hist_dtype": dt,
             "tpu_hist_layout": layout}), ds)
        seen.add(m)
        if m == "multival_pallas":
            assert ds is not None and use_tpu and dev == "tpu"
        else:
            H.histogram(*args, method=m)      # does not raise
            if m is not None:
                assert taken.pop() == (jnp.float32 if dt == "float32"
                                       else jnp.bfloat16)
    assert seen == {None, "radix_pallas", "radix_pallas_bf16",
                    "multival_pallas"}


def test_dispatch_telemetry_counters(monkeypatch):
    from lightgbm_tpu.obs import registry as R
    reg = R.MetricsRegistry()
    R.activate(reg)
    try:
        Xw, _ = make_wide_sparse(n=320)
        cfg = Config.from_params({"min_data_in_leaf": 5})
        dsw = BinnedDataset.from_matrix(Xw, cfg)
        monkeypatch.setattr(H, "_use_tpu", lambda: True)
        assert H.hist_method(cfg, dsw) == "multival_pallas"
        assert reg.counters.get("hist.layout_multival", 0) >= 1
        assert reg.gauges["hist.row_nnz_mean"] == pytest.approx(
            dsw.occupancy.row_nnz_mean)
        H.hist_method(cfg, None)
        assert reg.counters.get("hist.layout_planar", 0) >= 1
        bins, gnb, default = make_codes_fixture(n=64)
        MV.build_rowwise_codes(bins, gnb, default)
        assert reg.counters.get("hist.multival_rows", 0) == 64
    finally:
        R.deactivate(reg)


# ------------------------------------------------------ AOT signatures

def test_config_signature_splits_on_layout():
    from lightgbm_tpu.compile.signature import config_signature
    sigs = {json.dumps(config_signature(Config.from_params(
        {"tpu_hist_layout": v})), sort_keys=True)
        for v in ("auto", "planar", "multival")}
    assert len(sigs) == 3


def test_trace_signature_folds_derived_occupancy_only():
    Xw, _ = make_wide_sparse(n=320)
    ds = BinnedDataset.from_matrix(Xw, Config.from_params(
        {"min_data_in_leaf": 5}))
    occ = ds.occupancy

    def sig():
        ds._trace_sig = None
        return ds.trace_signature()

    base = sig()
    # dropping occupancy changes the identity (planar-only program set)
    ds.occupancy = None
    assert sig() != base
    # default codes are closed over by serial entries -> must split
    ds.occupancy = occ._replace(default_code=occ.default_code + 1)
    assert sig() != base
    # jittery float stats must NOT fracture the key space: same bucketed
    # capacity + same wide-sparse decision => same signature
    ds.occupancy = occ._replace(row_nnz_mean=occ.row_nnz_mean + 0.01)
    assert sig() == base
    same_bucket = MV.bucket_row_capacity(occ.row_nnz_max + 1) \
        == MV.bucket_row_capacity(occ.row_nnz_max)
    ds.occupancy = occ._replace(row_nnz_max=occ.row_nnz_max + 1)
    assert (sig() == base) == same_bucket
    # a different capacity bucket is a different multival plane shape
    ds.occupancy = occ._replace(row_nnz_max=occ.row_nnz_max + 100)
    assert sig() != base
    ds.occupancy = occ
    assert sig() == base


# ------------------------------------------------- learner integration

def test_serial_train_parity_multival_vs_planar(monkeypatch):
    """Full CPU training with the serial learner routed through the
    multival entry (XLA path) must reproduce the stock run."""
    # AOT off: a warm executable store would replay the multival program
    # without re-tracing, and the call counter below only fires at trace.
    # The live manager snapshots the env at construction, so patch both.
    from lightgbm_tpu.compile.manager import get_manager
    monkeypatch.setenv("LGBM_TPU_AOT", "0")
    monkeypatch.setattr(get_manager(), "aot_enabled", False)
    X, y = make_wide_sparse(n=400)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, "deterministic": True,
              "tpu_fused": False}
    ref = lgb.train(dict(params), lgb.Dataset(X, label=y),
                    num_boost_round=5)
    p_ref = ref.predict(X)
    calls = []
    real = MV.leaf_histogram_multival

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **dict(kw, use_pallas=False))

    monkeypatch.setattr(H, "hist_method",
                        lambda config, dataset=None: "multival_pallas")
    monkeypatch.setattr(MV, "leaf_histogram_multival", counted)
    mv = lgb.train(dict(params), lgb.Dataset(X, label=y),
                   num_boost_round=5)
    p_mv = mv.predict(X)
    assert calls, "serial learner never took the multival entry"
    np.testing.assert_allclose(p_mv, p_ref, rtol=1e-4, atol=5e-5)


def test_fused_leaf_hist_multival_matches_scatter(monkeypatch):
    """The fused grower's multival leaf histogram (dynamic-grid kernel
    over the planar state's mv planes, interpret mode) against the
    per-feature scatter oracle."""
    from lightgbm_tpu.treelearner.fused import FusedSerialGrower
    X, _ = make_wide_sparse(n=512)
    cfg = Config.from_params({"min_data_in_leaf": 5,
                              "tpu_hist_dtype": "float32"})
    ds = BinnedDataset.from_matrix(X, cfg)
    monkeypatch.setattr(H, "_use_tpu", lambda: True)
    fl = FusedSerialGrower(ds, cfg)
    monkeypatch.undo()
    assert fl._hist_method == "multival_pallas"
    assert fl._mv_dev is not None
    assert fl.layout.mv_planes == fl._mv_layout.row_capacity
    assert fl.layout.mv_start % 8 == 0
    g, h = _rand_gh(X.shape[0], seed=11)
    data = plane.build_data(fl.layout, fl.codes_planes(), g, h,
                            mv=fl._mv_dev)
    fbins = jnp.asarray(ds.feature_bins().astype(np.int32))
    for start, count in ((0, X.shape[0]), (64, 200)):
        out = fl._leaf_hist_multival(data, jnp.int32(start),
                                     jnp.int32(count))
        sel = slice(start, start + count)
        oracle = H.histogram_scatter(fbins[sel], g[sel], h[sel],
                                     ds.max_num_bin)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=1e-4, atol=1e-3)


# ----------------------------------------------------- uint16 EFB path

def make_exclusive_highcard(n=400, groups=8, feats_per_group=6, seed=0):
    """Mutually exclusive sparse features with ~n/feats_per_group
    distinct values each, so one bundle of feats_per_group features
    needs well over 256 bins."""
    rng = np.random.RandomState(seed)
    f = groups * feats_per_group
    X = np.zeros((n, f))
    for g in range(groups):
        owner = rng.randint(0, feats_per_group, size=n)
        vals = rng.rand(n) * (g + 1) + 0.1
        X[np.arange(n), g * feats_per_group + owner] = vals
    return X


def test_uint16_bundles_roundtrip():
    X = make_exclusive_highcard()
    # min_data_in_bin=1: every distinct value gets a bin, so each
    # 6-feature bundle carries ~6 * 67 bins — far past uint8
    binning = {"min_data_in_leaf": 5, "min_data_in_bin": 1}
    cfg16 = Config.from_params(dict(binning, efb_max_bundle_bins=1024))
    ds16 = BinnedDataset.from_matrix(X, cfg16)
    assert ds16.bundles is not None
    assert int(ds16.bundles.group_num_bins.max()) > 256
    assert ds16.bins.dtype == np.uint16
    # default budget keeps every group within uint8
    ds8 = BinnedDataset.from_matrix(X, Config.from_params(dict(binning)))
    assert ds8.bins.dtype == np.uint8
    assert int(ds8.bundles.group_num_bins.max()) <= 256
    assert ds16.bins.shape[1] < ds8.bins.shape[1]
    # lossless codes: decoded per-feature view equals the unbundled one
    ds_off = BinnedDataset.from_matrix(X, Config.from_params(
        dict(binning, enable_bundle=False)))
    np.testing.assert_array_equal(ds16.feature_bins(), ds_off.bins)
    # histogram parity through the uint16 per-feature gather tables
    from lightgbm_tpu.io.efb import per_feature_hist
    g, h = _rand_gh(X.shape[0], seed=12)
    ghist = H.histogram_scatter(ds16.device_bins(), g, h,
                                ds16.group_max_bins)
    total = ghist[0].sum(axis=0)
    fhist = per_feature_hist(ghist, ds16.device_hist_tables(),
                             total[0], total[1])
    oracle = H.histogram_scatter(jnp.asarray(ds_off.bins.astype(np.int32)),
                                 g, h, ds_off.max_num_bin)
    np.testing.assert_allclose(np.asarray(fhist), np.asarray(oracle),
                               rtol=1e-4, atol=1e-3)


def test_efb_conflict_budget_knobs():
    cfg = Config.from_params({"max_conflict_rate": 0.05,
                              "efb_max_bundle_bins": 512})
    assert cfg.efb_max_conflict_rate == 0.05
    assert cfg.efb_max_bundle_bins == 512
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        Config.from_params({"efb_max_conflict_rate": 1.5})
    with pytest.raises(LightGBMError):
        Config.from_params({"efb_max_bundle_bins": 1})


# ------------------------------------------------- static-analysis gate

def test_meshlint_covers_multival_clean():
    # test_meshlint.py already runs the package-wide zero-finding gates;
    # a single-file Package keeps this check out of the full ~11 s
    # reparse while still linting the new module's own source.
    from lightgbm_tpu.analysis import dtype_flow, kernel_contract
    from lightgbm_tpu.analysis.core import Package
    rel = "lightgbm_tpu/ops/multival.py"
    assert os.path.exists(os.path.join(REPO_ROOT, rel)), \
        "multival not under the scanned package dir"
    pkg = Package(REPO_ROOT, [rel])
    found = kernel_contract.check(pkg) + dtype_flow.check(pkg)
    mv = [str(f) for f in found if "multival" in f.path]
    assert mv == []


def test_analysis_baseline_stays_empty():
    path = os.path.join(REPO_ROOT, "lightgbm_tpu", "analysis",
                        "baseline.json")
    with open(path) as fh:
        assert json.load(fh) == {"version": 1, "entries": {}}


# -------------------------------------------------- wide perf gate

def _load_regress():
    spec = importlib.util.spec_from_file_location(
        "check_perf_regress",
        os.path.join(REPO_ROOT, "scripts", "check_perf_regress.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench(value, layout):
    return {"metric": "wide_sparse_train_wallclock", "value": value,
            "unit": "seconds", "vs_baseline": 148.2,
            "hist_layout": layout, "iter_p50_s": value / 10.0}


def test_gate_wide_layout_flip_and_regression(tmp_path):
    pr = _load_regress()
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_bench(10.0, "multival")))

    def run(rec):
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(rec))
        return pr.gate_wide(str(fresh), str(base), 0.10)

    assert run(_bench(10.2, "multival")) == 0          # within tol
    assert run(_bench(20.0, "multival")) == 1          # wall regressed
    # silent fallback to planar fails even at equal wall time
    assert run(_bench(10.0, "planar")) == 1
