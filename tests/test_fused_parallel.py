"""Fused (single-dispatch) data-parallel learner on the virtual 8-device
CPU mesh: the sharded persistent path must produce the same model as
single-device fused training (the split decisions are made on psum'd
histograms, so trees are replicated by construction).

Non-IID hardening (round-2 verdict item 8): the skewed cases put one
class entirely on one shard and leave some shards with near-empty leaf
windows — the global-count gating must still match serial exactly.
"""
import numpy as np
import jax
import pytest

import lightgbm_tpu as lgb

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 (virtual) devices")


def _train(params, X, y, rounds=8):
    bst = lgb.train(dict(params), lgb.Dataset(X, label=y),
                    num_boost_round=rounds, keep_training_booster=True)
    return bst


def _make(n=6000, f=8, seed=0, sort_labels=False):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1] ** 2 + 0.2 * rng.randn(n) > 0.3)
    y = y.astype(np.float32)
    if sort_labels:
        # one shard ends up holding a single class (non-IID row order)
        order = np.argsort(y, kind="stable")
        X, y = X[order], y[order]
    return X, y


@pytest.mark.parametrize("objective,sort_labels", [
    ("binary", False),
    ("binary", True),          # a shard holds only one class
    ("regression", False),
])
@pytest.mark.slow
def test_fused_dp_matches_serial(objective, sort_labels):
    X, y = _make(sort_labels=sort_labels)
    base = {"objective": objective, "num_leaves": 31, "verbose": -1,
            "learning_rate": 0.1, "min_data_in_leaf": 20}
    b_serial = _train(dict(base, tree_learner="serial"), X, y)
    b_dp = _train(dict(base, tree_learner="data"), X, y)
    from lightgbm_tpu.treelearner.parallel import FusedDataParallelGrower
    assert isinstance(b_dp._gbdt._fused, FusedDataParallelGrower)
    assert b_dp._gbdt._fused_persist
    # early trees must be STRUCTURALLY identical (split decisions come
    # from psum'd histograms); later trees may flip near-tie splits
    # because sharded f32 partial sums round differently than one pass
    # (true of the reference's distributed mode too)
    s1 = b_serial.model_to_string().split("Tree=")
    s2 = b_dp.model_to_string().split("Tree=")
    f1 = [l for l in s1[1].splitlines()
          if l.split("=")[0] in ("num_leaves", "split_feature")]
    f2 = [l for l in s2[1].splitlines()
          if l.split("=")[0] in ("num_leaves", "split_feature")]
    assert f1 == f2, "first tree structure diverged"
    # later trees may flip near-tie splits (sharded f32 partial sums
    # round differently; the skewed-label case amplifies it): the
    # contract is QUALITY parity, as for the reference's distributed
    # learners, not bitwise model identity
    p1 = b_serial.predict(X)
    p2 = b_dp.predict(X)
    assert float(np.mean(np.abs(p1 - p2))) < 0.05
    if objective == "binary":
        ll1 = float(np.mean(-y * np.log(p1 + 1e-9)
                            - (1 - y) * np.log(1 - p1 + 1e-9)))
        ll2 = float(np.mean(-y * np.log(p2 + 1e-9)
                            - (1 - y) * np.log(1 - p2 + 1e-9)))
    else:
        ll1 = float(np.mean((p1 - y) ** 2))
        ll2 = float(np.mean((p2 - y) ** 2))
    assert abs(ll1 - ll2) < 0.02, (ll1, ll2)


def test_fused_dp_uneven_shards():
    """Row count not divisible by the shard count (last shard padded)."""
    X, y = _make(n=6001)
    base = {"objective": "binary", "num_leaves": 15, "verbose": -1}
    b_serial = _train(dict(base, tree_learner="serial"), X, y, rounds=5)
    b_dp = _train(dict(base, tree_learner="data"), X, y, rounds=5)
    p1, p2 = b_serial.predict(X[:1000]), b_dp.predict(X[:1000])
    assert float(np.mean(np.abs(p1 - p2))) < 0.01


def test_fused_dp_sampled_rows_match_serial():
    """GOSS on shards of uneven size: each shard runs the serial grower's
    own per-tree program (FusedSerialGrower._grow_tree) on its resident
    code planes — local bag gathered from them, every local row's leaf by
    replaying the splits over them — so no shard holds a row-major table.
    The third tree is the first grown on a sample."""
    X, y = _make(n=2001)
    base = {"objective": "binary", "boosting": "goss", "num_leaves": 7,
            "learning_rate": 0.5, "verbose": -1}
    b_serial = _train(dict(base, tree_learner="serial"), X, y, rounds=4)
    b_dp = _train(dict(base, tree_learner="data"), X, y, rounds=4)
    g = b_dp._gbdt._fused
    from lightgbm_tpu.treelearner.parallel import FusedDataParallelGrower
    assert isinstance(g, FusedDataParallelGrower)
    assert b_dp._gbdt.bag_data_cnt == 400 + 200
    assert g._cp_sh.shape == (g.layout.code_planes,
                              g.num_shards * g.layout.num_lanes)
    assert g._bins_dev is None and not hasattr(g, "_bins_sh")
    p1, p2 = b_serial.predict(X), b_dp.predict(X)
    assert float(np.mean(np.abs(p1 - p2))) < 1e-4


@pytest.mark.slow
def test_fused_dp_bagging_matches_serial():
    """Round-4: the sharded fused grower covers bagging via per-shard
    local permutations (reference SetBaggingData semantics per machine,
    data_parallel_tree_learner.cpp handles every config through the one
    network layer). Same bag seed => same global bag => near-identical
    models (f32 psum ordering is the only noise)."""
    X, y = _make()
    bag = {"bagging_fraction": 0.8, "bagging_freq": 1, "bagging_seed": 3}
    base = {"objective": "binary", "num_leaves": 31, "verbose": -1,
            "min_data_in_leaf": 20, **bag}
    b_serial = _train(dict(base, tree_learner="serial"), X, y, rounds=6)
    b_dp = _train(dict(base, tree_learner="data"), X, y, rounds=6)
    from lightgbm_tpu.treelearner.parallel import FusedDataParallelGrower
    assert isinstance(b_dp._gbdt._fused, FusedDataParallelGrower)
    assert not b_dp._gbdt._fused_persist   # bagging -> per-tree path
    p1, p2 = b_serial.predict(X), b_dp.predict(X)
    assert float(np.mean(np.abs(p1 - p2))) < 1e-4


@pytest.mark.slow
def test_fused_dp_multiclass_matches_serial():
    """Multiclass (num_class trees/iter) through the sharded per-tree
    fused path."""
    X, y = _make()
    y3 = ((X[:, 0] > 0.5).astype(int)
          + (X[:, 1] > 0).astype(int)).astype(np.float64)
    mc = {"objective": "multiclass", "num_class": 3, "verbose": -1,
          "num_leaves": 15}
    b_s = _train(dict(mc, tree_learner="serial"), X, y3, rounds=4)
    b_d = _train(dict(mc, tree_learner="data"), X, y3, rounds=4)
    from lightgbm_tpu.treelearner.parallel import FusedDataParallelGrower
    assert isinstance(b_d._gbdt._fused, FusedDataParallelGrower)
    p1, p2 = b_s.predict(X), b_d.predict(X)
    assert float(np.mean(np.abs(p1 - p2))) < 1e-4
    acc = (np.argmax(p2, 1) == y3).mean()
    assert acc > 0.95


def _make_bundled(n=4000, seed=2):
    """Mutually-exclusive sparse columns that EFB actually bundles."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, 9), dtype=np.float32)
    X[:, 0] = rng.randn(n)
    X[:, 1] = rng.randn(n)
    # one-hot-ish trio: exactly one of columns 2..4 nonzero per row
    grp = rng.randint(0, 3, n)
    for g in range(3):
        rows = grp == g
        X[rows, 2 + g] = rng.rand(rows.sum()) + 0.5
    # two more mutually-exclusive pairs
    m = rng.rand(n) < 0.5
    X[m, 5] = rng.rand(m.sum()) + 0.5
    X[~m, 6] = rng.rand((~m).sum()) + 0.5
    X[:100, 7] = 1.0
    X[2000:, 8] = rng.rand(n - 2000)
    y = (X[:, 0] + X[:, 2] - X[:, 3] + 0.5 * X[:, 5]
         + 0.2 * rng.randn(n) > 0.3).astype(np.float32)
    return X, y


@pytest.mark.slow
def test_parallel_learners_keep_efb_bundles():
    """Round-4: parallel learners consume EFB bundles directly (no more
    debundling — the reference's flagship distributed result depends on
    bundling, Experiments.rst Criteo). Bundled datasets must train
    through data/voting learners and match serial quality."""
    X, y = _make_bundled()
    base = {"objective": "binary", "num_leaves": 15, "verbose": -1,
            "min_data_in_leaf": 20}
    b_serial = _train(dict(base, tree_learner="serial"), X, y, rounds=6)
    # the serial run must actually have bundles (else the test is vacuous)
    assert not b_serial._gbdt.train_data.efb_trivial, \
        "fixture no longer bundles; adjust _make_bundled"
    for learner in ("data", "voting"):
        b_p = _train(dict(base, tree_learner=learner, num_machines=8,
                          tpu_fused=False), X, y, rounds=6)
        assert not b_p._gbdt.train_data.efb_trivial, \
            f"{learner} learner debundled the dataset"
        p1, p2 = b_serial.predict(X), b_p.predict(X)
        assert np.corrcoef(p1, p2)[0, 1] > 0.999, learner
    # and the fused sharded path with bundles intact
    b_f = _train(dict(base, tree_learner="data"), X, y, rounds=6)
    from lightgbm_tpu.treelearner.parallel import FusedDataParallelGrower
    assert isinstance(b_f._gbdt._fused, FusedDataParallelGrower)
    assert not b_f._gbdt.train_data.efb_trivial
    p3 = b_f.predict(X)
    assert float(np.mean(np.abs(b_serial.predict(X) - p3))) < 1e-3


def test_fused_dp_scores_sync():
    """get_training_score gathers the sharded permuted scores back to
    row order correctly (checked against fresh predictions)."""
    X, y = _make(n=4096)
    b = _train({"objective": "binary", "num_leaves": 15, "verbose": -1,
                "tree_learner": "data"}, X, y, rounds=4)
    raw = np.asarray(b._gbdt.get_training_score())[0]
    pred_raw = b.predict(X, raw_score=True)
    np.testing.assert_allclose(raw, pred_raw, rtol=1e-3, atol=1e-4)
