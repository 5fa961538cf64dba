"""Round-4 fused-path coverage: forced splits and per-node feature
sampling run INSIDE the single-dispatch grower (they used to silently
drop to the ~10x-slower host-loop grower), and every remaining
rejection is named by fused_reject_reason."""
import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.treelearner.fused import (FusedSerialGrower,
                                            fused_reject_reason,
                                            fused_supported)
from lightgbm_tpu.objective.functions import create_objective

P = {"verbose": -1, "min_data_in_leaf": 20}


def make_binary(n=2500, f=6, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (1.5 * X[:, 0] - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
         + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _reason(params, X, y):
    merged = dict(P, objective="binary")
    merged.update(params)
    cfg = Config.from_params(merged)
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    return fused_reject_reason(cfg, ds, create_objective(cfg))


def test_forced_splits_run_fused_and_match_host_loop(tmp_path):
    """Forced splits (reference ForceSplits,
    serial_tree_learner.cpp:427) execute as a BFS phase inside the
    fused while_loop program and match the host-loop grower's models."""
    X, y = make_binary()
    fs = {"feature": 3, "threshold": 0.0,
          "left": {"feature": 4, "threshold": 0.5},
          "right": {"feature": 0, "threshold": -0.25}}
    path = str(tmp_path / "forced.json")
    with open(path, "w") as fh:
        json.dump(fs, fh)
    base = dict(P, objective="binary", forcedsplits_filename=path,
                num_leaves=15)
    b_fused = lgb.train(dict(base), lgb.Dataset(X, label=y),
                        num_boost_round=4, verbose_eval=False)
    assert isinstance(b_fused._gbdt._fused, FusedSerialGrower)
    assert b_fused._gbdt._fused._forced_sched is not None
    b_host = lgb.train(dict(base, tpu_fused=False), lgb.Dataset(X, label=y),
                       num_boost_round=4, verbose_eval=False)
    assert b_host._gbdt._fused is None
    for tf, th in zip(b_fused._gbdt.models, b_host._gbdt.models):
        # same forced structure: root on 3, BFS children on 4 then 0
        assert int(tf.split_feature[0]) == int(th.split_feature[0]) == 3
        assert int(tf.split_feature[1]) == int(th.split_feature[1]) == 4
        assert int(tf.split_feature[2]) == int(th.split_feature[2]) == 0
    pf, ph = b_fused.predict(X), b_host.predict(X)
    assert np.corrcoef(pf, ph)[0, 1] > 0.999


def test_feature_fraction_bynode_runs_fused():
    """feature_fraction_bynode draws a fresh candidate subset per scan
    event inside the fused program (col_sampler.hpp GetByNode)."""
    X, y = make_binary()
    base = dict(P, objective="binary", feature_fraction_bynode=0.5,
                num_leaves=31)
    b = lgb.train(dict(base), lgb.Dataset(X, label=y), num_boost_round=8,
                  verbose_eval=False)
    assert isinstance(b._gbdt._fused, FusedSerialGrower)
    # sampling actually bites: with only half the features visible per
    # node, trees must use a feature other than the dominant 0 somewhere
    # in places a full-view tree would not; quality stays reasonable
    p = b.predict(X)
    order = np.argsort(-p)
    yy = y[order] > 0
    pos, neg = yy.sum(), len(yy) - yy.sum()
    auc = 1.0 - (np.sum(np.arange(1, len(yy) + 1)[yy])
                 - pos * (pos + 1) / 2) / (pos * neg)
    assert auc > 0.9
    imp = b.feature_importance("split")
    assert (imp > 0).sum() >= 3  # per-node sampling spreads the splits


def test_fused_reject_reasons_are_named():
    X, y = make_binary()
    assert _reason({}, X, y) is None
    assert _reason({"feature_fraction_bynode": 0.5}, X, y) is None
    assert "interaction_constraints" in _reason(
        {"interaction_constraints": "[0,1],[2,3]"}, X, y)
    assert "extra_trees" in _reason({"extra_trees": True}, X, y)
    assert "cegb" in _reason({"cegb_penalty_split": 1.0}, X, y)
    assert "tpu_fused" in _reason({"tpu_fused": False}, X, y)
    # round-5: renew objectives run fused via the in-program leaf refit
    # — only sampling configs (which break the persistent path) reject
    assert _reason({"objective": "regression_l1"}, X, y) is None
    r = _reason({"objective": "regression_l1", "bagging_freq": 1,
                 "bagging_fraction": 0.8}, X, y)
    assert r is not None and "renew" in r
    cfg = Config.from_params(dict(P, objective="binary"))
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    assert fused_supported(cfg, ds, create_objective(cfg))


def test_pending_tree_has_two_states():
    """A PendingTree is device arrays until a host consumer asks, then a
    materialised Tree: shrinkage and bias applied before and after
    materialisation give the same leaves, any Tree attribute
    materialises once, and an instance __init__ has not run on (copy,
    unpickling) raises AttributeError instead of recursing."""
    import copy

    import jax
    from lightgbm_tpu.treelearner.fused import PendingTree
    X, y = make_binary()
    bst = lgb.Booster(dict(P, objective="binary", num_leaves=7),
                      lgb.Dataset(X, label=y))
    bst.update()
    t = bst._gbdt.models[0]
    assert isinstance(t, PendingTree) and t._tree is None
    assert isinstance(t.tree_arrays["leaf_value"], jax.Array)
    before = np.asarray(t.leaf_values_device())
    n = int(t.num_leaves)                  # a Tree attribute: materialises
    assert t._tree is not None and n == int(t.tree_arrays["n_leaves"])
    np.testing.assert_allclose(t.leaf_value[:n], before[:n], rtol=1e-6)
    late = PendingTree(t.grower, t.tree_arrays)
    early = PendingTree(t.grower, t.tree_arrays)
    early.materialize()
    for tree in (late, early):
        tree.apply_shrinkage(0.5)
        tree.add_bias(0.25)
    assert late._tree is None              # still pending: folded lazily
    np.testing.assert_allclose(np.asarray(late.leaf_values_device())[:n],
                               early.leaf_value[:n], rtol=1e-6)
    np.testing.assert_allclose(late.materialize().leaf_value[:n],
                               early.leaf_value[:n], rtol=1e-6)
    blank = PendingTree.__new__(PendingTree)
    for name in ("_tree", "tree_arrays", "grower", "pending_shrinkage"):
        with pytest.raises(AttributeError):
            getattr(blank, name)
    assert copy.copy(t)._tree is t._tree
