"""The plain reference against the package's own oracle (device_type=cpu:
scatter histograms, argsort partition) on a small seeded problem."""
import numpy as np
import pytest

from benchmarks.reference import gbdt_numpy as ref

PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 20, "verbose": -1,
          "device_type": "cpu"}


@pytest.fixture(scope="module")
def problem():
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(7)
    X = rng.standard_normal((2000, 6)).astype(np.float32)
    s = X[:, 0] - 0.8 * X[:, 1] * X[:, 2] + 0.5 * np.sin(2 * X[:, 3])
    y = (rng.random(2000) < ref.sigmoid(2.0 * s)).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params=dict(PARAMS)).construct()
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=5, verbose_eval=False)
    return X, y, ds, bst


def test_tree0_identical_to_the_oracle(problem):
    X, y, ds, bst = problem
    theirs = ref.parse_model(bst.model_to_string())[0]
    handle = ds._handle
    init = ref.binary_init_score(y)
    g, h = ref.binary_grad_hess(y, np.full(len(y), init))
    ours = ref.grow_tree(
        handle.bins, [m.num_bin for m in handle.bin_mappers],
        [m.bin_upper_bound for m in handle.bin_mappers], g, h,
        num_leaves=PARAMS["num_leaves"],
        min_data_in_leaf=PARAMS["min_data_in_leaf"], min_sum_hessian=1e-3,
        shrinkage=PARAMS["learning_rate"], bias=init)
    assert ours.num_leaves == theirs.num_leaves == PARAMS["num_leaves"]
    for name in ("split_feature", "left_child", "right_child", "leaf_count",
                 "internal_count"):
        assert np.array_equal(getattr(ours, name), getattr(theirs, name)), name
    np.testing.assert_allclose(ours.threshold, theirs.threshold, rtol=1e-12)
    # the oracle sums gradients in float32
    np.testing.assert_allclose(ours.leaf_value, theirs.leaf_value, atol=5e-6)
    np.testing.assert_allclose(ours.split_gain, theirs.split_gain, rtol=1e-4)


def test_walker_predicts_like_the_package(problem):
    X, y, ds, bst = problem
    trees = ref.parse_model(bst.model_to_string())
    assert len(trees) == 5
    raw = ref.predict_raw(trees, X)
    np.testing.assert_allclose(raw, bst.predict(X, raw_score=True), atol=1e-6)
    (_, _, loss, _), = bst.eval_train()
    assert abs(ref.binary_logloss(y, raw) - loss) < 1e-6


def test_auc_and_logloss_on_hand_worked_cases():
    y = np.array([0, 0, 1, 1], np.float32)
    assert ref.auc(y, np.array([0.1, 0.4, 0.35, 0.8])) == 0.75
    assert ref.auc(y, np.array([0.1, 0.2, 0.3, 0.4])) == 1.0
    assert ref.binary_logloss(y, np.zeros(4)) == pytest.approx(np.log(2))


def test_best_threshold_prefers_the_higher_of_equal_gains():
    # bins 1 and 2 are empty: thresholds 0, 1 and 2 part the rows alike
    hist = np.zeros((5, 3))
    hist[0] = (-30.0, 10.0, 40)
    hist[3] = (20.0, 10.0, 40)
    hist[4] = (15.0, 10.0, 40)
    gain, t = ref.best_threshold(hist, min_data_in_leaf=20,
                                 min_sum_hessian=1e-3, lambda_l2=0.0)
    assert t == 2
    assert gain == pytest.approx(30**2 / 10 + 35**2 / 20 - 5**2 / 30)
