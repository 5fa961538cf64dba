"""End-to-end behavioral tests.

Modeled on the reference test strategy (reference:
tests/python_package_test/test_engine.py — objective coverage, the
missing-value handling matrix at :121-267, categorical :268-378, early
stopping :560, continued training :592, cv :679, SHAP :974) — the
backend-agnostic behavioral definition of "LightGBM-equivalent".
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def make_binary(n=2000, f=8, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    logit = 1.5 * X[:, 0] - 2.0 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (logit + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def make_regression(n=2000, f=8, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = 3 * X[:, 0] + np.sin(X[:, 1] * 2) + 0.5 * X[:, 2] * X[:, 3] \
        + 0.1 * rng.randn(n)
    return X, y


def auc_score(y, p):
    order = np.argsort(-p, kind="stable")
    yy = y[order] > 0
    pos = yy.sum()
    neg = len(yy) - pos
    ranks = np.arange(1, len(yy) + 1)
    return 1.0 - (np.sum(ranks[yy]) - pos * (pos + 1) / 2) / (pos * neg)


P = {"verbose": -1, "min_data_in_leaf": 20}


class TestObjectives:
    def test_binary(self):
        X, y = make_binary()
        ds = lgb.Dataset(X, label=y)
        bst = lgb.train(dict(P, objective="binary", metric="binary_logloss"),
                        ds, num_boost_round=30, verbose_eval=False)
        p = bst.predict(X)
        assert ((p > 0.5) == y).mean() > 0.93
        assert p.min() >= 0 and p.max() <= 1

    def test_regression_l2(self):
        X, y = make_regression()
        bst = lgb.train(dict(P, objective="regression"), lgb.Dataset(X, label=y),
                        num_boost_round=50, verbose_eval=False)
        p = bst.predict(X)
        assert np.mean((p - y) ** 2) < 0.4

    @pytest.mark.slow
    def test_regression_l1(self):
        """Slow-marked: l1 stays tier-1-covered via test_regression (l2
        gradient path) and the fused renew l1 param in test_renew_fused."""
        X, y = make_regression()
        bst = lgb.train(dict(P, objective="regression_l1"),
                        lgb.Dataset(X, label=y), num_boost_round=50,
                        verbose_eval=False)
        assert np.mean(np.abs(bst.predict(X) - y)) < 0.6

    @pytest.mark.slow
    def test_huber_fair_quantile(self):
        """Slow-marked: pure objective numerics; the quantile/renew
        fused param in test_renew_fused keeps quantile tier-1."""
        X, y = make_regression(1200)
        for obj in ("huber", "fair"):
            bst = lgb.train(dict(P, objective=obj), lgb.Dataset(X, label=y),
                            num_boost_round=30, verbose_eval=False)
            assert np.mean(np.abs(bst.predict(X) - y)) < 1.0, obj
        # quantile: alpha=0.9 predictions sit above the median
        bq = lgb.train(dict(P, objective="quantile", alpha=0.9),
                       lgb.Dataset(X, label=y), num_boost_round=40,
                       verbose_eval=False)
        assert (bq.predict(X) > y).mean() > 0.7

    @pytest.mark.slow
    def test_poisson_gamma_tweedie(self):
        """Slow-marked: pure log-link objective numerics with no kernel
        or layout coupling; the shared gradient path is tier-1-covered
        by the l2/binary/multiclass objectives."""
        rng = np.random.RandomState(5)
        X = rng.randn(1500, 6)
        lam = np.exp(0.5 * X[:, 0] + 0.3 * X[:, 1])
        for obj, ylab in [("poisson", rng.poisson(lam).astype(float)),
                          ("gamma", lam * (0.5 + rng.rand(1500))),
                          ("tweedie", lam * (rng.rand(1500) > 0.3))]:
            bst = lgb.train(dict(P, objective=obj), lgb.Dataset(X, label=ylab),
                            num_boost_round=30, verbose_eval=False)
            p = bst.predict(X)
            assert np.all(p >= 0), obj  # log-link: positive predictions
            assert np.corrcoef(p, lam)[0, 1] > 0.5, obj

    def test_mape(self):
        X, y = make_regression()
        y = np.abs(y) + 2.0
        bst = lgb.train(dict(P, objective="mape"), lgb.Dataset(X, label=y),
                        num_boost_round=40, verbose_eval=False)
        assert np.mean(np.abs(bst.predict(X) - y) / y) < 0.35

    def test_multiclass(self):
        rng = np.random.RandomState(9)
        X = rng.randn(1800, 6)
        y = (X[:, 0] > 0.4).astype(int) + (X[:, 1] > 0.1).astype(int)
        params = dict(P, objective="multiclass", num_class=3,
                      metric="multi_logloss")
        bst = lgb.train(params, lgb.Dataset(X, label=y.astype(float)),
                        num_boost_round=30, verbose_eval=False)
        p = bst.predict(X)
        assert p.shape == (1800, 3)
        np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-5)
        assert (np.argmax(p, 1) == y).mean() > 0.9

    @pytest.mark.slow
    def test_multiclassova(self):
        """Slow-marked: softmax multiclass (test_multiclass) keeps the
        num_class output layout tier-1; ova only swaps the link."""
        rng = np.random.RandomState(9)
        X = rng.randn(1500, 6)
        y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)
        bst = lgb.train(dict(P, objective="multiclassova", num_class=3),
                        lgb.Dataset(X, label=y.astype(float)),
                        num_boost_round=25, verbose_eval=False)
        p = bst.predict(X)
        assert (np.argmax(p, 1) == y).mean() > 0.85

    @pytest.mark.slow
    def test_cross_entropy(self):
        """Slow-marked: the sigmoid-link gradient path stays tier-1 via
        test_binary; cross_entropy only relaxes labels to probabilities
        on the same link."""
        X, y = make_binary()
        yp = 0.8 * y + 0.1  # probability labels
        bst = lgb.train(dict(P, objective="cross_entropy"),
                        lgb.Dataset(X, label=yp), num_boost_round=30,
                        verbose_eval=False)
        p = bst.predict(X)
        assert auc_score(y, p) > 0.95

    def test_custom_objective_fobj(self):
        X, y = make_regression()
        ds = lgb.Dataset(X, label=y)

        def l2_fobj(preds, dataset):
            return preds - dataset.get_label(), np.ones_like(preds)

        bst = lgb.train(dict(P, objective="none", metric="l2"), ds,
                        num_boost_round=40, fobj=l2_fobj, verbose_eval=False)
        # custom objective has no boost_from_average; compare trends
        assert np.mean((bst.predict(X) - y) ** 2) < np.var(y) * 0.2

    def test_lambdarank(self):
        rng = np.random.RandomState(13)
        n_q, per_q = 60, 20
        n = n_q * per_q
        X = rng.randn(n, 6)
        rel = np.clip((X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(n)) * 1.2 + 1.5,
                      0, 4).astype(int)
        group = np.full(n_q, per_q)
        params = dict(P, objective="lambdarank", metric="ndcg",
                      eval_at=[5], min_data_in_leaf=5)
        ds = lgb.Dataset(X, label=rel.astype(float), group=group)
        bst = lgb.train(params, ds, num_boost_round=30, verbose_eval=False)
        p = bst.predict(X)
        from lightgbm_tpu.objective.rank import DCGCalculator
        dcg = DCGCalculator()
        ndcgs = []
        for q in range(n_q):
            s = slice(q * per_q, (q + 1) * per_q)
            m = dcg.cal_max_dcg_at_k(5, rel[s])
            if m > 0:
                ndcgs.append(dcg.cal_dcg_at_k(5, rel[s], p[s]) / m)
        assert np.mean(ndcgs) > 0.80

    def test_rank_xendcg(self):
        rng = np.random.RandomState(13)
        n_q, per_q = 50, 16
        n = n_q * per_q
        X = rng.randn(n, 5)
        rel = np.clip((X[:, 0] + 0.4 * rng.randn(n)) + 1.5, 0, 3).astype(int)
        params = dict(P, objective="rank_xendcg", metric="ndcg",
                      min_data_in_leaf=5)
        ds = lgb.Dataset(X, label=rel.astype(float), group=np.full(n_q, per_q))
        bst = lgb.train(params, ds, num_boost_round=25, verbose_eval=False)
        p = bst.predict(X)
        corr = np.corrcoef(p, rel)[0, 1]
        assert corr > 0.4


class TestMissingValues:
    """Reference missing-value matrix (test_engine.py:121-267)."""

    def _data_with_nan(self, seed=3):
        rng = np.random.RandomState(seed)
        X = rng.randn(1500, 4)
        nan_mask = rng.rand(1500) < 0.3
        y = np.where(nan_mask, 1.0, (X[:, 1] > 0).astype(float))
        X[nan_mask, 1] = np.nan
        return X, y, nan_mask

    def test_nan_routed_consistently(self):
        X, y, nan_mask = self._data_with_nan()
        bst = lgb.train(dict(P, objective="binary", min_data_in_leaf=1),
                        lgb.Dataset(X, label=y), num_boost_round=30,
                        verbose_eval=False)
        p = bst.predict(X)
        assert ((p > 0.5) == y).mean() > 0.95

    def test_zero_as_missing(self):
        rng = np.random.RandomState(4)
        X = rng.randn(1200, 3)
        zero_mask = rng.rand(1200) < 0.4
        X[zero_mask, 0] = 0.0
        y = np.where(zero_mask, 1.0, (X[:, 0] > 0).astype(float))
        bst = lgb.train(dict(P, objective="binary", zero_as_missing=True,
                             min_data_in_leaf=1),
                        lgb.Dataset(X, label=y), num_boost_round=30,
                        verbose_eval=False)
        assert ((bst.predict(X) > 0.5) == y).mean() > 0.95

    def test_use_missing_false(self):
        X, y, _ = self._data_with_nan()
        bst = lgb.train(dict(P, objective="binary", use_missing=False),
                        lgb.Dataset(X, label=y), num_boost_round=15,
                        verbose_eval=False)
        # NaN treated as zero: model still trains and predicts finitely
        assert np.isfinite(bst.predict(X)).all()


class TestCategorical:
    def test_categorical_feature(self):
        rng = np.random.RandomState(21)
        n = 2000
        cat = rng.randint(0, 12, n)
        X = np.column_stack([cat.astype(float), rng.randn(n)])
        # target depends on membership of a category subset
        y = np.isin(cat, [2, 5, 7]).astype(float)
        ds = lgb.Dataset(X, label=y, categorical_feature=[0],
                         params={"min_data_in_leaf": 1, "min_data_per_group": 1,
                                 "cat_smooth": 1.0, "verbose": -1})
        bst = lgb.train({"objective": "binary", "verbose": -1,
                         "min_data_in_leaf": 1, "min_data_per_group": 1,
                         "cat_smooth": 1.0},
                        ds, num_boost_round=30, verbose_eval=False)
        p = bst.predict(X)
        assert ((p > 0.5) == y).mean() > 0.97

    @pytest.mark.slow
    def test_categorical_onehot(self):
        """Slow-marked: the categorical split rule stays tier-1 via
        test_categorical_feature; this variant only drops cardinality
        under max_cat_to_onehot to take the one-vs-rest branch."""
        rng = np.random.RandomState(22)
        n = 1000
        cat = rng.randint(0, 3, n)  # <= max_cat_to_onehot
        X = np.column_stack([cat.astype(float), rng.randn(n)])
        y = (cat == 1).astype(float)
        ds = lgb.Dataset(X, label=y, categorical_feature=[0],
                         params={"verbose": -1, "min_data_in_leaf": 1})
        bst = lgb.train({"objective": "binary", "verbose": -1,
                         "min_data_in_leaf": 1}, ds, num_boost_round=20,
                        verbose_eval=False)
        assert ((bst.predict(X) > 0.5) == y).mean() > 0.97


class TestTrainingControl:
    @pytest.mark.slow
    def test_early_stopping(self):
        """Slow-marked: early stopping stays tier-1 via
        test_pipeline::test_early_stop_parity (same callback picking
        the same best_iteration, pipelined and synchronous)."""
        X, y = make_binary(3000)
        ds = lgb.Dataset(X[:2000], label=y[:2000])
        vs = ds.create_valid(X[2000:], label=y[2000:])
        evals = {}
        bst = lgb.train(dict(P, objective="binary", metric="binary_logloss"),
                        ds, num_boost_round=200, valid_sets=[vs],
                        early_stopping_rounds=5, evals_result=evals,
                        verbose_eval=False)
        assert bst.best_iteration > 0
        assert len(evals["valid_0"]["binary_logloss"]) <= 200

    def test_continued_training(self):
        X, y = make_binary()
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        b1 = lgb.train(dict(P, objective="binary"), ds, num_boost_round=10,
                       verbose_eval=False)
        ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
        b2 = lgb.train(dict(P, objective="binary"), ds2, num_boost_round=10,
                       init_model=b1, verbose_eval=False)
        assert b2.num_trees() == 20
        ll1 = -np.mean(y * np.log(np.clip(b1.predict(X), 1e-9, 1))
                       + (1 - y) * np.log(np.clip(1 - b1.predict(X), 1e-9, 1)))
        ll2 = -np.mean(y * np.log(np.clip(b2.predict(X), 1e-9, 1))
                       + (1 - y) * np.log(np.clip(1 - b2.predict(X), 1e-9, 1)))
        assert ll2 < ll1

    def test_bagging(self):
        X, y = make_binary()
        bst = lgb.train(dict(P, objective="binary", bagging_fraction=0.5,
                             bagging_freq=1), lgb.Dataset(X, label=y),
                        num_boost_round=20, verbose_eval=False)
        assert auc_score(y, bst.predict(X)) > 0.95

    def test_feature_fraction(self):
        X, y = make_binary()
        bst = lgb.train(dict(P, objective="binary", feature_fraction=0.5),
                        lgb.Dataset(X, label=y), num_boost_round=20,
                        verbose_eval=False)
        assert auc_score(y, bst.predict(X)) > 0.93

    def test_goss(self):
        X, y = make_binary(4000)
        bst = lgb.train(dict(P, objective="binary", boosting="goss",
                             learning_rate=0.3),
                        lgb.Dataset(X, label=y), num_boost_round=25,
                        verbose_eval=False)
        assert auc_score(y, bst.predict(X)) > 0.95

    def test_goss_sampling_stays_on_device(self):
        """The GOSS round (top-k by |g*h|, rest sampling, perm build)
        must dispatch without pulling [N] arrays to host — asserted by
        a device-to-host transfer guard around the sampled-iteration
        _bagging call (reference goss.hpp computes on its own arrays;
        the TPU analogue must not sync the device per iteration)."""
        import jax
        X, y = make_binary(4000)
        bst = lgb.train(dict(P, objective="binary", boosting="goss",
                             learning_rate=0.5),
                        lgb.Dataset(X, label=y), num_boost_round=3,
                        verbose_eval=False, keep_training_booster=True)
        g = bst._gbdt
        assert g.iter >= int(1.0 / 0.5), "need a sampled iteration"
        with jax.transfer_guard_device_to_host("disallow"):
            g._bagging(g.iter)
        assert g.bag_data_cnt < g.num_data
        # the permutation is a valid [bag | oob] row permutation
        perm = np.asarray(g._perm)
        assert np.array_equal(np.sort(perm), np.arange(g.num_data))
        bag = perm[:g.bag_data_cnt]
        assert np.array_equal(bag, np.sort(bag))  # stable ascending bag

    @pytest.mark.slow
    def test_dart(self):
        """Slow-marked: the DART drop/normalize path stays tier-1 via
        test_pipeline::test_dart_parity; this re-proves training
        quality on top of the same boosting mode."""
        X, y = make_binary()
        bst = lgb.train(dict(P, objective="binary", boosting="dart",
                             drop_rate=0.3), lgb.Dataset(X, label=y),
                        num_boost_round=25, verbose_eval=False)
        assert auc_score(y, bst.predict(X)) > 0.93

    def test_rf(self):
        X, y = make_binary()
        bst = lgb.train(dict(P, objective="binary", boosting="rf",
                             bagging_fraction=0.7, bagging_freq=1,
                             feature_fraction=0.7),
                        lgb.Dataset(X, label=y), num_boost_round=20,
                        verbose_eval=False)
        p = bst.predict(X)
        assert auc_score(y, p) > 0.9
        assert p.min() >= 0 and p.max() <= 1

    def test_max_depth(self):
        X, y = make_binary()
        bst = lgb.train(dict(P, objective="binary", max_depth=2,
                             num_leaves=63), lgb.Dataset(X, label=y),
                        num_boost_round=5, verbose_eval=False)
        for t in bst._gbdt.models:
            assert t.leaf_depth[:t.num_leaves].max() <= 2

    def test_min_gain_to_split(self):
        X, y = make_binary()
        b_lo = lgb.train(dict(P, objective="binary"), lgb.Dataset(X, label=y),
                         num_boost_round=5, verbose_eval=False)
        b_hi = lgb.train(dict(P, objective="binary", min_gain_to_split=1000.0),
                         lgb.Dataset(X, label=y), num_boost_round=5,
                         verbose_eval=False)
        n_lo = sum(t.num_leaves for t in b_lo._gbdt.models)
        n_hi = sum(t.num_leaves for t in b_hi._gbdt.models)
        assert n_hi < n_lo

    def test_weights(self):
        X, y = make_binary()
        w = np.where(y > 0, 10.0, 1.0)
        bst = lgb.train(dict(P, objective="binary"),
                        lgb.Dataset(X, label=y, weight=w),
                        num_boost_round=15, verbose_eval=False)
        # heavily weighting positives shifts predictions upward
        b0 = lgb.train(dict(P, objective="binary"), lgb.Dataset(X, label=y),
                       num_boost_round=15, verbose_eval=False)
        assert bst.predict(X).mean() > b0.predict(X).mean()

    def test_monotone_constraints(self):
        rng = np.random.RandomState(31)
        X = rng.rand(1500, 2)
        y = 2 * X[:, 0] + rng.randn(1500) * 0.01
        bst = lgb.train(dict(P, objective="regression",
                             monotone_constraints=[1, 0]),
                        lgb.Dataset(X, label=y), num_boost_round=20,
                        verbose_eval=False)
        grid = np.column_stack([np.linspace(0, 1, 50), np.full(50, 0.5)])
        p = bst.predict(grid)
        assert np.all(np.diff(p) >= -1e-10)


class TestPredictionPaths:
    def test_pred_leaf_and_contrib(self):
        X, y = make_binary(500)
        bst = lgb.train(dict(P, objective="binary"), lgb.Dataset(X, label=y),
                        num_boost_round=8, verbose_eval=False)
        leaves = bst.predict(X[:20], pred_leaf=True)
        assert leaves.shape == (20, 8)
        contrib = bst.predict(X[:20], pred_contrib=True)
        raw = bst.predict(X[:20], raw_score=True)
        np.testing.assert_allclose(contrib.sum(axis=1), raw, rtol=1e-4,
                                   atol=1e-5)

    def test_start_num_iteration(self):
        X, y = make_binary(500)
        bst = lgb.train(dict(P, objective="binary"), lgb.Dataset(X, label=y),
                        num_boost_round=10, verbose_eval=False)
        p_all = bst.predict(X[:50], raw_score=True)
        p_first5 = bst.predict(X[:50], raw_score=True, num_iteration=5)
        p_last5 = bst.predict(X[:50], raw_score=True, start_iteration=5,
                              num_iteration=5)
        np.testing.assert_allclose(p_first5 + p_last5, p_all, rtol=1e-4,
                                   atol=1e-5)

    def test_model_roundtrip_file(self, tmp_path):
        X, y = make_binary(500)
        bst = lgb.train(dict(P, objective="binary"), lgb.Dataset(X, label=y),
                        num_boost_round=8, verbose_eval=False)
        path = str(tmp_path / "model.txt")
        bst.save_model(path)
        b2 = lgb.Booster(model_file=path)
        np.testing.assert_allclose(b2.predict(X), bst.predict(X), rtol=1e-6)

    def test_dump_model_json(self):
        X, y = make_binary(500)
        bst = lgb.train(dict(P, objective="binary"), lgb.Dataset(X, label=y),
                        num_boost_round=3, verbose_eval=False)
        d = bst.dump_model()
        assert d["num_tree_per_iteration"] == 1
        assert len(d["tree_info"]) == 3
        assert "tree_structure" in d["tree_info"][0]

    def test_feature_importance(self):
        X, y = make_binary()
        bst = lgb.train(dict(P, objective="binary"), lgb.Dataset(X, label=y),
                        num_boost_round=10, verbose_eval=False)
        imp_split = bst.feature_importance("split")
        imp_gain = bst.feature_importance("gain")
        assert imp_split.sum() > 0
        # features 0 and 1 dominate the signal
        assert imp_gain[0] + imp_gain[1] > imp_gain[4:].sum()


class TestCV:
    def test_cv_basic(self):
        X, y = make_binary()
        res = lgb.cv(dict(P, objective="binary", metric="binary_logloss"),
                     lgb.Dataset(X, label=y), num_boost_round=10, nfold=3)
        assert len(res["binary_logloss-mean"]) == 10
        assert res["binary_logloss-mean"][-1] < res["binary_logloss-mean"][0]

    @pytest.mark.slow
    def test_cv_early_stopping(self):
        """Slow-marked: early stopping (TestTrainingControl) and CV
        aggregation (test_cv_basic) are each tier-1-covered; this
        re-proves their composition over 100 candidate rounds (27s)."""
        X, y = make_binary()
        res = lgb.cv(dict(P, objective="binary", metric="binary_logloss"),
                     lgb.Dataset(X, label=y), num_boost_round=100, nfold=3,
                     early_stopping_rounds=3)
        assert len(res["binary_logloss-mean"]) < 100

    @pytest.mark.slow
    def test_cv_return_booster(self):
        """Slow-marked: fold construction and metric aggregation are
        tier-1-covered by test_cv_basic; this only checks the
        return_cvbooster plumbing on top of the same folds."""
        X, y = make_binary(800)
        res = lgb.cv(dict(P, objective="binary"), lgb.Dataset(X, label=y),
                     num_boost_round=5, nfold=3, return_cvbooster=True)
        assert len(res["cvbooster"].boosters) == 3


class TestSklearn:
    def test_classifier(self):
        X, y = make_binary()
        from lightgbm_tpu.sklearn import LGBMClassifier
        clf = LGBMClassifier(n_estimators=20, num_leaves=15)
        clf.fit(X, y.astype(int))
        assert (clf.predict(X) == y).mean() > 0.93
        proba = clf.predict_proba(X)
        assert proba.shape == (len(y), 2)
        np.testing.assert_allclose(proba.sum(1), 1.0, rtol=1e-6)
        assert clf.feature_importances_.sum() > 0

    # the sklearn surface is covered by test_classifier/test_regressor
    # and multiclass by TestObjectives; the combination is full-run only
    @pytest.mark.slow
    def test_classifier_multiclass(self):
        rng = np.random.RandomState(2)
        X = rng.randn(1200, 5)
        y = np.array(["a", "b", "c"])[
            (X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)]
        from lightgbm_tpu.sklearn import LGBMClassifier
        clf = LGBMClassifier(n_estimators=15).fit(X, y)
        assert set(clf.classes_) == {"a", "b", "c"}
        assert (clf.predict(X) == y).mean() > 0.85

    def test_regressor(self):
        X, y = make_regression()
        from lightgbm_tpu.sklearn import LGBMRegressor
        reg = LGBMRegressor(n_estimators=30).fit(X, y)
        assert np.mean((reg.predict(X) - y) ** 2) < 0.5

    @pytest.mark.slow
    def test_regressor_early_stopping(self):
        """Slow-marked: early stopping is tier-1-covered in
        TestTrainingControl::test_early_stopping and
        test_robust.py::test_early_stopping_resume; this re-proves the
        sklearn-wrapper plumbing over 100 candidate rounds (21s)."""
        X, y = make_regression(2400)
        from lightgbm_tpu.sklearn import LGBMRegressor
        reg = LGBMRegressor(n_estimators=100)
        reg.fit(X[:1600], y[:1600], eval_set=[(X[1600:], y[1600:])],
                eval_metric="l2", early_stopping_rounds=5)
        assert reg.best_iteration_ is not None

    def test_ranker(self):
        rng = np.random.RandomState(17)
        n_q, per_q = 40, 15
        n = n_q * per_q
        X = rng.randn(n, 4)
        rel = np.clip((X[:, 0] + 0.5 * rng.randn(n)) + 1, 0, 3).astype(int)
        from lightgbm_tpu.sklearn import LGBMRanker
        rk = LGBMRanker(n_estimators=15, min_child_samples=5)
        rk.fit(X, rel, group=np.full(n_q, per_q))
        assert np.corrcoef(rk.predict(X), rel)[0, 1] > 0.4


class TestDatasetOps:
    def test_subset(self):
        X, y = make_binary(1000)
        ds = lgb.Dataset(X, label=y, free_raw_data=False).construct()
        sub = ds.subset(np.arange(100, 400))
        sub.construct()
        assert sub.num_data() == 300
        np.testing.assert_array_equal(sub._handle.bins,
                                      ds._handle.bins[100:400])

    def test_save_load_binary(self, tmp_path):
        X, y = make_binary(500)
        ds = lgb.Dataset(X, label=y).construct()
        path = str(tmp_path / "data.bin")
        ds.save_binary(path)
        ds2 = lgb.Dataset(path).construct()
        assert ds2.num_data() == 500
        np.testing.assert_array_equal(ds2._handle.bins, ds._handle.bins)

    def test_add_features_from(self):
        X, y = make_binary(600)
        d1 = lgb.Dataset(X[:, :4], label=y, free_raw_data=False).construct()
        d2 = lgb.Dataset(X[:, 4:], free_raw_data=False).construct()
        n_before = d1._handle.num_features
        d1.add_features_from(d2)
        assert d1._handle.num_features == n_before + d2._handle.num_features

    def test_reset_parameter_callback(self):
        X, y = make_binary(800)
        lrs = [0.2] * 5 + [0.05] * 5
        bst = lgb.train(dict(P, objective="binary"), lgb.Dataset(X, label=y),
                        num_boost_round=10, learning_rates=lrs,
                        verbose_eval=False)
        assert bst.num_trees() == 10
