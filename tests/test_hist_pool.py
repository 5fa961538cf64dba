"""histogram_pool_size cap: pool-less / recompute modes must train the
same model as the unlimited pool (reference HistogramPool LRU,
feature_histogram.hpp:1061 — here the cap switches off subtraction and
caching instead of evicting)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import quantize as Q


def make_data(n=1500, f=40, seed=9):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return X, y


@pytest.mark.slow
def test_pool_cap_matches_unlimited_fused():
    """Slow-marked (tier-1 budget): the serial pool-cap parity twin is
    already slow-marked for the same reason; pool-cap correctness under
    the fused learner re-proves composition of two tier-1-covered
    pieces (14s)."""
    X, y = make_data()
    base = {"objective": "binary", "verbose": -1, "min_data_in_leaf": 20,
            "num_leaves": 31}
    b_full = lgb.train(dict(base), lgb.Dataset(X, label=y),
                       num_boost_round=8, verbose_eval=False)
    # 31*40*256*2*4B ~= 2.5 MB -> 1 MB cap forces pool-less mode
    b_cap = lgb.train(dict(base, histogram_pool_size=1),
                      lgb.Dataset(X, label=y),
                      num_boost_round=8, verbose_eval=False)
    assert not b_cap._gbdt._fused._use_hist_pool
    assert b_full._gbdt._fused._use_hist_pool
    np.testing.assert_allclose(b_cap.predict(X), b_full.predict(X),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_pool_cap_matches_unlimited_serial():
    """Slow-marked: pool-cap parity is tier-1-covered by the fused
    variant above; this re-proves it on the host-loop serial grower
    (7s)."""
    X, y = make_data()
    # interaction constraints force the host-loop serial grower
    # (categoricals used to, but they run fused since round 3)
    base = {"objective": "binary", "verbose": -1, "min_data_in_leaf": 20,
            "num_leaves": 31,
            "interaction_constraints": [[0, 1, 2, 3], [4, 5, 6, 7]]}
    b_full = lgb.train(dict(base), lgb.Dataset(X, label=y),
                       num_boost_round=6, verbose_eval=False)
    b_cap = lgb.train(dict(base, histogram_pool_size=1),
                      lgb.Dataset(X, label=y),
                      num_boost_round=6, verbose_eval=False)
    assert b_cap._gbdt._fused is None
    np.testing.assert_allclose(b_cap.predict(X), b_full.predict(X),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_pool_cap_matches_unlimited_fused_categorical():
    """Categoricals run the FUSED grower now; the pool-less fallback
    must still match unlimited-pool training there. Slow-marked: the
    pool-less parity itself is tier-1-covered by the fused and serial
    variants above; this re-proves it on the categorical path (24s)."""
    X, y = make_data()
    Xc = X.copy()
    Xc[:, 3] = np.random.RandomState(1).randint(0, 5, len(X))
    base = {"objective": "binary", "verbose": -1, "min_data_in_leaf": 20,
            "num_leaves": 31, "categorical_feature": [3]}
    b_full = lgb.train(dict(base), lgb.Dataset(Xc, label=y),
                       num_boost_round=6, verbose_eval=False)
    b_cap = lgb.train(dict(base, histogram_pool_size=1),
                      lgb.Dataset(Xc, label=y),
                      num_boost_round=6, verbose_eval=False)
    np.testing.assert_allclose(b_cap.predict(Xc), b_full.predict(Xc),
                               rtol=1e-4, atol=1e-5)


def test_pool_cap_with_monotone_intermediate():
    """The intermediate monotone recompute path must survive dropped
    histograms (on-demand reconstruction)."""
    rng = np.random.RandomState(5)
    X = rng.rand(1200, 3)
    y = 2 * X[:, 0] - X[:, 1] + 0.02 * rng.randn(1200)
    params = {"objective": "regression", "verbose": -1,
              "min_data_in_leaf": 20, "num_leaves": 31,
              "monotone_constraints": [1, -1, 0],
              "monotone_constraints_method": "intermediate",
              "histogram_pool_size": 1}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10,
                    verbose_eval=False)
    grid = np.column_stack([np.linspace(0, 1, 50), np.full(50, .5),
                            np.full(50, .5)])
    assert np.all(np.diff(bst.predict(grid)) >= -1e-10)


# -- the pool after one grown tree ------------------------------------------
# Each split writes two rows of the loop-carried [L, F, B, 2] pool in place
# (fused.py, `lgbm.pool`). The hazard of an in-place write is reading the
# parent's row after the left child overwrote it, so: after one tree, every
# live row is the histogram of the rows its leaf ended with, and no row still
# holds a split parent's.

POOL_CASES = {
    "f32": {},
    "quantized_i32": {"use_quantized_grad": True},
    "forced_splits": {"forcedsplits_filename": "forced.json"},
    "data_parallel": {"tree_learner": "data", "tpu_mesh_shape": [4]},
    "categorical": {"categorical_feature": [3]},
}
FORCED = {"feature": 0, "threshold": 0.0,
          "left": {"feature": 1, "threshold": 0.2},
          "right": {"feature": 2, "threshold": -0.2}}


def _grow_once(g, data, n_valid, key):
    """One `_train_iter`, with the final FusedTreeState of its one tree."""
    seen = {}
    core = g._grow_tree_core

    def spy(*args, **kwargs):
        ta, st = core(*args, **kwargs)
        seen["st"] = st
        return ta, st

    g._grow_tree_core = spy
    try:
        _, ta = g._train_iter(data, g.feature_masks_for_tree(),
                              jnp.float32(0.1), jnp.float32(0.0),
                              n_valid=n_valid, key=key)
    finally:
        del g._grow_tree_core
    st = seen["st"]
    return (ta, st.hist_pool, st.data, st.leaf_start, st.leaf_count,
            st.n_leaves)


def _leaves_under(ta, node):
    out = []
    for child in (int(ta["left_child"][node]), int(ta["right_child"][node])):
        out += [~child] if child < 0 else _leaves_under(ta, child)
    return out


@pytest.mark.parametrize("case", sorted(POOL_CASES))
def test_pool_rows_are_the_final_leaves_histograms(case, tmp_path):
    extra = dict(POOL_CASES[case])
    if case == "data_parallel" and len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    if case == "forced_splits":
        path = tmp_path / extra["forcedsplits_filename"]
        path.write_text(json.dumps(FORCED))
        extra["forcedsplits_filename"] = str(path)
    X, y = make_data(n=2400, f=10)
    if case == "categorical":
        X[:, 3] = np.random.RandomState(1).randint(0, 6, len(X))
    params = dict({"objective": "binary", "verbose": -1, "num_leaves": 15,
                   "max_bin": 31, "min_data_in_leaf": 20}, **extra)
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    g = bst._gbdt._fused
    assert g._use_hist_pool and g._efb_hist is None
    assert (g._forced_sched is not None) == (case == "forced_splits")
    assert g.any_categorical == (case == "categorical")
    quant = case == "quantized_i32"
    key = g._next_quant_key() if quant else None
    data = g.init_persistent_state(np.zeros(len(X), np.float32))
    Ly, L = g.layout, g.num_leaves
    if case == "data_parallel":
        D = g.num_shards
        assert D == 4 and g.psum_axis == "data"
        out = jax.jit(shard_map(
            lambda data_l, nvalid_l: _grow_once(g, data_l, nvalid_l[0], key),
            mesh=g.mesh, check_vma=False,
            in_specs=(P(None, "data"), P("data")),
            out_specs=(P(), P(), P(None, "data"), P("data"), P("data"),
                       P())))(data, g._n_per_shard)
    else:
        D = 1
        out = jax.jit(lambda d: _grow_once(
            g, d, jnp.int32(g.actual_rows), key))(data)
    ta, pool, data, start, count, n_leaves = jax.device_get(out)
    n_leaves = int(n_leaves)
    assert n_leaves == L, "the cases are sized to grow a full tree"
    assert pool.dtype == (np.int32 if quant else np.float32)
    if case == "forced_splits":
        assert list(ta["split_feature"][:3]) == [0, 1, 2]
    start, count = start.reshape(D, L), count.reshape(D, L)

    # the scatter oracle, from the final partition alone
    bins = np.asarray(bst._gbdt.train_data.bins)
    F, B = g.num_features, g.max_num_bin
    assert bins.shape[1] == F
    acc = np.int64 if quant else np.float64
    want = np.zeros((L, F, B, 2), acc)
    for d in range(D):
        lanes = data[:, d * Ly.num_lanes:(d + 1) * Ly.num_lanes]
        if quant:
            gh = np.stack(Q.unpack_gh(lanes[Ly.grad]), axis=-1)
        else:
            gh = lanes[[Ly.grad, Ly.hess]].view(np.float32).T
        for leaf in range(L):
            w = slice(start[d, leaf], start[d, leaf] + count[d, leaf])
            rows = bins[lanes[Ly.rowid, w]]
            for f in range(F):
                np.add.at(want[leaf, f], rows[:, f], gh[w].astype(acc))
    assert count.sum() == len(X)
    assert (want[..., 1].sum(axis=(1, 2)) > 0).all()

    if quant:
        np.testing.assert_array_equal(pool, want)
    else:
        np.testing.assert_allclose(pool, want, rtol=1e-4, atol=1e-5)
    # ... and a parent's old row is nowhere
    for node in range(L - 1):
        parent = want[_leaves_under(ta, node)].sum(axis=0)
        for row in range(L):
            assert not np.allclose(pool[row], parent, rtol=1e-4, atol=1e-5), \
                f"row {row} still holds the histogram of split node {node}"
