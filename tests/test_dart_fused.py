"""DART on the per-tree fused tier: the dropped trees replayed over the
resident code planes (`plane.replay_forest_*`, `boosting/dart_replay`),
their scales on the host, no tree fetched and no row-major table.

Held here: the forest replay against the one-tree replay and against
numpy routing (categorical bitsets, missing bins, EFB bundles), the
one-tree leaf ids bit for bit; the device DART against the host-loop
DART (the oracle path, which materializes and walks) and against the
float64 bookkeeping of `benchmarks.reference.dart_numpy`, in all four
`uniform_drop` x `xgboost_dart_mode` modes; no blocking sync in an
iteration; a checkpoint round trip still byte-identical; a pending tree
scaled as a host tree is, bias included; rollback by the same replay.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmarks.reference import dart_numpy
from lightgbm_tpu import obs
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import plane
from lightgbm_tpu.treelearner.fused import PendingTree

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import kernel_check as K  # noqa: E402


@pytest.fixture
def drop_executables():
    """Interpreted kernels are large executables; drop them per case
    (the memory-mapping limit, ROADMAP C11)."""
    yield
    plane.traverse_planes_pallas.clear_cache()
    plane.replay_forest_pallas.clear_cache()


# ------------------------------------------------------ the forest replay

# features 0..5 in 2 bundle columns: (column, offset, slots, skip bin)
EFB6 = [(0, 1, 9, 0), (0, 10, 9, 4), (0, 19, 30, 30),
        (1, 1, 20, 7), (1, 21, 20, 0), (1, 41, 5, 2)]
# kind -> (columns, highest code + 1, features split on, what else)
KINDS = {
    "numerical": (6, 256, list(range(6)), {}),
    "categorical": (6, 256, list(range(6)), {"cat": [1, 2, 5]}),
    "missing_bin": (6, 8, list(range(6)), {"miss_bin": 7, "max_bin": 7}),
    "efb_bundled": (2, 64, list(range(6)),
                    {"efb": EFB6, "cat": [0, 4], "max_bin": 31}),
}
ROWS, LEAVES = 3000, 15


def _forest(kind: str, trees: int, seed: int):
    """(code planes, layout, miss, efb, tree arrays, routes, values) of
    `trees` random trees of up to LEAVES leaves, with random leaf and
    internal values."""
    cols, hi, feats, extra = KINDS[kind]
    rng = np.random.RandomState(seed)
    codes = rng.randint(0, hi, size=(ROWS, cols)).astype(np.uint8)
    layout = plane.make_layout(cols, 8, ROWS)
    cp = plane.build_codes_planes(jnp.asarray(codes), layout)
    efb = tuple(jnp.asarray(t, jnp.int32) for t in zip(*extra["efb"])) \
        if "efb" in extra else None
    nf = len(extra["efb"]) if efb else cols
    miss = jnp.full(nf, extra.get("miss_bin", -1), jnp.int32)
    W, Wv = plane.replay_widths(LEAVES)
    tas, routes, values = [], [], []
    for t in range(trees):
        ta = K.random_tree(rng, LEAVES, rng.randint(0, LEAVES), feats,
                           extra.get("max_bin", hi),
                           cat_features=extra.get("cat", ()))
        ta = dict(ta, leaf_value=jnp.asarray(rng.normal(size=LEAVES),
                                             jnp.float32),
                  internal_value=jnp.asarray(rng.normal(size=LEAVES - 1),
                                             jnp.float32))
        tas.append(ta)
        tb = plane.traverse_table(layout, ta, miss, efb)
        routes.append(jnp.pad(tb, (0, W - tb.shape[0])))
        vv = plane.replay_values(ta)
        values.append(jnp.pad(vv, (0, Wv - vv.shape[0])))
    return (cp, layout, miss, efb, tas, jnp.stack(routes),
            jnp.stack(values))


@pytest.mark.parametrize("k", [0, 1, 7, 50])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_forest_replay_is_the_weighted_sum_of_its_trees(kind, k,
                                                       drop_executables):
    trees = max(k, 1) + 2
    cp, layout, miss, efb, tas, routes, values = _forest(kind, trees,
                                                         7 * k + len(kind))
    rng = np.random.RandomState(k)
    idx = rng.choice(trees, size=k, replace=k > trees).astype(np.int32)
    w = rng.normal(size=k).astype(np.float32)
    kmax = max(k, 1) + 3
    pad = idx[-1] if k else 0
    sel = jnp.asarray(np.concatenate([[k], idx, [pad] * (kmax - k)]),
                      jnp.int32)
    vals = jnp.concatenate([values[idx] * jnp.asarray(w)[:, None],
                            jnp.zeros((kmax - k, values.shape[1]))])
    ref = np.asarray(plane.replay_forest_ref(cp, routes, vals, sel))
    got = np.asarray(plane.replay_forest_pallas(cp, routes, vals, sel,
                                                interpret=True))
    # the oracle: each tree's leaf ids by the one-tree XLA replay, its
    # weighted leaf values summed in the same order in float32
    want = np.zeros(cp.shape[1], np.float32)
    for j, t in enumerate(idx):
        leaf = np.asarray(plane.traverse_planes_ref(cp, layout, tas[t], miss,
                                                    efb))
        want += (np.float32(w[j]) * np.asarray(tas[t]["leaf_value"]))[leaf]
    np.testing.assert_array_equal(ref, want)
    np.testing.assert_array_equal(got, want)
    if k == 1:
        # the one-tree kernel's leaf ids, bit for bit, and its values
        leaf = np.asarray(plane.traverse_planes_pallas(
            cp, routes[idx[0]], interpret=True))
        np.testing.assert_array_equal(leaf, np.asarray(
            plane.traverse_planes_ref(cp, layout, tas[idx[0]], miss, efb)))


def test_forest_replay_of_numerical_trees_against_numpy_routing(
        drop_executables):
    """Leaf values by walking the nodes over the codes in numpy."""
    cp, layout, miss, efb, tas, routes, values = _forest("numerical", 4, 3)
    codes = np.asarray(jnp.stack([
        (cp[c // 4] >> (8 * (c % 4))) & 255 for c in range(6)], axis=1))
    sel = jnp.asarray([3, 0, 2, 3, 3], jnp.int32)
    vals = values[jnp.asarray([0, 2, 3, 3])].at[3].set(0.0)
    got = np.asarray(plane.replay_forest_pallas(cp, routes, vals, sel,
                                                interpret=True))
    want = np.zeros(cp.shape[1], np.float32)
    for t in (0, 2, 3):
        ta = {k: np.asarray(v) for k, v in tas[t].items()}
        node = np.zeros(len(codes), np.int64) if ta["n_leaves"] > 1 \
            else np.full(len(codes), -1, np.int64)
        rows = np.flatnonzero(node >= 0)
        while rows.size:
            at = node[rows]
            node[rows] = np.where(
                codes[rows, ta["split_feature"][at]] <= ta["threshold_bin"][at],
                ta["left_child"][at], ta["right_child"][at])
            rows = rows[node[rows] >= 0]
        want += ta["leaf_value"][~node]
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------- DART end to end

def _data(n=4096, f=28, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0)
    return X, y.astype(np.float64)


DART = {"objective": "binary", "boosting": "dart", "num_leaves": 15,
        "learning_rate": 0.2, "drop_rate": 0.5, "skip_drop": 0.0,
        "drop_seed": 4, "max_drop": 50, "verbose": -1}


@pytest.mark.parametrize("xgboost", [False, True])
@pytest.mark.parametrize("uniform", [False, True])
def test_device_dart_against_the_host_loop_and_the_reference(uniform,
                                                             xgboost):
    X, y = _data()
    p = dict(DART, uniform_drop=uniform, xgboost_dart_mode=xgboost)
    dev = lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=40)
    host = lgb.train(dict(p, tpu_fused=False), lgb.Dataset(X, label=y),
                     num_boost_round=40)
    gd, gh = dev._gbdt, host._gbdt
    assert gd.execution_plan()["tier"] == "per-tree-fused"
    assert gd.execution_plan()["dart"] == {"replay": "xla", "kmax": 50}
    assert gh.execution_plan()["tier"] == "host-loop"
    assert "dart" not in gh.execution_plan()
    want = dart_numpy.schedule(40, p)
    assert list(gd.drop_history) == list(gh.drop_history) == \
        list(enumerate(want["drops"]))
    assert sum(len(d) for d in want["drops"]) > 100
    np.testing.assert_allclose(gd.tree_weight, want["tree_weight"],
                               rtol=1e-12)
    np.testing.assert_allclose(gh.tree_weight, want["tree_weight"],
                               rtol=1e-12)
    text = dev.model_to_string()
    np.testing.assert_allclose(dart_numpy.parse_shrinkage(text),
                               dart_numpy.model_shrinkage(want, True),
                               rtol=1e-12)
    # the two models, and each model against its own training score
    raw_d = dev.predict(X, raw_score=True)
    raw_h = host.predict(X, raw_score=True)
    np.testing.assert_allclose(raw_d, raw_h, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gd.get_training_score())[0],
                               raw_d, atol=1e-5)
    assert gd.train_data._device_bins is None


def test_a_dart_iteration_makes_no_blocking_sync():
    X, y = _data(2048)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(dict(DART), ds, num_boost_round=6,
                    keep_training_booster=True)
    reg = obs.activate(obs.MetricsRegistry())
    tracer = obs_trace.activate_tracer(obs.Tracer())
    obs_trace.install_sync_tracing()
    try:
        for _ in range(3):
            bst.update()
        syncs = sum(1 for ev in tracer.buf if ev[2] == "sync")
    finally:
        obs_trace.uninstall_sync_tracing()
        obs_trace.deactivate_tracer(tracer)
        obs.deactivate(reg)
    g = bst._gbdt
    assert syncs == 0
    drops = [d for it, d in g.drop_history if it >= 6]
    assert reg.counters["dart.trees_replayed"] == sum(len(d) for d in drops) > 0
    assert reg.counters["dart.drop_rounds"] == sum(1 for d in drops if d)
    assert ds._handle._device_bins is None
    assert all(isinstance(t, PendingTree) and t._tree is None
               for t in g.models)


def test_the_pallas_replay_keeps_the_score_its_model_gives(monkeypatch,
                                                          drop_executables):
    """With the Pallas kernels selected (interpreted) the plan names the
    Pallas replay, the drops are the reference's, and the training score
    the replays kept is what the saved model predicts."""
    X, y = _data(2048, 8)
    p = dict(DART, num_leaves=7, max_bin=63)
    monkeypatch.setattr(H, "_use_tpu", lambda: True)
    bst = lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=8)
    g = bst._gbdt
    assert g.execution_plan()["dart"]["replay"] == "pallas"
    assert list(g.drop_history) == list(enumerate(
        dart_numpy.schedule(8, p)["drops"]))
    np.testing.assert_allclose(np.asarray(g.get_training_score())[0],
                               bst.predict(X, raw_score=True), atol=1e-5)


def test_a_dart_checkpoint_round_trip_is_byte_identical(tmp_path):
    X, y = _data(600, 5, seed=3)
    p = dict(DART, num_leaves=7, min_data_in_leaf=5, checkpoint_interval=2,
             learning_rate=0.3)

    def run(rounds, ckpt=None):
        return lgb.train(dict(p), lgb.Dataset(X, label=y),
                         num_boost_round=rounds, verbose_eval=False,
                         checkpoint_dir=ckpt)

    d = str(tmp_path / "ck")
    run(5, d)
    assert any(n.endswith(".lgbckpt") for n in os.listdir(d))
    resumed = run(10, d)
    fresh = run(10)
    assert resumed._gbdt.execution_plan()["tier"] == "per-tree-fused"
    assert any(len(dd) for _, dd in fresh._gbdt.drop_history)
    assert resumed.model_to_string() == fresh.model_to_string()


def test_rollback_on_the_per_tree_tier_replays_the_tree_off():
    X, y = _data(2048)
    ds = lgb.Dataset(X, label=y)
    for extra in ({"boosting": "gbdt", "bagging_fraction": 0.7,
                   "bagging_freq": 1}, dict(DART)):
        p = dict(extra, objective="binary", num_leaves=15, verbose=-1)
        bst = lgb.train(dict(p), ds, num_boost_round=5,
                        keep_training_booster=True)
        g = bst._gbdt
        assert g.execution_plan()["tier"] == "per-tree-fused"
        forest = getattr(g, "_forest", None)
        bst.rollback_one_iter()
        assert bst.num_trees() == 4 and g.iter == 4
        # DART replays the tree off its resident tables: none are built
        assert getattr(g, "_forest", None) is forest
        np.testing.assert_allclose(np.asarray(g.get_training_score())[0],
                                   bst.predict(X, raw_score=True), atol=1e-5)
        assert ds._handle._device_bins is None
        bst.update()
        np.testing.assert_allclose(np.asarray(g.get_training_score())[0],
                                   bst.predict(X, raw_score=True), atol=1e-5)


# --------------------------------------------------- a pending tree's scale

def test_a_pending_tree_scales_its_bias_as_a_host_tree_does():
    """Tree::Shrinkage scales the whole output: a bias added before it
    too. A tree left pending and one materialized at once agree after
    apply_shrinkage, add_bias, apply_shrinkage, to the bit."""
    X, y = _data(1024, 6)
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                     "bagging_fraction": 0.5, "bagging_freq": 1},
                    lgb.Dataset(X, label=y), num_boost_round=1)
    t = bst._gbdt.models[0]
    late = PendingTree(t.grower, t.tree_arrays)
    early = PendingTree(t.grower, t.tree_arrays)
    early.materialize()
    for tree in (late, early):
        tree.apply_shrinkage(0.1)
        tree.add_bias(-0.7)
        tree.apply_shrinkage(0.25)
    n = int(early.num_leaves)
    assert late._tree is None
    np.testing.assert_allclose(np.asarray(late.leaf_values_device())[:n],
                               early.leaf_value[:n], rtol=1e-6)
    assert late.pending_bias == pytest.approx(-0.7 * 0.25)
    mat = late.materialize()
    np.testing.assert_array_equal(mat.leaf_value, early.leaf_value)
    assert mat.shrinkage == early.shrinkage == 0.25
    assert mat.to_string() == early.to_string()
