"""Sampled training (GOSS) on bundled one-hot columns against a numpy
oracle: the sampler against the rule of goss.hpp:111-147, the first
sampled tree of `lgb.train` against float64 sums over its own bag, EFB
against `enable_bundle=false` and the dense copy, what the per-tree
fused tier says about itself (plan, scopes, counters), and the bag's
way into lane order: one compaction pass of the partition kernel over
the bag flag, held to the gather by a permutation it replaced, and the
one grow program every bag size shares. CPU, 20,000 x 700 at most.

The oracle is inline, as the other tests' are; benchmarks/reference/
keeps its own copy for the chip (`goss_numpy.py`).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import gbdt as G

FIELDS = (12, 31, 7, 22, 313, 313)      # one-hot fields: 698 columns + 2
P = dict(objective="binary", boosting="goss", top_rate=0.2, other_rate=0.1,
         num_leaves=31, max_bin=255, learning_rate=0.5,
         min_sum_hessian_in_leaf=20, min_data_in_leaf=0, verbose=-1)


# ------------------------------------------------------------- the oracle

def goss_counts(n, top_rate, other_rate):
    top_k = max(1, int(n * top_rate))
    return top_k, max(1, min(int(n * other_rate), n - top_k))


def goss_top_set(g, h, top_k):
    """Rows of the top_k largest sum_c |g*h|, ties to the lower row id."""
    w = np.abs(g.astype(np.float64) * h.astype(np.float64)).sum(axis=0)
    return np.sort(np.lexsort((np.arange(len(w)), -w))[:top_k])


def one_hot_rows(n, seed):
    """(CSR float32 [n, 700] with 8 stored values a row, y): two numeric
    columns and six one-hot fields with uneven levels."""
    rng = np.random.default_rng(seed)
    cols = [np.zeros(n, np.int64), np.ones(n, np.int64)]
    vals = [rng.uniform(0.1, 24.0, n), np.exp(rng.normal(1.6, 0.7, n))]
    score = 0.5 * np.sin(vals[0] / 4.0)
    off = 2
    for card in FIELDS:
        p = 1.0 / np.arange(1, card + 1) ** 0.6
        lev = rng.choice(card, n, p=p / p.sum())
        score += np.random.default_rng(card).normal(0, 0.6, card)[lev]
        cols.append(off + lev)
        vals.append(np.ones(n))
        off += card
    X = sp.csr_matrix(
        (np.stack(vals, 1).astype(np.float32).ravel(),
         np.stack(cols, 1).astype(np.int32).ravel(),
         np.arange(0, 8 * n + 1, 8, dtype=np.int32)), shape=(n, off))
    y = (rng.random(n) < 1 / (1 + np.exp(-1.5 * score))).astype(np.float32)
    return X, y


def parse_tree(text, index):
    block = dict(ln.split("=", 1) for ln in
                 text.split(f"\nTree={index}\n")[1].split("\n\n")[0]
                 .splitlines() if "=" in ln)
    num = lambda key, dt: np.asarray(block[key].split(), dt)  # noqa: E731
    return dict(feature=num("split_feature", np.int64),
                threshold=num("threshold", np.float64),
                left=num("left_child", np.int64),
                right=num("right_child", np.int64),
                value=num("leaf_value", np.float64),
                count=num("leaf_count", np.int64))


def leaf_of(tree, dense):
    node = np.zeros(len(dense), np.int64)
    rows = np.arange(len(dense))
    while rows.size:
        at = node[rows]
        go_left = dense[rows, tree["feature"][at]] <= tree["threshold"][at]
        node[rows] = np.where(go_left, tree["left"][at], tree["right"][at])
        rows = rows[node[rows] >= 0]
    return ~node


# ------------------------------------------------------------ the sampler

@pytest.mark.parametrize("classes", [1, 3])
@pytest.mark.parametrize("rates", [(0.2, 0.1), (0.1, 0.1)])
@pytest.mark.parametrize("n", [1000, 4097])
def test_sampler_against_the_numpy_rule(n, rates, classes):
    rng = np.random.default_rng(n + classes)
    g = rng.normal(size=(classes, n)).astype(np.float32)
    h = rng.uniform(0.05, 0.25, size=(classes, n)).astype(np.float32)
    top_k, other_k = goss_counts(n, *rates)
    g2, h2, in_bag = jax.jit(
        G._goss_sample_device, static_argnames=("top_k", "other_k"))(
        jnp.asarray(g), jnp.asarray(h), jnp.int32(7),
        top_k=top_k, other_k=other_k)
    g2, h2, in_bag = np.asarray(g2), np.asarray(h2), np.asarray(in_bag)

    assert in_bag.dtype == bool and in_bag.shape == (n,)
    bag = np.flatnonzero(in_bag)
    assert len(bag) == top_k + other_k
    mult = np.float32((n - top_k) / other_k)
    weighted = np.flatnonzero(~np.isclose(g2[0], g[0], rtol=1e-6, atol=0))
    top = goss_top_set(g, h, top_k)
    assert np.array_equal(np.setdiff1d(bag, weighted), top), "the top set"
    assert len(weighted) == other_k and not np.intersect1d(weighted, top).size
    assert np.isin(weighted, bag).all()
    np.testing.assert_allclose(g2[:, weighted], g[:, weighted] * mult, 1e-6)
    np.testing.assert_allclose(h2[:, weighted], h[:, weighted] * mult, 1e-6)
    rest = np.setdiff1d(np.arange(n), weighted)
    assert np.array_equal(g2[:, rest], g[:, rest])
    assert np.array_equal(h2[:, rest], h[:, rest])


# ------------------------------------------------- the k-th value alone

def kth_case(name, n, rng):
    if name == "normal":
        return rng.normal(size=n).astype(np.float32)
    if name == "four_values":               # heavy ties on the threshold
        return rng.choice(np.float32([-1.5, 0.25, 2.0, 2.5]), n)
    if name == "all_equal":
        return np.full(n, 3.25, np.float32)
    if name == "signed_zeros":
        return rng.choice(np.float32([-0.0, 0.0, 1.0, -1.0]), n)
    if name == "inf_and_denormals":
        x = rng.normal(size=n).astype(np.float32)
        for at, v in enumerate([np.inf, -np.inf, 1e-42, -1e-42, 1e-45]):
            x[at::7] = v
        return x
    assert name == "top_rows_masked"        # the sampler's second call
    r = rng.random(n).astype(np.float32)
    return np.where(rng.random(n) < 0.2, np.float32(-1.0), r)


@pytest.mark.parametrize("n, k", [(1, 1)] + [
    (n, k) for n in (1000, 4097)            # 4097: no multiple of 128
    for k in (1, 2, n // 5, n - 1, n)])
@pytest.mark.parametrize("name", [
    "normal", "four_values", "all_equal", "signed_zeros",
    "inf_and_denormals", "top_rows_masked"])
def test_kth_largest_is_the_sorts_element(name, n, k):
    """The select against `np.sort(x)[n - k]`: the same float32, not a
    neighbour (-0.0 and 0.0 are one value to every compare); the mask
    against the index set of `jax.lax.top_k`, equal values to the lower
    index."""
    x = kth_case(name, n, np.random.default_rng(n + len(name)))
    got = np.asarray(jax.jit(G._kth_largest, static_argnums=1)(
        jnp.asarray(x), k))
    assert got.dtype == np.float32 and got == np.sort(x)[n - k]
    rows = np.flatnonzero(np.asarray(
        jax.jit(G._largest_k_mask, static_argnums=1)(jnp.asarray(x), k)))
    _, want = jax.lax.top_k(jnp.asarray(x), k)
    assert np.array_equal(rows, np.sort(np.asarray(want)))


def parents_rule(g, h, seed, top_k, other_k):
    """`_goss_sample_device` as it stood before PR 35, in numpy: each
    k-th value read off a full sort, ties to the lower row by a cumsum;
    the bag is the one-bit mask its permutation was a stable argsort
    of."""
    def largest_k_mask(x, k):
        kth = np.sort(x)[len(x) - k]
        above, tie = x > kth, x == kth
        return above | (tie & (np.cumsum(tie) <= k - above.sum()))
    n = g.shape[1]
    # the weight as the device sums it: float32, class by class
    is_top = largest_k_mask(np.sum(np.abs(g * h), axis=0), top_k)
    r = np.asarray(jax.random.uniform(jax.random.PRNGKey(seed), (n,)))
    sampled = largest_k_mask(np.where(is_top, np.float32(-1.0), r), other_k)
    multiply = np.float32((n - top_k) / other_k)
    return (np.where(sampled, g * multiply, g),
            np.where(sampled, h * multiply, h), is_top | sampled)


def test_sampler_is_the_parents_rule_to_the_bit():
    rng = np.random.default_rng(35)
    g = rng.normal(size=(3, 4097)).astype(np.float32)
    h = rng.uniform(0.05, 0.25, size=(3, 4097)).astype(np.float32)
    g[:, ::3] = g[:, 1::3]          # equal weights at the threshold too
    h[:, ::3] = h[:, 1::3]
    top_k, other_k = goss_counts(4097, 0.2, 0.1)
    got = jax.jit(G._goss_sample_device,
                  static_argnames=("top_k", "other_k"))(
        jnp.asarray(g), jnp.asarray(h), jnp.int32(11),
        top_k=top_k, other_k=other_k)
    want = parents_rule(g, h, 11, top_k, other_k)
    for a, b in zip(got, want):
        assert np.asarray(a).tobytes() == b.tobytes()


# ------------------------------------------------- through lgb.train

@pytest.fixture(scope="module")
def flights():
    return one_hot_rows(20_000, seed=5)


def test_first_sampled_tree_is_the_oracles_on_the_same_bag(flights):
    """learning_rate 0.5: sampling starts at iteration 2, the third tree."""
    X, y = flights
    n = X.shape[0]
    bst = lgb.train(P, lgb.Dataset(X, label=y), num_boost_round=2,
                    keep_training_booster=True)
    gb = bst._gbdt
    score = np.asarray(gb.get_training_score(), np.float64)[0]
    bst.update()
    top_k, other_k = goss_counts(n, 0.2, 0.1)
    assert gb.bag_data_cnt == top_k + other_k
    bag = np.asarray(gb._perm)[:gb.bag_data_cnt]

    p = 1.0 / (1.0 + np.exp(-score))
    g, h = p - y, p * (1.0 - p)
    mult = (n - top_k) / other_k
    is_other = np.abs(np.asarray(gb._grad[0])[bag]) > 4.0 * np.abs(g[bag])
    assert is_other.sum() == other_k
    top = goss_top_set(g[None], h[None], top_k)
    # float32 weights against float64 ones: all but a few rows at the
    # threshold agree
    assert len(np.setdiff1d(top, bag[~is_other])) <= 3

    tree = parse_tree(bst.model_to_string(), 2)
    leaf = leaf_of(tree, X[bag].toarray())
    scale = np.where(is_other, mult, 1.0)
    L = len(tree["value"])
    assert np.array_equal(np.bincount(leaf, None, L), tree["count"])
    value = -0.5 * (np.bincount(leaf, g[bag] * scale, L)
                    / np.bincount(leaf, h[bag] * scale, L))
    np.testing.assert_allclose(tree["value"], value, rtol=2e-4, atol=2e-6)
    flat = -0.5 * (np.bincount(leaf, g[bag], L) / np.bincount(leaf, h[bag], L))
    assert np.max(np.abs(tree["value"] - flat)) > 1e-2, \
        "weights left at 1 would pass too: the oracle cannot see them"


@pytest.mark.parametrize("other", ["enable_bundle=false", "dense"])
def test_bundling_loses_nothing(flights, other):
    """efb_max_conflict_rate=0: only columns that no sampled row sets
    together are bundled, so the trees are those of the unbundled columns:
    the same splits and counts, and values that differ by the order of a
    float32 sum."""
    X, y = flights
    X, y = X[:4000], y[:4000]
    params = dict(P, num_leaves=15, efb_max_conflict_rate=0.0)
    bundled = lgb.Dataset(X, label=y, params=params).construct()
    assert bundled._handle.bins.shape[1] <= 16
    want = lgb.train(params, bundled, num_boost_round=3).model_to_string()
    if other == "dense":
        ds = lgb.Dataset(X.toarray(), label=y, params=params)
    else:
        params = dict(params, enable_bundle=False)
        ds = lgb.Dataset(X, label=y, params=params)
        assert ds.construct()._handle.bins.shape[1] > 600
    got = lgb.train(params, ds, num_boost_round=3).model_to_string()
    for index in range(3):          # the third tree is grown on a sample
        a, b = parse_tree(want, index), parse_tree(got, index)
        for key in ("feature", "threshold", "left", "right", "count"):
            assert np.array_equal(a[key], b[key]), (index, key)
        np.testing.assert_allclose(a["value"], b["value"], atol=2e-5)


@pytest.mark.parametrize("extra, sampling", [
    (dict(boosting="goss"), "goss(top_rate=0.2, other_rate=0.1)"),
    (dict(boosting="gbdt", bagging_fraction=0.8, bagging_freq=1),
     "bagging(0.8/1)"),
    (dict(boosting="gbdt"), None)])
def test_execution_plan_names_the_sampling(extra, sampling):
    X, y = one_hot_rows(2000, seed=1)
    bst = lgb.train(dict(P, **extra), lgb.Dataset(X, label=y),
                    num_boost_round=1, keep_training_booster=True)
    plan = bst._gbdt.execution_plan()
    assert plan["sampling"] == sampling
    assert plan["tier"] == ("per-tree-fused" if sampling
                            else "persistent-fused")
    # off a TPU the partition is the XLA one, and so is the traverse; a
    # run that leaves no row out assigns no leaf by traversing
    assert plan.get("row_traverse") == ("xla" if sampling else None)


# ------------------------------------------- the traverse kernel, end to end

SAMPLED = {
    "bagging": dict(boosting="gbdt", bagging_fraction=0.6, bagging_freq=1),
    "pos_neg_bagging": dict(boosting="gbdt", pos_bagging_fraction=0.5,
                            neg_bagging_fraction=0.8, bagging_freq=1),
    "goss": dict(boosting="goss", learning_rate=0.5),
    "rf": dict(boosting="rf", bagging_fraction=0.6, bagging_freq=1,
               feature_fraction=0.8),
    "goss_four_shards": dict(boosting="goss", learning_rate=0.5,
                             tree_learner="data", tpu_mesh_shape=[4]),
}


@pytest.mark.parametrize("name", sorted(SAMPLED))
def test_traverse_kernel_trains_the_ref_traverses_model(name, monkeypatch,
                                                        tmp_path):
    """Six trees with the Pallas kernels selected (interpreted here): the
    model and the training scores with `traverse_planes_pallas` assigning
    every row its leaf are, byte for byte, those with the XLA loop in its
    place. One-hot fields in bundles, a sample from the third tree on
    (GOSS) or from the first."""
    from lightgbm_tpu.compile import reset_manager
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.treelearner.fused import FusedSerialGrower
    if name == "goss_four_shards" and len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    X, y = one_hot_rows(3000, seed=6)
    params = dict(P, num_leaves=7, **SAMPLED[name])
    monkeypatch.setattr(H, "_use_tpu", lambda: True)
    monkeypatch.setenv("LGBM_TPU_WARMUP", "0")

    def train(side):
        # a compile cache a side: the two programs differ in nothing the
        # manager's key names
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / side))
        reset_manager()
        bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6,
                        keep_training_booster=True)
        assert bst._gbdt.execution_plan()["row_traverse"] == side
        return (bst.model_to_string(),
                np.asarray(bst._gbdt.get_training_score()).tobytes())

    try:
        got = train("pallas")
        monkeypatch.setattr(FusedSerialGrower, "row_traverse_method", "xla")
        want = train("xla")
    finally:
        reset_manager()
    assert got[0].count("Tree=") == 6
    assert got == want


# ------------------------------------------- the bag's way to lane order

def small_grower(n, method):
    """A grower of the per-tree tier on n rows x 6 columns, its partition
    set to `method` (the Pallas kernels run in the interpreter here)."""
    rng = np.random.default_rng(n)
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    params = dict(objective="binary", num_leaves=7, verbose=-1,
                  bagging_fraction=0.5, bagging_freq=1)
    g = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))._gbdt._fused
    g._part_method, g._interpret = method, True
    return g


def bag_case(name, n, tile, rng):
    flag = np.zeros(n, bool)
    if name == "random":
        flag[:] = rng.random(n) < 0.3
    elif name == "one_row":
        flag[n // 3] = True
    elif name == "all_but_one":
        flag[:] = True
        flag[tile + 5] = False
    elif name == "ends_on_a_tile":          # the bag fills whole tiles
        flag[rng.choice(n, 2 * tile, replace=False)] = True
    else:
        assert name == "last_rows"          # the bag's end meets the pad
        flag[n - 100:] = True
    return flag


@pytest.mark.parametrize("name", ["random", "one_row", "all_but_one",
                                  "ends_on_a_tile", "last_rows"])
@pytest.mark.parametrize("method", ["ref", "pallas", "pallas2"])
def test_compaction_lays_the_bag_out_in_row_order(method, name):
    """`_compact_bag` against numpy: lanes [0, count) of EVERY plane are
    the bag's rows in ascending order, what `take(src, flatnonzero)`
    gives, and the count is the flag's sum. 10,000 rows in a layout of
    tile 4,096: three tiles a pass, pad lanes behind the last row."""
    n = 10_000
    g = small_grower(n, method)
    Ly = g.layout
    assert Ly.num_lanes > n and n % Ly.tile
    rng = np.random.default_rng(len(name))
    flag = bag_case(name, n, Ly.tile, rng)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    codes = g.codes_planes()
    data, count = jax.jit(g._compact_bag)(
        codes, jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(flag),
        jnp.int32(n))
    rows = np.flatnonzero(flag)
    assert int(count) == flag.sum() == len(rows)
    data = np.asarray(data)[:, :len(rows)]
    assert np.array_equal(data[:Ly.code_planes], np.asarray(codes)[:, rows])
    assert data[Ly.grad].view(np.float32).tobytes() == grad[rows].tobytes()
    assert data[Ly.hess].view(np.float32).tobytes() == hess[rows].tobytes()
    assert np.array_equal(data[Ly.rowid], rows)


def gather_by_permutation(self, codes_planes, grad, hess, in_bag, n_valid,
                          mv=None):
    """`_compact_bag` as the bag was laid out until PR 37: a stable sort
    of the row ids by the flag, then ONE gather of codes, gradients and
    hessians by it."""
    from lightgbm_tpu.ops import plane
    n = grad.shape[0]
    perm = jnp.argsort(~in_bag, stable=True).astype(jnp.int32)
    C, R = codes_planes.shape
    src = jnp.concatenate([codes_planes[:, :n], plane.f32_as_i32(grad)[None],
                           plane.f32_as_i32(hess)[None]], axis=0)
    bag = jnp.take(src, perm, axis=1)
    data = plane.build_data(
        self.layout, jnp.pad(bag[:C], ((0, 0), (0, R - n))),
        plane.i32_as_f32(bag[C]), plane.i32_as_f32(bag[C + 1]), rowid=perm,
        mv=None if mv is None else jnp.take(mv, perm, axis=1))
    return data, jnp.sum(in_bag, dtype=jnp.int32)


@pytest.mark.parametrize("name", sorted(SAMPLED))
def test_compacted_bag_grows_the_gathered_bags_trees(name, monkeypatch,
                                                     tmp_path):
    """Six trees on one-hot fields in bundles, each kind of row sampling,
    on one device and on four shards: the model (structure, thresholds,
    leaf values, counts) and the training scores are, byte for byte,
    those of the layout the compaction replaced."""
    from lightgbm_tpu.compile import reset_manager
    from lightgbm_tpu.treelearner.fused import FusedSerialGrower
    if name == "goss_four_shards" and len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    X, y = one_hot_rows(3000, seed=8)
    params = dict(P, num_leaves=7, **SAMPLED[name])
    monkeypatch.setenv("LGBM_TPU_WARMUP", "0")

    def train(side):
        # a compile cache a side: the two programs differ in nothing the
        # manager's key names
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / side))
        reset_manager()
        bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6,
                        keep_training_booster=True)
        assert bst._gbdt.execution_plan()["bag_layout"] == \
            "partition-compaction"
        return (bst.model_to_string(),
                np.asarray(bst._gbdt.get_training_score()).tobytes())

    try:
        got = train("compaction")
        monkeypatch.setattr(FusedSerialGrower, "_compact_bag",
                            gather_by_permutation)
        want = train("gather")
    finally:
        reset_manager()
    assert got[0].count("Tree=") == 6
    assert got == want


def spy_on_grow(g, seen):
    """Record (bag flag's sum or None, the entry's executable key) of
    every `fused/grow_tree` call of grower g."""
    grow = g._grow_jit

    def spy(*args, **statics):
        seen.append((None if args[4] is None else int(args[4].sum()),
                     g._grow_entry.key_for(args, statics)))
        return grow(*args, **statics)

    g._grow_jit = spy


def test_a_bag_of_drifting_size_keeps_one_grow_program():
    """pos/neg bagging draws the bag's size anew each round. Planted:
    16,384 rows at 0.5 / 0.5, so the sizes straddle 8,192, a multiple of
    the lane tile. The flag has the rows' shape whatever it keeps and
    the bag's size is the compaction's own count, so every size calls
    ONE executable."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(16384, 6)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.normal(size=len(X)) > 0).astype(np.float32)
    params = dict(objective="binary", num_leaves=7, verbose=-1,
                  pos_bagging_fraction=0.5, neg_bagging_fraction=0.5,
                  bagging_freq=1)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=1,
                    keep_training_booster=True)
    g = bst._gbdt._fused
    calls = []
    spy_on_grow(g, calls)
    for _ in range(10):
        bst.update()
    counts, keys = zip(*calls)
    tile = g.layout.max_tile
    assert len({-(-c // tile) for c in counts}) > 1, (counts, tile)
    assert len(set(counts)) > 5 and len(set(keys)) == 1, calls
    assert counts[-1] == bst._gbdt.bag_data_cnt
    assert bst._gbdt.execution_plan()["tier"] == "per-tree-fused"


def test_a_round_that_keeps_every_row_compacts_nothing(per_tree_programs):
    """GOSS before sampling starts: the grow program is the one a run
    without sampling uses. It is handed no flag, and its text holds no
    op under `lgbm.bag_gather` and one partition fewer than the sampled
    program's."""
    X, y = one_hot_rows(2000, seed=4)
    bst = lgb.train(dict(P, learning_rate=0.25), lgb.Dataset(X, label=y),
                    num_boost_round=1, keep_training_booster=True)
    calls = []
    spy_on_grow(bst._gbdt._fused, calls)
    for _ in range(5):
        bst.update()                       # iterations 1..5; sampling from 4
    counts, keys = zip(*calls)
    assert counts == (None,) * 3 + (bst._gbdt.bag_data_cnt,) * 2
    assert len(set(keys[:3])) == 1 and len(set(keys[3:])) == 1
    assert keys[0] != keys[3]

    def scopes(program):
        return [nm.split("/") for nm in re.findall(
            r'loc\("([^"]*)"', per_tree_programs[program])]

    assert not [nm for nm in scopes("grow_all_rows")
                if "lgbm.bag_gather" in nm]
    assert [nm for nm in scopes("grow_all_rows") if "lgbm.build_state" in nm]
    assert not [nm for nm in scopes("grow") if "lgbm.build_state" in nm]
    sorts = {k: per_tree_programs[k].count("stablehlo.sort")
             for k in ("grow", "grow_all_rows")}
    # off a TPU a partition is `partition_ref`, one sort a capacity
    assert sorts["grow"] > sorts["grow_all_rows"] > 0, sorts


def test_the_permutation_is_derived_from_the_flag_and_resumes():
    """`_perm` read after an update is [the bag's rows | the rest], both
    ascending, and a checkpoint taken mid-bag (bagging_freq 3: the bag
    of iteration 3 serves 4 and 5) restores the same flag: the resumed
    booster's next trees are the uninterrupted one's."""
    X, y = one_hot_rows(2000, seed=9)
    params = dict(P, boosting="gbdt", num_leaves=7, bagging_fraction=0.6,
                  bagging_freq=3)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(params, ds, num_boost_round=5, keep_training_booster=True)
    gb = bst._gbdt
    flag = np.asarray(gb._in_bag)
    assert flag.sum() == gb.bag_data_cnt == 1200
    perm = np.asarray(gb._perm)
    assert perm.dtype == np.int32 and gb._perm is gb._perm      # cached
    assert np.array_equal(perm, np.concatenate(
        [np.flatnonzero(flag), np.flatnonzero(~flag)]))

    state, text = gb.checkpoint_state(), bst.model_to_string()
    other = lgb.Booster(dict(params, verbose=-1), ds)
    other._gbdt.restore_checkpoint_state(state, text)
    assert np.array_equal(np.asarray(other._gbdt._in_bag), flag)
    assert other._gbdt.bag_data_cnt == 1200
    for b in (bst, other):
        b.update()                          # iteration 5: the same bag
        b.update()                          # iteration 6: a new draw
    assert other.model_to_string() == bst.model_to_string()
    assert not np.array_equal(np.asarray(gb._in_bag), flag)


@pytest.mark.parametrize("extra, sampled_trees", [
    (dict(boosting="goss", learning_rate=0.5), 3),
    (dict(boosting="gbdt", bagging_fraction=0.7, bagging_freq=1), 5),
    (dict(objective="multiclass", num_class=3, boosting="gbdt"), 0)])
def test_trees_are_counted_by_their_rows_layout(extra, sampled_trees):
    """`fused.bag_compactions` / `fused.full_state_builds`: one count a
    tree at dispatch, by whether the tree was handed a bag flag."""
    from lightgbm_tpu import obs
    X, y = one_hot_rows(2000, seed=1)
    if "num_class" in extra:
        y = (np.arange(len(y)) % 3).astype(np.float32)
    reg = obs.MetricsRegistry()
    obs.activate(reg)
    try:
        bst = lgb.train(dict(P, num_leaves=7, **extra),
                        lgb.Dataset(X, label=y), num_boost_round=5,
                        keep_training_booster=True)
    finally:
        obs.deactivate(reg)
    plan = bst._gbdt.execution_plan()
    assert plan["tier"] == "per-tree-fused"
    assert plan.get("bag_layout") == ("partition-compaction"
                                      if sampled_trees else None)
    trees = 5 * extra.get("num_class", 1)
    assert reg.counters.get("fused.bag_compactions", 0) == sampled_trees
    assert reg.counters.get("fused.full_state_builds", 0) == \
        trees - sampled_trees


# ------------------------------------------------------------ the scopes

@pytest.fixture(scope="module")
def per_tree_programs():
    """{program: lowered text with locations} of a sampled iteration."""
    X, y = one_hot_rows(3000, seed=2)
    bst = lgb.train(dict(P, num_leaves=7), lgb.Dataset(X, label=y),
                    num_boost_round=3, keep_training_booster=True)
    gb = bst._gbdt
    g = gb._fused
    n = gb.num_data
    top_k, other_k = goss_counts(n, 0.2, 0.1)
    vec = jnp.zeros(n, jnp.float32)

    def grow(in_bag):
        return jax.jit(g._entry_grow_tree,
                       static_argnames=("compute_score_update",)).lower(
            g._tables(), g.codes_planes(), vec, vec, in_bag, jnp.int32(n),
            g.feature_masks_for_tree(), None, compute_score_update=True)

    lowered = {
        "sampler": jax.jit(G._goss_sample_device,
                           static_argnames=("top_k", "other_k")).lower(
            gb._grad, gb._hess, jnp.int32(1), top_k=top_k, other_k=other_k),
        "grow": grow(gb._in_bag),
        "grow_all_rows": grow(None),
        "score_add": jax.jit(G._score_add_device,
                             static_argnames=("class_id",)).lower(
            gb.train_score.score, jnp.zeros(7, jnp.float32),
            jnp.zeros(n, jnp.int32), class_id=0),
        "gradients": type(gb.objective).get_gradients.lower(
            gb.objective, gb.train_score.score[0]),
    }
    return {k: v.as_text(debug_info=True) for k, v in lowered.items()}


@pytest.mark.parametrize("scope, program", [
    ("lgbm.goss_sample", "sampler"), ("lgbm.bag_gather", "grow"),
    ("lgbm.row_traverse", "grow"), ("lgbm.score_update", "score_add"),
    ("lgbm.grad", "gradients")])
def test_per_tree_program_carries_scope(per_tree_programs, scope, program):
    names = re.findall(r'loc\("([^"]*)"', per_tree_programs[program])
    under = [nm for nm in names if scope in nm.split("/")]
    assert under, f"no op of the {program} program is under {scope}"
    if program == "grow":
        # the shared grower's own stages are there beside it
        assert any("lgbm.partition" in nm.split("/") for nm in names)


def test_sampler_selects_without_a_sort(per_tree_programs):
    """The two k-th values come from counting passes and the bag leaves
    as a flag: nothing sorts, scatters or gathers, and every op of
    the program's body, the passes' loop bodies included, carries the
    scope the benchmark books the sampler's time by (constants have no
    location; a helper's own ops take the scope from the call, which is
    in the body)."""
    text = per_tree_programs["sampler"]
    ops = re.findall(r'(?:stablehlo|chlo)\.[a-z_]+', text)
    assert not [op for op in ops
                if "sort" in op or "scatter" in op or "gather" in op]
    assert ops.count("stablehlo.while") >= 2        # a loop a k-th value

    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def names(ref):
        """Every name the location holds, its call sites' included."""
        return re.findall(r'"([^"]*)"', locs[ref]) + [
            nm for inner in re.findall(r"#loc\d+", locs[ref])
            for nm in names(inner)]

    main = text.split("func.func public @main", 1)[1]
    main = main.split("func.func private", 1)[0]
    checked = 0
    for ln in main.splitlines()[1:]:
        op = re.search(r'(?:= |^\s+)"?((?:stablehlo|chlo)\.[a-z_]+|call)\b',
                       ln)
        ref = re.search(r"loc\((#loc\d+)\)\s*$", ln)
        if not op or not ref or op.group(1) in ("stablehlo.constant",
                                                "stablehlo.return"):
            continue
        checked += 1
        assert any("lgbm.goss_sample" in nm.split("/")
                   for nm in names(ref.group(1))), ln
    assert checked > 100, checked
