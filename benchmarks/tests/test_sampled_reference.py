"""The sampled-training reference (reference/goss_numpy.py): the GOSS rule
on a hand-made vector, what `check_sample` accepts and what it refuses
(three mutant samplers), the CSC histogram against a dense one, the CSR
walker against the dense walker, the generator's shape and seeding, and
what a bundle loses: the reference's rule, and the program with and
without bundling as its two witnesses."""
import numpy as np
import pytest
import scipy.sparse as sp

from benchmarks.generators import expo_onehot
from benchmarks.reference import gbdt_numpy as ref
from benchmarks.reference import goss_numpy as goss


def test_the_rule_on_a_hand_made_vector():
    g = np.array([0.5, -0.1, 0.9, 0.2, -0.7, 0.05, 0.3, -0.4, 0.6, 0.0])
    h = np.full(10, 0.25)
    w = goss.weight(g, h)
    assert goss.counts(10, 0.2, 0.1) == (2, 1)
    assert goss.counts(10, 0.01, 0.01) == (1, 1)        # never empty
    assert goss.threshold(w, 2) == pytest.approx(0.7 * 0.25)
    assert goss.multiplier(10, 2, 1) == 8.0
    top, other = goss.sample(w, 2, 1, np.random.default_rng(0))
    assert top.tolist() == [2, 4] and len(other) == 1
    assert other[0] not in (2, 4)
    # two classes: the weights add up over them
    assert goss.weight(np.stack([g, g]), np.stack([h, h])) == \
        pytest.approx(2 * w)
    # ties at the threshold go to the lower row id
    top, _ = goss.sample(np.array([1.0, 3.0, 1.0, 1.0]), 2, 1,
                         np.random.default_rng(0))
    assert top.tolist() == [0, 1]


def _weights(n=64_000, seed=3):
    rng = np.random.default_rng(seed)
    g = rng.normal(size=n)
    return goss.weight(g, rng.uniform(0.05, 0.25, n)), np.abs(g), rng


def _bag(top, other):
    bag = np.sort(np.concatenate([top, other]))
    return bag, np.isin(bag, other)


def test_check_sample_accepts_the_rule():
    w, _, rng = _weights()
    top_k, other_k = goss.counts(len(w), 0.2, 0.1)
    got = goss.check_sample(w, *_bag(*goss.sample(w, top_k, other_k, rng)),
                            top_k, other_k, tie_rtol=1e-5)
    assert got["ok"], got
    assert got["left_out"] == 0 and got["others"] == other_k


@pytest.mark.parametrize("mutant", ["first_rows", "unweighted", "by_gradient",
                                    "too_few", "with_replacement"])
def test_check_sample_refuses_a_mutant_sampler(mutant):
    """Each is a sampler a careless speed-up could become."""
    w, abs_g, rng = _weights()
    n = len(w)
    top_k, other_k = goss.counts(n, 0.2, 0.1)
    top, other = goss.sample(w, top_k, other_k, rng)
    rest = np.setdiff1d(np.arange(n), top)
    if mutant == "first_rows":          # the first other_k, not a draw
        bag, is_other = _bag(top, rest[:other_k])
        line = "worst_block_sigmas"
    elif mutant == "unweighted":        # drawn, but the weights left at 1
        bag, is_other = _bag(top, other)
        is_other[:] = False
        line = "others"
    elif mutant == "by_gradient":       # top set by |g| alone
        top = np.sort(np.argsort(-abs_g)[:top_k])
        rest = np.setdiff1d(np.arange(n), top)
        bag, is_other = _bag(top, rng.choice(rest, other_k, replace=False))
        line = "left_out"
    elif mutant == "too_few":           # a cheaper, smaller sample
        bag, is_other = _bag(top, other[:other_k // 2])
        line = "bag_size"
    else:                               # a row drawn twice
        bag, is_other = _bag(top, other)
        bag[-1] = bag[-2]
        line = "distinct"
    got = goss.check_sample(w, bag, is_other, top_k, other_k, tie_rtol=1e-5)
    assert not got["ok"], got
    good = goss.check_sample(w, *_bag(*goss.sample(w, top_k, other_k, rng)),
                             top_k, other_k, tie_rtol=1e-5)
    assert got[line] != good[line] or line == "worst_block_sigmas"
    if line == "worst_block_sigmas":
        assert got[line] > 5.0 > good[line]


def test_csc_histogram_is_the_dense_one():
    rng = np.random.default_rng(1)
    dense = np.where(rng.random((500, 6)) < 0.3,
                     rng.uniform(0.5, 9.0, (500, 6)), 0.0)
    dense[:, 0] = rng.random(500) < 0.2                 # an indicator
    g, h = rng.normal(size=500), rng.uniform(0.1, 0.3, 500)
    csc = sp.csc_matrix(dense)
    totals = np.array([g.sum(), h.sum(), 500.0])
    bounds = [np.array([1e-35, np.inf])] + \
        [np.array([1e-35, 2.0, 4.5, 7.0, np.inf])] * 5
    for c in range(6):
        got = goss.csc_histogram(csc, c, bounds[c], g, h, totals)
        want = ref.histogram(goss.column_bins(dense[:, c], bounds[c]), g, h,
                             len(bounds[c]))
        np.testing.assert_allclose(got, want, atol=1e-9)


def test_csr_walker_is_the_dense_walker():
    X, _ = expo_onehot.make(3000, seed=4)
    tree = ref.Tree(np.array([0, 80, 1]), np.array([12.0, 0.5, 3.0]),
                    np.array([1, ~0, ~2]), np.array([2, ~1, ~3]),
                    np.array([0.1, -0.2, 0.3, -0.4]), np.zeros(4, np.int64),
                    np.zeros(3, np.int64), np.zeros(3))
    dense = X.toarray()
    assert np.array_equal(goss.leaf_of_csr(tree, X), ref.leaf_of(tree, dense))
    assert np.array_equal(goss.leaf_of_csr(tree, X, 700, 1900),
                          ref.leaf_of(tree, dense[700:1900]))
    np.testing.assert_array_equal(goss.predict_raw_csr([tree, tree], X),
                                  ref.predict_raw([tree, tree], dense))
    # rows of unequal length: drop a stored value here and there
    ragged = X.copy()
    ragged.data[::7] = 0
    ragged.eliminate_zeros()
    assert np.array_equal(goss.leaf_of_csr(tree, ragged),
                          ref.leaf_of(tree, ragged.toarray()))
    cnt, sg, sh = goss.leaf_sums(ref.leaf_of(tree, dense), np.ones(3000),
                                 np.full(3000, 2.0), 4)
    assert cnt.sum() == 3000 and sg.sum() == 3000 and sh.sum() == 6000


def test_round_to_bits():
    x = np.array([1.0, 1.0 + 2.0 ** -8, 1.0 + 2.0 ** -7, -0.3, 0.0, 100.7])
    # bfloat16 keeps 8 significant bits: 1 + 2^-8 is a tie and goes to even
    assert goss.round_to_bits(x, 8).tolist() == \
        [1.0, 1.0, 1.0 + 2.0 ** -7, -0.30078125, 0.0, 100.5]
    assert goss.round_to_bits(x, 4).tolist() == \
        [1.0, 1.0, 1.0, -0.3125, 0.0, 104.0]
    rel = np.abs(goss.round_to_bits(np.linspace(0.01, 3, 999), 4)
                 / np.linspace(0.01, 3, 999) - 1)
    assert 2.0 ** -6 < rel.max() <= 2.0 ** -4


def test_bundle_keeps_the_later_member():
    # entries of one row: bundle, place in the bundle's order, claims
    bundle = np.array([-1, 0, 0, 1, 1, 0])
    rank = np.array([0, 2, 0, 1, 3, 1])
    keeps = goss.bundle_keeps(bundle, rank, np.ones(6, bool))
    assert keeps.tolist() == [True, True, False, False, True, False]
    # an entry at its default bin writes nothing, so it takes no row
    claims = np.array([True, False, True, True, True, True])
    keeps = goss.bundle_keeps(bundle, rank, claims)
    assert keeps.tolist() == [True, True, False, False, True, True]
    assert goss.bundle_keeps(bundle[:1], rank[:1], claims[:1]).tolist() == [True]


def test_what_a_bundle_loses_has_two_witnesses():
    """Three indicators that a few rows set together go into one bundle
    (the bundler's conflict budget). The program WITHOUT bundling bins and
    routes every row as the reference does from the raw values; the
    program WITH it differs on exactly the rows the reference's rule says
    lose an indicator, and agrees with the reference on the rows as
    `_as_bundled` presents them. So where the two programs differ, the
    reference sides with the unbundled one, and the difference is what
    bundling loses, not a fault of the walker."""
    import lightgbm_tpu as lgb
    from benchmarks.harness import loader
    mode = loader.load_module("modes", "train_sampled")
    rng = np.random.default_rng(0)
    n = 30_000
    a, b, c = (np.zeros(n, bool) for _ in range(3))
    a[:3000], b[3000:6000], c[6000:9000] = True, True, True
    b[[5, 17]] = True                   # two rows set A and B, one A and C
    c[40] = True
    order = rng.permutation(n)
    a, b, c = a[order], b[order], c[order]
    y = (rng.random(n) < 1 / (1 + np.exp(-(0.8 * a + 2.5 * b - 1.5 * c - 0.3)))
         ).astype(np.float32)
    X = sp.csr_matrix(np.stack([rng.uniform(0, 10, n), a, b, c], 1)
                      .astype(np.float32))
    params = dict(objective="binary", num_leaves=4, min_data_in_leaf=0,
                  min_sum_hessian_in_leaf=1, verbose=-1)
    bands = {"max_groups": 2, "bundle_conflict_rate": 1e-4}
    take = np.arange(n)
    trees, seen = {}, {}
    for bundled in (True, False):
        p = dict(params, enable_bundle=bundled)
        ds = lgb.Dataset(X, label=y, params=p).construct()
        st = mode.State(None, ds, X, X, y, n, {})
        ub = mode._upper_bounds(st)
        seen[bundled], changed = mode._as_bundled(st, ub)
        assert changed == (3 if bundled else 0)
        assert ds._handle.bins.shape[1] == (2 if bundled else 4)
        (_, ok, text), = mode._bundles(st, seen[bundled], take, ub,
                                       dict(bands, max_groups=4))
        assert ok and " 0 decoded bins differ" in text, text
        if bundled:
            # held against the RAW rows the codes are refused: 3 bins
            (_, ok, text), = mode._bundles(st, X, take, ub, bands)
            assert not ok and " 3 decoded bins differ" in text, text
        bst = lgb.train(p, ds, num_boost_round=1)
        trees[bundled] = ref.parse_model(bst.model_to_string())[0]
    # A is the last member of the bundle and keeps its three rows
    lost = (seen[True] != X).nonzero()
    assert sorted(zip(*lost)) == sorted(
        [(r, 2) for r in np.flatnonzero(a & b)]
        + [(r, 3) for r in np.flatnonzero(a & c)])
    for bundled, rows in ((False, X), (True, seen[True])):
        tree = trees[bundled]
        assert tree.split_feature[0] == 2      # B, which loses two rows
        counts = np.bincount(goss.leaf_of_csr(tree, rows), None,
                             tree.num_leaves)
        assert np.array_equal(counts, tree.leaf_count), bundled
    # and the bundled program routes the three rows as the raw values do not
    raw = np.bincount(goss.leaf_of_csr(trees[True], X), None,
                      trees[True].num_leaves)
    assert np.abs(raw - trees[True].leaf_count).sum() == 2 * 3, raw


def test_generator_shape_and_seeding():
    X, y = expo_onehot.make(5000, seed=2**31 + 45)
    assert X.shape == (5000, 700) and X.dtype == np.float32
    assert np.all(np.diff(X.indptr) == expo_onehot.STORED)
    offs = expo_onehot.field_offsets()
    cols = X.indices.reshape(5000, 8)
    assert np.all(cols[:, :2] == [0, 1]) and np.all(X.data.reshape(-1, 8)[:, 2:] == 1)
    for f, (_, card, _) in enumerate(expo_onehot.FIELDS):
        assert np.all((cols[:, 2 + f] >= offs[f])
                      & (cols[:, 2 + f] < offs[f] + card))
    assert 0.3 < y.mean() < 0.7
    X2, y2 = expo_onehot.make(5000, seed=2**31 + 45)
    assert (X != X2).nnz == 0 and np.array_equal(y, y2)
    X3, _ = expo_onehot.make(5000, seed=2**31 + 46)
    assert (X != X3).nnz > 0
    with pytest.raises(ValueError):
        expo_onehot.make(10, seed=1, cols=28)
