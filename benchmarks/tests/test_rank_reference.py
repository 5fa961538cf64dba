"""The ranking reference (reference/lambdarank_numpy.py) on cases small
enough to work by hand, the generator's shape, and the comparison of
modes/train_rank.py on mutants: gradients that depart from the equations
in one way each must fail the named line `gradients_<name>`, and the
program's own must pass it with both controls refused."""
import types

import numpy as np
import pytest

from benchmarks.generators import ltr_like
from benchmarks.harness import loader
from benchmarks.reference import lambdarank_numpy as rank

mode = loader.load_module("modes", "train_rank")
BANDS = {"grad_rtol": 2e-5, "grad_atol": 1e-7}
PARAMS = {"sigmoid": 2.0, "lambdarank_truncation_level": 5,
          "lambdarank_norm": True}


def test_two_documents_by_hand():
    """One pair: label 1 above label 0, scored the wrong way round."""
    g, h, pairs = rank.query_gradients(np.array([0.0, 1.0]),
                                       np.array([1, 0]), norm=False)
    inv = 1.0                                   # max DCG = (2^1 - 1) / log2(2)
    d_ndcg = 1.0 * abs(1.0 / np.log2(3) - 1.0) * inv    # ranks 1 and 0
    rho = 1.0 / (1.0 + np.exp(-1.0))            # delta = s_h - s_w = -1
    assert pairs == 1
    assert g == pytest.approx([-d_ndcg * rho, d_ndcg * rho])
    assert h == pytest.approx([d_ndcg * rho * (1 - rho)] * 2)


def test_truncation_moves_only_the_inverse_max_dcg():
    rng = np.random.default_rng(0)
    s, lab = rng.normal(size=40), rng.integers(0, 5, 40)
    g30, h30, _ = rank.query_gradients(s, lab, truncation=30, norm=False)
    g3, h3, _ = rank.query_gradients(s, lab, truncation=3, norm=False)
    gain = rank.label_gain()
    ratio = rank.max_dcg(lab, 30, gain) / rank.max_dcg(lab, 3, gain)
    assert g3 == pytest.approx(g30 * ratio) and h3 == pytest.approx(h30 * ratio)


def test_ndcg_counts_a_query_without_relevant_documents_as_one():
    score = np.array([0.3, 0.1, 0.9, 0.5, 0.2])
    label = np.array([0, 0, 2, 1, 0])
    both = rank.ndcg_at_k(score, label, [2, 3], 10)
    second = rank.ndcg_at_k(score[2:], label[2:], [3], 10)
    assert second == pytest.approx(1.0)         # already in the ideal order
    assert both == pytest.approx((1.0 + second) / 2)
    assert rank.ndcg_at_k(-score[2:], label[2:], [3], 1) == 0.0


def test_bf16_rounding_keeps_eight_bits():
    x = np.array([1.0, 1.0 + 2.0 ** -8, 1.0 + 2.0 ** -7, -3.14159, 0.0])
    got = rank.round_bf16(x)
    assert got[0] == 1.0 and got[2] == 1.0 + 2.0 ** -7 and got[4] == 0.0
    assert got[1] in (1.0, 1.0 + 2.0 ** -7)     # a tie, to even: 1.0
    assert abs(got[3] - x[3]) <= 2.0 ** -8 * abs(x[3])


def test_generator_gives_the_published_shape():
    X, y, group, held = ltr_like.make(24_000, 200, 30, seed=2**31 + 9)
    assert X.dtype == np.float32 and X.shape[1] == 137
    assert group.sum() == 24_000 and len(group) == 200 and len(held) == 30
    assert X.shape[0] == y.shape[0] == 24_000 + held.sum()
    assert group.min() >= 1 and group.max() <= 1251
    shares = np.bincount(y, minlength=5) / len(y)
    assert set(np.unique(y)) <= set(range(5))
    assert np.all(np.abs(shares - ltr_like.SHARES) < (0.05, 0.04, 0.03, 0.01,
                                                      0.01))
    counts = X[:, ltr_like.COUNTS]
    assert np.all(counts == np.rint(counts)) and np.mean(counts == 0) > 0.5
    assert set(np.unique(X[:, ltr_like.BOOLS])) == {0.0, 1.0}
    again = ltr_like.make(24_000, 200, 30, seed=2**31 + 9)
    assert np.array_equal(again[0], X) and np.array_equal(again[2], group)
    # queries differ in how many relevant documents they hold
    bounds = np.concatenate([[0], np.cumsum(group)])
    big = [q for q in range(200) if group[q] >= 50]
    rel = [np.mean(y[bounds[q]:bounds[q + 1]] > 0) for q in big]
    assert np.std(rel) > 0.08


# ------------------------------------------------------------ the mutants

def _mutant(score, label, group, how, *, sigmoid, truncation):
    """The pair equations with one departure (`how`), float64."""
    gain = rank.label_gain()
    bounds = np.concatenate([[0], np.cumsum(group)])
    grad, hess = np.zeros(len(score)), np.zeros(len(score))
    for q in range(len(group)):
        lo, hi = bounds[q], bounds[q + 1]
        s, lab = np.asarray(score[lo:hi], np.float64), label[lo:hi]
        m = hi - lo
        if how == "ascending_sort":
            order = np.argsort(s, kind="stable")
        elif how == "unstable_sort":        # ties in reverse row order
            order = np.lexsort((-np.arange(m), -s))
        else:
            order = np.argsort(-s, kind="stable")
        r = np.empty(m, np.int64)
        r[order] = np.arange(m)
        t = truncation + 1 if how == "inv_at_wrong_t" else truncation
        top = rank.max_dcg(lab, t, gain)
        inv = 1.0 / top if top > 0 else 0.0
        disc = rank.discount(r)
        pair = lab[:, None] > lab[None, :]
        if how == "pairs_cut_to_top_t":
            pair &= np.minimum(r[:, None], r[None, :]) < truncation
        delta = s[:, None] - s[None, :]
        d = (gain[lab][:, None] - gain[lab][None, :]) \
            * np.abs(disc[:, None] - disc[None, :]) * inv
        if s.max() != s.min():
            d = d / (0.01 + np.abs(delta))
        rho = 1.0 / (1.0 + np.exp(sigmoid * delta))
        lam = np.where(pair, -sigmoid * d * rho, 0.0)
        power = 1 if how == "sigma_not_squared" else 2
        hes = np.where(pair, sigmoid ** power * d * rho * (1 - rho), 0.0)
        if how == "hessian_sign":
            hes = -hes
        g = lam.sum(1) - lam.sum(0)
        h = hes.sum(1) + hes.sum(0)
        total = -2.0 * lam.sum()
        if total > 0 and how != "normalisation_dropped":
            g, h = (x * np.log2(1 + total) / total for x in (g, h))
        grad[lo:hi], hess[lo:hi] = g, h
    return grad, hess


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(5)
    group = np.concatenate([[1, 2, 7, 8, 9, 130, 300],
                            rng.integers(20, 90, 40)])
    n = int(group.sum())
    label = rng.integers(0, 5, n).astype(np.int32)
    score = (rng.integers(-6, 7, n) * 0.25).astype(np.float32)     # ties
    st = types.SimpleNamespace(y=label, rows=n, group=group)
    return st, score, np.arange(len(group))


def _line(st, score, got, queries):
    kw = dict(bands=BANDS, params=PARAMS)
    (name, ok, detail), (_, refused, _) = mode._gradients(
        st, "case", score, {"grad": got[0], "hess": got[1]}, queries, **kw)
    assert name == "gradients_case"
    return ok, refused, detail


def test_the_equations_pass_their_own_line(case):
    st, score, queries = case
    got = _mutant(score, st.y, st.group, None, sigmoid=2.0, truncation=5)
    ok, refused, detail = _line(st, score, got, queries)
    assert ok and refused, detail


@pytest.mark.parametrize("how", [
    "normalisation_dropped", "unstable_sort", "ascending_sort",
    "pairs_cut_to_top_t", "inv_at_wrong_t", "hessian_sign",
    "sigma_not_squared"])
def test_a_mutant_fails_the_named_line(case, how):
    st, score, queries = case
    got = _mutant(score, st.y, st.group, how, sigmoid=2.0, truncation=5)
    ok, _, detail = _line(st, score, got, queries)
    assert not ok, detail


def test_the_program_passes_and_a_misplaced_bucket_fails(case):
    """The device program's own gradients pass the line; the same with
    one bucket's rows written back one slot on fail it."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective.rank import LambdarankNDCG
    st, score, queries = case
    obj = LambdarankNDCG(Config.from_params(dict(
        PARAMS, objective="lambdarank")))
    bounds = np.concatenate([[0], np.cumsum(st.group)])
    obj.init(types.SimpleNamespace(label=st.y.astype(np.float32),
                                   weights=None, query_boundaries=bounds),
             st.rows)
    g, h = (np.asarray(x, np.float64)
            for x in obj.get_gradients(jnp.asarray(score)))
    ok, refused, detail = _line(st, score, (g, h), queries)
    assert ok and refused, detail
    bucket = next(b for b in obj._layout["buckets"] if b["m"] == 64)
    for q in bucket["queries"]:
        lo, hi = bounds[q], bounds[q + 1]
        g[lo:hi], h[lo:hi] = np.roll(g[lo:hi], 1), np.roll(h[lo:hi], 1)
    ok, _, detail = _line(st, score, (g, h), queries)
    assert not ok, detail
