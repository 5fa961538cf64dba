"""The ranking objectives' one device program an iteration
(objective/rank.py) against the plain numpy reference of the pair
equations (benchmarks/reference/lambdarank_numpy.py: float64, a loop over
queries and pairs), two-sided; what the program is made of (one scope,
no scatter); XE-NDCG to the bit against outputs recorded before PR 34
rewrote the layout; the plan's `rank_grad` entry; and the unsampled
per-tree grow program's leaf of every row. CPU, small.
"""
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmarks.reference import lambdarank_numpy as ref
from lightgbm_tpu.config import Config
from lightgbm_tpu.objective import rank

SIZES = (1, 2, 7, 8, 9, 130, 1300)
RTOL = 2e-5         # of the query's largest |reference| (float32 sums)


def _objective(cls, sizes, label, **params):
    obj = cls(Config.from_params(dict(params, objective=cls.name)))
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    obj.init(SimpleNamespace(label=np.asarray(label, np.float32),
                             weights=None, query_boundaries=bounds),
             int(bounds[-1]))
    return obj


def _held(got_g, got_h, want_g, want_h, sizes):
    """Every document's gradient and hessian within RTOL of its query's
    largest reference value, both ways."""
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    for q in range(len(sizes)):
        lo, hi = bounds[q], bounds[q + 1]
        for got, want in ((got_g, want_g), (got_h, want_h)):
            tol = RTOL * np.abs(want[lo:hi]).max() + 1e-7
            worst = np.abs(got[lo:hi] - want[lo:hi]).max()
            assert worst <= tol, (q, sizes[q], worst, tol)


def _case(seed, sizes=SIZES, scores="normal"):
    rng = np.random.default_rng(seed)
    n = int(np.sum(sizes))
    label = rng.integers(0, 5, n)
    if scores == "normal":
        score = rng.normal(size=n)
    elif scores == "tied":              # a handful of distinct values
        score = rng.integers(0, 4, n) * 0.5
    else:                               # all equal: best == worst
        score = np.full(n, 0.25)
    return np.asarray(sizes), label, score.astype(np.float32)


@pytest.mark.parametrize("truncation", [1, 20, 30])
@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("scores", ["normal", "tied", "equal"])
def test_lambdarank_gradients_equal_the_reference(scores, norm, truncation):
    sizes, label, score = _case(3, scores=scores)
    obj = _objective(rank.LambdarankNDCG, sizes, label,
                     lambdarank_norm=norm,
                     lambdarank_truncation_level=truncation)
    g, h = obj.get_gradients(jnp.asarray(score))
    want_g, want_h = ref.gradients(score, label, sizes, norm=norm,
                                   truncation=truncation)
    assert g.dtype == jnp.float32 and g.shape == (len(label),)
    assert np.abs(want_g).max() > 0
    _held(np.asarray(g), np.asarray(h), want_g, want_h, sizes)


def test_one_label_queries_have_no_gradient():
    sizes = np.array([1, 5, 40, 9])
    rng = np.random.default_rng(0)
    label = np.repeat([2, 0, 3, 1], sizes)
    obj = _objective(rank.LambdarankNDCG, sizes, label)
    g, h = obj.get_gradients(jnp.asarray(
        rng.normal(size=len(label)).astype(np.float32)))
    assert not np.any(np.asarray(g)) and not np.any(np.asarray(h))


def test_sigmoid_and_label_gain_reach_the_program():
    sizes, label, score = _case(5, sizes=(12, 70, 3))
    gain = [0.0, 1.0, 1.0, 5.0, 4.0]    # neither 2^l - 1 nor monotone
    obj = _objective(rank.LambdarankNDCG, sizes, label, sigmoid=2.0,
                     label_gain=gain)
    g, h = obj.get_gradients(jnp.asarray(score))
    want = ref.gradients(score, label, sizes, sigmoid=2.0,
                         gain=np.asarray(gain))
    _held(np.asarray(g), np.asarray(h), *want, sizes)


def test_max_dcg_is_the_calculators():
    sizes, label, _ = _case(9, sizes=(1, 3, 8, 31, 64, 100))
    obj = _objective(rank.LambdarankNDCG, sizes, label,
                     lambdarank_truncation_level=5)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    for q in range(len(sizes)):
        top = obj.dcg.cal_max_dcg_at_k(5, label[bounds[q]:bounds[q + 1]])
        assert obj.inverse_max_dcgs[q] == pytest.approx(
            1.0 / top if top > 0 else 0.0, rel=1e-12)


def test_log2_keeps_float32_precision():
    """The program's own log2 (the TPU's is good to 1e-4, measured in
    PR 34): to 3e-7 of the value, or 6e-7 absolute near 1."""
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.uniform(1.0, 2e4, 50_000),
                        1.0 + rng.uniform(0, 1e-3, 1000),
                        [1.0, 2.0, np.sqrt(2.0), 1e-3, 3e8]]).astype(np.float32)
    got = np.asarray(jax.jit(rank._log2)(jnp.asarray(x)), np.float64)
    want = np.log2(x.astype(np.float64))
    assert np.all(np.abs(got - want) <= 3e-7 * np.abs(want) + 6e-7)


def test_layout_gives_every_row_one_slot():
    sizes = np.array([3, 9, 1, 20, 8, 17, 2, 130])
    lay = rank._bucket_queries(np.concatenate([[0], np.cumsum(sizes)]),
                               pair_slots=2048)
    assert [b["m"] for b in lay["buckets"]] == [8, 16, 32, 256]
    assert len(np.unique(lay["row_slot"])) == sizes.sum()
    for b in lay["buckets"]:
        assert b["padded"] % b["chunk"] == 0 and b["padded"] >= len(b["queries"])
        assert b["chunk"] * b["m"] ** 2 <= 2048 or b["chunk"] == 1
        assert np.all(sizes[b["queries"]] <= b["m"])
        assert np.all(np.diff(b["queries"]) > 0)    # as given, in a bucket
    real = lay["count"] > 0
    assert sorted(lay["count"][real]) == sorted(sizes)
    assert lay["slots"] == sum(b["padded"] * b["m"] for b in lay["buckets"])


def _gradient_program_text(obj, n):
    """(lowered ops, compiled text with every instruction's `op_name`)."""
    fn = jax.jit(obj._gradients_device)     # tpulint: jit-ok(test: the program's own text)
    args = (jnp.zeros(n, jnp.float32), obj._layout_dev, obj._label_slots)
    last = obj._inv_dev if hasattr(obj, "_inv_dev") else \
        jnp.zeros(obj._layout["slots"], jnp.float32)
    lowered = fn.lower(*args, last)
    return lowered.as_text(), lowered.compile().as_text()


NESTED = ("lgbm.rank_sort", "lgbm.rank_pairs", "lgbm.rank_to_rows")


@pytest.mark.parametrize("cls", [rank.LambdarankNDCG, rank.RankXENDCG])
def test_gradient_program_is_one_scope_and_scatters_nothing(cls):
    sizes, label, _ = _case(1, sizes=(1, 2, 7, 8, 9, 130, 600, 40, 40))
    obj = _objective(cls, sizes, label)
    ops_text, compiled = _gradient_program_text(obj, len(label))
    assert "scatter" not in ops_text and "stablehlo.gather" in ops_text
    names = re.findall(r'op_name="([^"]*)"', compiled)
    # an instruction's name is its whole path from the program down; the
    # inside of a sort's comparator or a reduction's adder, traced in a
    # `lax.map` body, keeps the path from its nested scope only
    ops = [nm for nm in names if nm.startswith("jit(")]
    inner = [nm for nm in names if not nm.startswith("jit(") and "/" in nm]
    assert len(ops) > 100
    outside = [nm for nm in ops if "lgbm.rank_grad" not in nm.split("/")]
    assert not outside, outside[:5]
    assert all(nm.split("/")[0] in NESTED for nm in inner), \
        [nm for nm in inner if nm.split("/")[0] not in NESTED][:5]
    for nested in NESTED:
        if cls is rank.RankXENDCG and nested == "lgbm.rank_sort":
            continue            # XE-NDCG ranks nothing
        under = [nm for nm in ops if nested in nm.split("/")]
        assert under, nested
        # nested INSIDE the one outermost scope
        assert all(nm.split("/").index("lgbm.rank_grad")
                   < nm.split("/").index(nested) for nm in under)


def test_rank_module_adds_at_no_index():
    src = open(os.path.join(os.path.dirname(rank.__file__), "rank.py")).read()
    code = re.sub(r'""".*?"""', "", src, flags=re.S)
    assert not re.search(r"\.at\[[^\]]*\]\.add", code)
    assert "scatter" not in re.sub(r"#.*", "", code)


def test_xendcg_is_unchanged_to_the_bit():
    """Against `RankXENDCG.get_gradients` as it was before PR 34 (a host
    loop over chunks and two scatter-adds a chunk), recorded on this
    seeded case over two calls (the host-drawn stream moves on)."""
    rng = np.random.default_rng(20340)
    sizes = np.array([1, 2, 7, 8, 9, 33, 130, 5, 64, 17, 300, 1, 12])
    n = int(sizes.sum())
    label = rng.integers(0, 5, n).astype(np.float32)
    score = rng.normal(size=n).astype(np.float32)
    recorded = np.load(os.path.join(os.path.dirname(__file__),
                                    "recorded_rank_xendcg.npz"))
    obj = _objective(rank.RankXENDCG, sizes, label, objective_seed=7)
    for call in range(2):
        g, h = obj.get_gradients(jnp.asarray(score * (1 + call)))
        assert np.array_equal(np.asarray(g), recorded[f"rank_xendcg_{call}_g"])
        assert np.array_equal(np.asarray(h), recorded[f"rank_xendcg_{call}_h"])


# ------------------------------------------------- through lgb.train

def _ranking_table(seed, queries=60):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 70, queries)
    sizes[:3] = (1, 2, 140)
    n = int(sizes.sum())
    X = rng.normal(size=(n, 6)).astype(np.float32)
    latent = X[:, 0] - 0.7 * X[:, 1] * X[:, 2] + 0.6 * rng.normal(size=n)
    label = np.clip(np.floor(latent + 1.5), 0, 4).astype(np.int32)
    return X, label, sizes


@pytest.fixture(scope="module")
def trained():
    X, label, sizes = _ranking_table(4)
    params = dict(objective="lambdarank", num_leaves=15, learning_rate=0.2,
                  min_data_in_leaf=5, metric="ndcg", eval_at=[1, 3, 5, 10],
                  verbose=-1)
    ds = lgb.Dataset(X, label=label, group=sizes, params=params)
    bst = lgb.train(params, ds, num_boost_round=1, verbose_eval=False,
                    keep_training_booster=True)
    return bst, X, label, sizes


def test_train_runs_the_reference_gradients_five_iterations(trained):
    """Each iteration's gradients, as the booster kept them, against the
    reference driven by the model's own float32 scores before it."""
    bst, _, label, sizes = trained
    gbdt = bst._gbdt
    for _ in range(5):
        score = np.asarray(gbdt.get_training_score())[0].copy()
        bst.update()
        want = ref.gradients(score, label, sizes)
        _held(np.asarray(gbdt._grad[0]), np.asarray(gbdt._hess[0]), *want,
              sizes)
    (_, name, value, _), *_ = [e for e in bst.eval_train()
                              if e[1] == "ndcg@10"]
    want = ref.ndcg_at_k(np.asarray(gbdt.get_training_score())[0], label,
                         sizes, 10)
    assert value == pytest.approx(want, abs=1e-9)
    assert value > ref.ndcg_at_k(np.zeros(len(label)), label, sizes, 10)


def test_plan_names_the_rank_gradient_program(trained):
    bst, _, _, sizes = trained
    plan = bst._gbdt.execution_plan()
    assert plan["tier"] == "per-tree-fused"
    got = plan["rank_grad"]
    assert got["queries"] == len(sizes)
    m_of = np.maximum(8, 2 ** np.ceil(np.log2(sizes)).astype(int))
    assert got["buckets"] == {int(m): int(np.sum(m_of == m))
                              for m in np.unique(m_of)}
    assert got["pair_slots"] == int(np.sum(m_of.astype(np.int64) ** 2))
    binary = lgb.Booster({"objective": "binary", "verbose": -1},
                         lgb.Dataset(np.zeros((64, 2), np.float32),
                                     label=np.arange(64) % 2))
    assert "rank_grad" not in binary._gbdt.execution_plan()


def test_unsampled_grow_program_routes_rows_as_the_persistent_tier():
    """`_grow_tree` with every row in the bag gives each row the leaf the
    tree's splits send it to: the same tree grown by the persistent tier
    (plain binary) and by the per-tree tier (a custom objective forces
    it), the per-tree tier's `leaf_of_row` against the model's own
    routing of the rows."""
    rng = np.random.default_rng(8)
    X = rng.normal(size=(3000, 5)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = dict(objective="binary", num_leaves=31, min_data_in_leaf=5,
                  learning_rate=0.3, verbose=-1)
    ds = lgb.Dataset(X, label=y, params=params)
    persistent = lgb.train(params, ds, num_boost_round=1, verbose_eval=False,
                           keep_training_booster=True)
    assert persistent._gbdt.execution_plan()["tier"] == "persistent-fused"
    per_tree = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    gbdt = per_tree._gbdt
    p0 = float(np.mean(y))
    grad = (np.full(len(y), p0) - y).astype(np.float32)
    hess = np.full(len(y), p0 * (1 - p0), np.float32)
    ta, leaf_of_row = gbdt._fused.grow_device(
        jnp.asarray(grad), jnp.asarray(hess))
    want = persistent.predict(X, pred_leaf=True).reshape(-1)
    assert int(ta["n_leaves"]) == persistent._gbdt.models[0].num_leaves
    assert np.array_equal(np.asarray(leaf_of_row), want)
