"""The DART reference (reference/dart_numpy.py) on schedules small enough
to follow by hand, and the lines of modes/train_dart.py's check on
mutants: bookkeeping that departs from dart.hpp's in one way each must
fail a line, and the reference's own must pass every one with its
controls refused."""
import numpy as np
import pytest

from benchmarks.harness import loader
from benchmarks.reference import dart_numpy as dart

mode = loader.load_module("modes", "train_dart")
PARAMS = {"learning_rate": 0.1, "drop_rate": 0.1, "max_drop": 50,
          "skip_drop": 0.5, "uniform_drop": False,
          "xgboost_dart_mode": False, "drop_seed": 4}
BANDS = {"score_atol": 2e-4, "score_rtol": 1e-5}


def test_a_round_by_hand():
    """Iteration 0 can drop nothing; a round dropping k scales the new
    tree by lr / (k + 1) and each dropped tree by k / (k + 1)."""
    p = dict(PARAMS, drop_rate=1.0, skip_drop=0.0, max_drop=2)
    s = dart.schedule(4, p)
    assert s["drops"][0] == () and s["birth"][0] == 0.1
    assert s["drops"][1] == (0,) and s["birth"][1] == pytest.approx(0.05)
    assert s["drops"][2] == (0, 1) and s["drops"][3] == (0, 1)
    assert s["factor"][0] == pytest.approx(0.5 * (2 / 3) ** 2)
    assert s["factor"][1] == pytest.approx((2 / 3) ** 2)
    assert s["factor"][2] == s["factor"][3] == 1.0
    np.testing.assert_array_equal(s["score_factor"], s["factor"])
    # the weights lose what the trees lose; sum_weight is their sum
    assert s["sum_weight"] == pytest.approx(s["tree_weight"].sum())


def test_xgboost_mode_and_the_cap():
    """Uniform drops at rate min(1, max_drop / i) cap at max_drop; in
    xgboost mode a round dropping k grows its tree at lr / (lr + k) and
    scales each dropped one by k / (lr + k)."""
    p = dict(PARAMS, drop_rate=1.0, skip_drop=0.0, max_drop=3,
             xgboost_dart_mode=True, uniform_drop=True)
    s = dart.schedule(8, p)
    ks = [len(d) for d in s["drops"]]
    assert ks[:4] == [0, 1, 2, 3] and max(ks) == 3
    for it, k in enumerate(ks):
        assert s["birth"][it] == pytest.approx(0.1 / (0.1 + k) if k else 0.1)
    assert s["factor"][1] == pytest.approx(np.prod(
        [k / (0.1 + k) for it, (k, d) in enumerate(zip(ks, s["drops"]))
         if 1 in d]))
    assert len(s["tree_weight"]) == 0       # uniform: no weights


def test_the_schedule_follows_the_seed_and_skips_half():
    a, b = dart.schedule(400, PARAMS), dart.schedule(400, PARAMS)
    assert a["drops"] == b["drops"]
    c = dart.schedule(400, dict(PARAMS, drop_seed=5))
    assert a["drops"] != c["drops"]
    dropping = [len(d) for d in a["drops"][200:] if d]
    assert 0.35 < len(dropping) / 200 < 0.65
    # about a tenth of the forest when a round drops
    assert 15 < np.mean(dropping) < 30


def test_shrinkage_of_a_model_text():
    text = ("tree\nversion=v3\n\nTree=0\nnum_leaves=2\nshrinkage=0.25\n\n"
            "Tree=1\nnum_leaves=1\nshrinkage=1\n\nend of trees\n")
    np.testing.assert_array_equal(dart.parse_shrinkage(text), [0.25, 1.0])


# ------------------------------------------------------------ the mutants

def _program(how, iters=60, seed=0):
    """What a program with bookkeeping `how` would show the check: its
    drops, weights, model text shrinkage, tree 0, and its training score
    on rows where each tree's output as grown is a random draw."""
    rng = np.random.default_rng(seed)
    p = dict(PARAMS, drop_rate=0.3)
    run = dart.schedule(iters, p, mutant=how)
    grown = rng.normal(scale=0.05, size=(iters, 500))
    bias = -0.3
    grown[0] += bias
    birth0 = np.array([grown[0, 0], grown[0, 1]])
    # tree 0 now: its raw part scaled, its bias by what the program did
    tree0 = (birth0 - bias) * run["factor"][0] + bias * run["bias_factor"]
    model_out = run["factor"][:, None] * grown
    model_out[0] = (grown[0] - bias) * run["factor"][0] \
        + bias * run["bias_factor"]
    score = np.sum(run["score_factor"][:, None] * grown, axis=0)
    if how == "bias_unscaled":
        score = score - bias * run["score_factor"][0] + bias * run[
            "bias_factor"]
    seen = {"drops": dict(enumerate(run["drops"])),
            "tree_weight": run["tree_weight"],
            "sum_weight": run["sum_weight"],
            "shrinkage": dart.model_shrinkage(run, True), "tree0": tree0}
    return p, seen, birth0, score, model_out, run


def _lines(how):
    p, seen, birth0, score, model_out, run = _program(how)
    want = dart.schedule(len(run["drops"]), p)
    lines = mode.schedule_lines(seen, want, birth0, True, 1e-12)
    # the score line reads the program's model at its own factors
    factor_model = seen["shrinkage"] / np.where(
        np.arange(len(want["birth"])) == 0, 1.0, want["birth"])
    ratios = {"reference": want["score_factor"] / factor_model}
    for c in ("no_add_back", "one_over_k_plus_1"):
        ctl = dart.schedule(len(want["drops"]), p, mutant=c,
                            drops=want["drops"])
        ratios[c] = ctl["score_factor"] / factor_model
    lines += mode.score_lines(score, mode.walk(iter(model_out), ratios),
                              BANDS)
    return {name: (ok, detail) for name, ok, detail in lines}


def test_the_reference_passes_every_line():
    lines = _lines(None)
    assert all(ok for ok, _ in lines.values()), lines


@pytest.mark.parametrize("how", dart.MUTANTS)
def test_a_mutant_fails_a_line(how):
    lines = _lines(how)
    failed = [name for name, (ok, _) in lines.items() if not ok]
    assert failed, lines


def test_the_held_out_walker_reads_thresholds_as_pathforest_does():
    """`heldout_raw`'s walker at thresholds rounded to float32 agrees with
    PathForest on rows placed exactly at a threshold's float32 rounding,
    where the walker at the text's float64 thresholds takes the other
    branch: the rows the line counts apart are of this kind alone."""
    import lightgbm_tpu as lgb
    from benchmarks.reference import gbdt_numpy as ref
    rng = np.random.default_rng(1)
    X = rng.standard_normal((4000, 6)).astype(np.float32)
    y = (X[:, 0] + rng.standard_normal(4000) > 0).astype(np.float32)
    bst = lgb.train({"objective": "binary", "boosting": "dart",
                     "num_leaves": 15, "verbose": -1, "skip_drop": 0.0},
                    lgb.Dataset(X, label=y), num_boost_round=5)
    trees = ref.parse_model(bst.model_to_string())
    H = rng.standard_normal((1000, 6)).astype(np.float32)
    placed = 0
    for tr in trees:
        for k in range(tr.num_leaves - 1):
            t32 = np.float32(tr.threshold[k])
            if float(t32) != tr.threshold[k] and placed < len(H):
                H[placed, tr.split_feature[k]] = t32
                placed += 1
    raw = bst.predict(H, raw_score=True)
    walk = lambda ts: sum(t.leaf_value[ref.leaf_of(t, H)] for t in ts)
    at64 = np.abs(raw - walk(trees))
    at32 = np.abs(raw - walk([mode._f32_thresholds(t) for t in trees]))
    assert placed > 20 and np.sum(at64 > 1e-4) > 0
    assert np.max(at32) < 1e-5
