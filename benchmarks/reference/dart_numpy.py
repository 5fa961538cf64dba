"""The plain reference of DART's bookkeeping, in numpy and float64.

No JAX and nothing of the package under test. DART (Rashmi and
Gilad-Bachrach, AISTATS 2015; LightGBM's `src/boosting/dart.hpp`,
DroppingTrees and Normalize) at iteration i, with i trees grown:

- draw: with probability 1 - skip_drop, each earlier iteration is
  dropped with probability drop_rate (x its weight / the mean weight
  unless uniform_drop; the rate capped so that about max_drop drop), at
  most max_drop of them; k = how many;
- the new tree is grown against the score without the dropped trees, at
  shrinkage lr / (k + 1) (xgboost mode: lr / (lr + k), or lr when k = 0);
- normalize: each dropped tree's output is scaled by f = k / (k + 1)
  (xgboost mode: k / (lr + k)) and the score holds it at that scale; its
  weight goes by the same factor, and sum_weight by what it lost.

The draws follow the package's own order from
`np.random.RandomState(drop_seed)` (one draw for the skip, then one per
earlier iteration, stopping at max_drop), which is not LightGBM's own
generator: the law is the same, the draws are not. `schedule` needs no
data: the drops, the scales and the weights follow from the parameters
alone. `parse_shrinkage` reads each tree's `shrinkage=` from a model
text, which `gbdt_numpy.parse_model` leaves out.

`schedule(..., mutant=...)` runs the bookkeeping with one departure, for
the benchmark's tests and for the controls of `correct`.
"""
from __future__ import annotations

import numpy as np

MUTANTS = ("no_add_back", "one_over_k_plus_1", "xgboost_factors_swapped",
           "drop_set_off_by_one", "skip_drop_inverted", "bias_unscaled")


def _factors(k: int, lr: float, xgboost: bool, mutant):
    """(new tree's shrinkage, normalize factor f) of a round dropping k."""
    if mutant == "xgboost_factors_swapped":
        xgboost = not xgboost
    if xgboost:
        shrink = lr if k == 0 else lr / (lr + k)
        f = k / (lr + k)
    else:
        shrink = lr / (1.0 + k)
        f = k / (k + 1.0)
    if mutant == "one_over_k_plus_1":
        f = 1.0 / (k + 1.0)
    return shrink, f


def schedule(iters: int, params: dict, *, mutant=None, drops=None) -> dict:
    """DART's bookkeeping over `iters` iterations of one tree each.

    Returns, all float64: `drops` (per iteration the tuple of dropped
    iterations), `birth` (each tree's shrinkage when grown), `factor`
    (the product of the normalize factors it went through: its output in
    the model over its output when grown), `score_factor` (the same for
    its share of the training score; equal to `factor` unless a mutant
    loses the add-back), `bias_factor` (what became of a bias tree 0 was
    grown with), `tree_weight`, `sum_weight`. `drops` given: those sets
    are used in place of the draws (the controls keep the reference's)."""
    lr = float(params["learning_rate"])
    rate0 = float(params.get("drop_rate", 0.1))
    max_drop = int(params.get("max_drop", 50))
    skip = float(params.get("skip_drop", 0.5))
    uniform = bool(params.get("uniform_drop", False))
    xgb = bool(params.get("xgboost_dart_mode", False))
    rng = np.random.RandomState(int(params.get("drop_seed", 4)))
    out_drops, birth, factor, score_factor = [], [], [], []
    bias_factor = 1.0
    weight, sum_weight = [], 0.0
    for it in range(iters):
        dropped = []
        u = rng.rand()
        skipped = u < skip if mutant == "skip_drop_inverted" else u >= skip
        if skipped:
            rate = rate0
            if not uniform:
                if weight:
                    inv_avg = len(weight) / sum_weight
                    if max_drop > 0:
                        rate = min(rate, max_drop * inv_avg / sum_weight)
                    for i in range(it):
                        if rng.rand() < rate * weight[i] * inv_avg:
                            dropped.append(i)
                            if len(dropped) >= max_drop:
                                break
            else:
                if max_drop > 0 and it > 0:
                    rate = min(rate, max_drop / float(it))
                for i in range(it):
                    if rng.rand() < rate:
                        dropped.append(i)
                        if len(dropped) >= max_drop:
                            break
        if drops is not None:
            dropped = list(drops[it])
        out_drops.append(tuple(dropped))
        k = len(dropped)
        shrink, f = _factors(k, lr, xgb, mutant)
        birth.append(shrink)
        factor.append(1.0)
        score_factor.append(1.0)
        scaled = dropped
        if mutant == "drop_set_off_by_one":
            scaled = [min(i + 1, it - 1) for i in dropped]
        for i in scaled:
            before = factor[i]
            factor[i] *= f
            if mutant == "no_add_back":
                score_factor[i] -= before
            else:
                score_factor[i] = factor[i]
            if i == 0 and mutant != "bias_unscaled":
                bias_factor *= f
        if not uniform:
            for i in dropped:
                if not xgb:
                    sum_weight -= weight[i] / (k + 1.0)
                    weight[i] *= k / (k + 1.0)
                else:
                    sum_weight -= weight[i] / (k + lr)
                    weight[i] *= k / (k + lr)
            weight.append(shrink)
            sum_weight += shrink
    return {"drops": out_drops, "birth": np.asarray(birth),
            "factor": np.asarray(factor),
            "score_factor": np.asarray(score_factor),
            "bias_factor": bias_factor, "tree_weight": np.asarray(weight),
            "sum_weight": sum_weight}


def model_shrinkage(ref: dict, with_bias: bool) -> np.ndarray:
    """Each tree's `shrinkage=` in the model text the schedule implies:
    its birth shrinkage x its factor; tree 0's factor alone where it was
    grown with a bias (Tree::AddBias sets the field to 1)."""
    out = ref["birth"] * ref["factor"]
    if with_bias and len(out):
        out[0] = ref["factor"][0]
    return out


def parse_shrinkage(text: str) -> np.ndarray:
    """The `shrinkage=` of every tree of a model text, in order."""
    out = []
    for chunk in text.split("\nTree=")[1:]:
        for line in chunk.splitlines():
            if line.startswith("shrinkage="):
                out.append(float(line.split("=", 1)[1]))
                break
        else:
            out.append(1.0)
    return np.asarray(out, np.float64)
