"""Ranking training traffic: `lgb.Dataset(X, label, group=...)` ->
`lgb.train` -> `Booster.update()` under a query-grouped objective
(LambdaRank).

Set-up, window and traced sub-window are `modes/train.py`'s (the same
`bench:update` / `bench:sync` spans and `traced_trees` artifact, so the
same readers work); the data set carries query sizes, and the count of
label-differing pairs goes among the artifacts for the per-pair reader.

A query's gradient depends on every other document of that query, so the
model alone does not tell what a tree should contain. The check therefore
reads the gradients the program itself used, twice, and holds them to
`reference/lambdarank_numpy.py` (float64, one `[M, M]` matrix of pairs a
query): those of iteration 0, kept at set-up (every score equal, so
`best == worst` and every rank is a tie broken by row order), and those of
ONE MORE iteration run after the window under the check's own eyes, from
the float32 scores read just before it. Each is compared on a seeded
sample of whole queries plus the largest ones and every one-document query
of the sample, and the same comparison is shown to refuse two controls:
the reference with its pair terms rounded to bfloat16, and the reference
at a truncation level one lower (which moves only `inv`). Tree 0 is held
to the reference's gradients over EVERY row.

What this file reads of the program beyond its public API, all of it
here: `bst._gbdt.device_score_state()` (to block on, and the scores the
watched iteration starts from), `bst._gbdt.execution_plan()`,
`bst._gbdt._grad` / `._hess` (what the gradient program returned for the
iteration just run), `predict_path`, and `ds._handle.bins` (the binned
matrix, for the reference's root histograms).
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.harness import loader, work_rank
from benchmarks.harness.clock import now
from benchmarks.reference import gbdt_numpy as ref
from benchmarks.reference import lambdarank_numpy as rank

_train = loader.load_module("modes", "train")
window = _train.window

SAMPLE_QUERIES = 2_048      # whole queries the gradient checks compare
LARGEST = 20                # and the largest queries besides
CONTROL_QUERIES = 256       # of them, what the two controls recompute
WALK_QUERIES = 4_096        # whole queries the reference walker scores
NDCG_AT = 10
HELDOUT_ITERS = 20          # held-out NDCG is read at this many trees


@dataclasses.dataclass
class State:
    bst: object
    ds: object
    X: np.ndarray               # train rows, then the held-out rows
    y: np.ndarray               # int32 relevance labels, likewise
    group: np.ndarray           # training query sizes, in row order
    heldout_group: np.ndarray
    rows: int
    first: dict                 # iteration 0's gradients, as used
    artifacts: dict


class NoRankPlan(RuntimeError):
    """The program cannot run this mode's cells inside a run's time."""


def _needs_the_rank_program(lgb, params: dict) -> None:
    """Asked of a 64-row booster, before anything is generated: does the
    program's plan name its ranking gradient program? One that does not
    predates it (PR 34): its gradient is a host loop over ~700 chunks
    with two row-sized scatter-adds each, seconds an iteration at this
    size (PERF.md section 6), so it fails here, at once and with a
    reason, instead of running out a run's time."""
    X = np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32)
    quiet = dict(params, verbose=-1)
    ds = lgb.Dataset(X, label=np.arange(64) % 3, group=[16] * 4, params=quiet)
    plan = lgb.Booster(quiet, ds)._gbdt.execution_plan()
    if "rank_grad" not in plan:
        raise NoRankPlan(
            "this program's execution_plan() does not name a ranking "
            f"gradient program ({plan}): its gradients are a host loop a "
            "run of this cell would not end on")


def _used_gradients(bst) -> dict:
    """The gradients and hessians the last iteration was grown on."""
    gbdt = bst._gbdt
    return {"grad": np.asarray(gbdt._grad[0], np.float64),
            "hess": np.asarray(gbdt._hess[0], np.float64)}


def setup(ctx) -> State:
    import lightgbm_tpu as lgb
    shape, params = ctx.config["shape"], dict(ctx.config["params"])
    _needs_the_rank_program(lgb, params)
    rows = int(shape["rows"])
    gen = ctx.config["generator"]
    with ctx.stage("generate"):
        X, y, group, held = ctx.load("generators", gen["name"]).make(
            rows, int(shape["queries"]), int(shape["heldout_queries"]),
            seed=ctx.seed, cols=int(shape["cols"]), **gen["args"])
    with ctx.stage("construct"):
        ds = lgb.Dataset(X[:rows], label=y[:rows], group=group,
                         params=dict(params)).construct()
    with ctx.stage("first_call"):
        bst = lgb.train(dict(params), ds, num_boost_round=1,
                        verbose_eval=False, keep_training_booster=True)
        _train._block(bst)
    first = _used_gradients(bst)
    with ctx.stage("warmup"):
        for _ in range(int(ctx.traffic["warmup_iters"])):
            bst.update()
        _train._block(bst)
    ctx.say(f"execution plan: {bst._gbdt.execution_plan()}")
    pairs = work_rank.label_pairs(group, y[:rows])
    ctx.say(f"{rows} rows in {len(group)} queries of 1..{int(group.max())} "
            f"documents; {pairs} pairs with different labels an iteration")
    return State(bst, ds, X, y, group, held, rows, first,
                 {"rank_pairs": pairs, "rank_queries": len(group)})


def traced(ctx, st: State) -> dict:
    got = _train.traced(ctx, st)
    st.artifacts["traced_syncs"] = got["counters"]["blocking_syncs"]
    return got


# ------------------------------------------------------------- the check

def _rank_params(params: dict) -> dict:
    return dict(sigmoid=float(params["sigmoid"]),
                truncation=int(params["lambdarank_truncation_level"]),
                norm=bool(params["lambdarank_norm"]))


def _sample_queries(st: State, seed: int) -> np.ndarray:
    """A seeded sample of whole queries, the largest ones, and with them
    whatever one-document queries the sample drew."""
    nq = len(st.group)
    take = np.random.default_rng(seed).choice(
        nq, min(SAMPLE_QUERIES, nq), replace=False)
    largest = np.argsort(-st.group, kind="stable")[:LARGEST]
    return np.unique(np.concatenate([take, largest]))


def _worst(got: dict, want_g, want_h, bounds, queries, bands) -> float:
    """The comparison: every document's gradient and hessian against the
    reference's, the worst in units of its tolerance, which is
    `grad_rtol` of the largest |reference| of the document's query plus
    `grad_atol`."""
    worst = 0.0
    for q in queries:
        lo, hi = bounds[q], bounds[q + 1]
        for have, want in ((got["grad"], want_g), (got["hess"], want_h)):
            tol = bands["grad_rtol"] * np.abs(want[lo:hi]).max() \
                + bands["grad_atol"]
            worst = max(worst, float(np.abs(have[lo:hi] - want[lo:hi]).max()
                                     / tol))
    return worst


def _gradients(st: State, name: str, score: np.ndarray, got: dict,
               queries: np.ndarray, bands: dict, params: dict) -> list:
    """One gradient check: the program's gradients of one iteration
    against the reference from the scores it started from, and the two
    controls through the same comparison."""
    y, kw = st.y[:st.rows], _rank_params(params)
    bounds = np.concatenate([[0], np.cumsum(st.group)])
    want = rank.gradients(score, y, st.group, queries, **kw)
    worst = _worst(got, *want, bounds, queries, bands)
    few = queries[np.argsort(-st.group[queries], kind="stable")][
        np.linspace(0, len(queries) - 1,
                    min(CONTROL_QUERIES, len(queries))).astype(np.int64)]
    coarse = _worst(got, *rank.gradients(score, y, st.group, few,
                                         terms=rank.round_bf16, **kw),
                    bounds, few, bands)
    other_t = _worst(got, *rank.gradients(
        score, y, st.group, few, **dict(kw, truncation=kw["truncation"] - 1)),
        bounds, few, bands)
    ones = int(np.sum(st.group[queries] == 1))
    return [
        (f"gradients_{name}", worst <= 1.0,
         f"{len(queries)} whole queries ({int(st.group[queries].sum())} "
         f"documents; the {LARGEST} largest, up to "
         f"{int(st.group[queries].max())} documents, and {ones} of one "
         f"document among them): worst |x - reference| is {worst:.3g} of "
         f"its tolerance ({bands['grad_rtol']:g} of the query's largest "
         f"|reference| + {bands['grad_atol']:g})"),
        (f"gradients_{name}_controls", coarse > 1.0 and other_t > 1.0,
         f"the same comparison on {len(few)} of them against two controls: "
         f"the reference with pair terms rounded to bfloat16 reads "
         f"{coarse:.3g} tolerances, the reference at truncation level "
         f"{kw['truncation'] - 1} reads {other_t:.3g} (held: both above 1, "
         "refused)")]


def _tree0(st: State, tree: ref.Tree, bands: dict, params: dict) -> list:
    """Tree 0 against the reference on every training row: leaf counts
    exactly, leaf values from the float64 Newton step over the
    REFERENCE's gradients (all scores 0), each within what bfloat16's
    rounding of every term can move it, and the root's gain against the
    best gain over all columns' own histograms."""
    X, y, n = st.X[:st.rows], st.y[:st.rows], st.rows
    L = tree.num_leaves
    kw = _rank_params(params)
    bounds = np.concatenate([[0], np.cumsum(st.group)])
    parts = _train._in_blocks(
        lambda lo, hi: rank.gradients(
            np.zeros(bounds[hi] - bounds[lo]), y[bounds[lo]:bounds[hi]],
            st.group[lo:hi], **kw), len(st.group))
    g = np.concatenate([p[0] for p in parts])
    h = np.concatenate([p[1] for p in parts])

    leaf = np.concatenate(_train._in_blocks(
        lambda lo, hi: ref.leaf_of(tree, X[lo:hi]), n))
    rows_in = np.bincount(leaf, None, L)
    sum_g, sum_h = np.bincount(leaf, g, L), np.bincount(leaf, h, L)
    abs_g = np.bincount(leaf, np.abs(g), L)
    counts_ok = np.array_equal(rows_in.astype(np.int64), tree.leaf_count)
    lr, l2 = float(params["learning_rate"]), float(params.get("lambda_l2", 0))
    value = lr * ref.leaf_output(sum_g, sum_h, l2)
    err = np.abs(tree.leaf_value - value)
    # each gradient and hessian enters a histogram rounded to bfloat16:
    # off by up to 2^-9 of itself, whatever its sign
    tol = bands["leaf_value_atol"] + lr * bands["leaf_value_bf16_ulps"] \
        * 2.0 ** -9 * (abs_g + np.abs(sum_g)) / (sum_h + l2)

    bins = st.ds._handle.bins
    limits = dict(min_data_in_leaf=int(params["min_data_in_leaf"]),
                  min_sum_hessian=float(params["min_sum_hessian_in_leaf"]),
                  lambda_l2=l2)
    with ThreadPoolExecutor() as pool:
        found = list(pool.map(
            lambda f: ref.best_threshold(
                ref.histogram(bins[:, f], g, h, int(bins[:, f].max()) + 1),
                **limits), range(bins.shape[1])))
    best_gain, best_f = max((gain, f) for f, (gain, _) in enumerate(found))
    gain_err = abs(tree.split_gain[0] - best_gain) / best_gain
    return [
        ("tree0_leaf_counts", counts_ok,
         f"{L} leaves, {int(np.sum(rows_in != tree.leaf_count))} counts "
         "differ from numpy's routing of every training row"),
        ("tree0_leaf_values", bool(np.all(err <= tol)),
         f"against the Newton step over the reference's gradients of all "
         f"{n} rows: max |value - reference| {err.max():.3g} (worst in "
         f"units of its tolerance {np.max(err / tol):.3g})"),
        ("tree0_root_gain", gain_err <= bands["root_gain_rtol"],
         f"model {tree.split_gain[0]:.6g} on feature "
         f"{int(tree.split_feature[0])}, reference best {best_gain:.6g} on "
         f"feature {best_f} of {bins.shape[1]}: off by {gain_err:.3g} of it "
         f"(allowed {bands['root_gain_rtol']})")]


def _quality(ctx, st: State, trees: list, bands: dict) -> list:
    """The training metric against the reference's, and the held-out
    NDCG at 20 trees."""
    y, bounds = st.y[:st.rows], np.concatenate([[0], np.cumsum(st.group)])
    name = f"ndcg@{NDCG_AT}"
    said = {e[1]: e[2] for e in st.bst.eval_train()}[name]
    score = np.asarray(st.bst._gbdt.get_training_score(), np.float64)[0]
    everywhere = rank.ndcg_at_k(score, y, st.group, NDCG_AT)
    # the same queries by the reference walker over the model's text
    nq = len(st.group)
    take = np.sort(np.random.default_rng(ctx.seed + 1).choice(
        nq, min(WALK_QUERIES, nq), replace=False))
    rows = np.concatenate([np.arange(bounds[q], bounds[q + 1]) for q in take])
    Xs = st.X[rows]
    raw = np.concatenate(_train._in_blocks(
        lambda lo, hi: ref.predict_raw(trees, Xs[lo:hi]), len(rows)))
    walked = rank.ndcg_at_k(raw, y[rows], st.group[take], NDCG_AT)
    own = rank.ndcg_at_k(score[rows], y[rows], st.group[take], NDCG_AT)
    untrained = rank.ndcg_at_k(np.zeros(st.rows), y, st.group, NDCG_AT)
    out = [("train_ndcg",
            abs(said - everywhere) <= bands["ndcg_metric_atol"]
            and abs(own - walked) <= bands["ndcg_atol"]
            and said > untrained,
            f"eval_train {name} {said:.6f} over {len(trees)} trees; the "
            f"reference's NDCG of the program's scores over all {nq} "
            f"queries {everywhere:.6f}; on {len(take)} sampled queries the "
            f"program's scores read {own:.6f} and the reference walker's "
            f"{walked:.6f} (allowed {bands['ndcg_atol']} apart); the "
            f"untrained order reads {untrained:.6f}")]

    held_rows = st.X[st.rows:]
    heldout = st.bst.predict(held_rows, num_iteration=HELDOUT_ITERS)
    got = rank.ndcg_at_k(heldout, st.y[st.rows:], st.heldout_group, NDCG_AT)
    floor = rank.ndcg_at_k(np.zeros(len(heldout)), st.y[st.rows:],
                           st.heldout_group, NDCG_AT)
    lo = max(floor, bands["ndcg20"] - bands["ndcg20_band"])
    hi = bands["ndcg20"] + bands["ndcg20_band"]
    out.append(("heldout_ndcg", lo < got <= hi,
                f"{name} {got:.5f} at {HELDOUT_ITERS} trees on "
                f"{len(st.heldout_group)} held-out queries ({len(heldout)} "
                f"rows; want {lo:.5f}..{hi:.5f}, the untrained order reads "
                f"{floor:.5f}); predict path {st.bst._gbdt.predict_path}"))
    return out


def check(ctx, st: State) -> list:
    import jax
    bands, params = ctx.config["correct"], ctx.config["params"]
    plan = st.bst._gbdt.execution_plan()
    on_tpu = jax.default_backend() == "tpu"
    kernels_ok = not on_tpu or ("pallas" in str(plan["hist"])
                                and "pallas" in str(plan["partition"]))
    ranked = plan.get("rank_grad") or {}
    out = [("per_tree_tier",
            plan["tier"] == "per-tree-fused" and kernels_ok
            and (not on_tpu or plan["device_count"] == 1)
            and ranked.get("queries") == len(st.group),
            f"tier {plan['tier']}, hist {plan['hist']}, partition "
            f"{plan['partition']}, learner {plan['learner']}, "
            f"{plan['device_count']} device(s), rank_grad {ranked} "
            f"(the run's queries: {len(st.group)})")]
    if st.artifacts.get("traced_syncs") is not None:
        out.append(("no_blocking_sync", st.artifacts["traced_syncs"] == 0,
                    f"{st.artifacts['traced_syncs']} blocking syncs in the "
                    "traced iterations"))

    t0 = now()
    parts = {}

    def timed(name, fn, *args):
        t = now()
        got = fn(*args)
        parts[name] = now() - t
        return got

    # one more iteration under the check's eyes: scores in, gradients out
    score = np.asarray(jax.block_until_ready(
        st.bst._gbdt.device_score_state()), np.float32)[0].copy()
    st.bst.update()
    _train._block(st.bst)
    watched = _used_gradients(st.bst)
    queries = _sample_queries(st, ctx.seed)
    out += timed("gradients at iteration 0", _gradients, st, "iter0",
                 np.zeros(st.rows, np.float32), st.first, queries, bands,
                 params)
    out += timed("gradients after the window", _gradients, st, "trained",
                 score, watched, queries, bands, params)
    trees = st.artifacts["trees"] = ref.parse_model(st.bst.model_to_string())
    out += timed("tree 0", _tree0, st, trees[0], bands, params)
    out += timed("NDCG", _quality, ctx, st, trees, bands)
    ctx.say(f"the check took {now() - t0:.1f} s: " + ", ".join(
        f"{k} {v:.1f}" for k, v in parts.items()))
    return out
