"""Sampled training traffic on sparse input: `lgb.Dataset(csr)` ->
`lgb.train` -> `Booster.update()`, with row sampling by gradient (GOSS).

Set-up, window and traced sub-window are `modes/train.py`'s (the same
`bench:update` / `bench:sync` spans and `traced_trees` artifact, so the
same readers work); the input is a scipy CSR matrix, and the warm-up is
long enough that sampling has started before the window opens, so every
timed iteration grows its tree on a sample.

The check cannot tell what a sampled tree should contain from the model
alone, so after the window (and the traced sub-window) it runs ONE MORE
sampled iteration under its own eyes: the scores before it, then the
permutation, the bag size, the gradients as the sampler left them and the
new tree after it, and holds them against `reference/goss_numpy.py`.

What this file reads of the program beyond its public API, all of it
here: `bst._gbdt.device_score_state()` (to block on, and the scores the
watched iteration starts from), `bst._gbdt.execution_plan()`,
`bst._gbdt._perm` / `.bag_data_cnt` / `._grad` (the bag of the watched
iteration and which of its rows came back weighted), `predict_path`, and
of `ds._handle`: `bins` (a seeded sample of rows, in `_bundles` only),
`bundles` (`group_of`, `offset_of`, `nslots_of`, `skip_of`, `groups`),
`bin_mappers[i].bin_upper_bound`, `inner_feature_index` (the bundled codes
against the reference's own bins; which of two clashing indicators a
bundle keeps is the reference's rule on `groups`, not read from a code).
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.harness import loader
from benchmarks.harness.clock import now
from benchmarks.reference import gbdt_numpy as ref
from benchmarks.reference import goss_numpy as goss

_train = loader.load_module("modes", "train")
window = _train.window
traced = _train.traced

SAMPLE_ROWS = 262_144       # training rows the bundle check and the walker see
AUC_ITERS = 20              # held-out AUC is read at this many trees
BLOCK = 16_384              # rows a thread takes at a time


@dataclasses.dataclass
class State:
    bst: object
    ds: object
    X: object                   # scipy CSR: train rows, then the held-out rows
    Xtrain: object              # X[:rows], sliced once
    y: np.ndarray
    rows: int
    artifacts: dict


class NoSampledPath(RuntimeError):
    """The program cannot run this mode's cells inside a run's time."""


def _needs_the_sampled_path(lgb, params: dict) -> None:
    """Asked of a 512-row booster, before anything is generated: does the
    program's plan name its row sampling? One that does not predates the
    per-tree tier's sampled path (PR 27): it walks every row of every tree
    by gathers over a row-major table, 43.7 s an iteration at 11M rows
    (PERF.md section 6), so neither set-up nor a window would end inside a
    run's time. It fails here, at once and with a reason, instead."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 4)).astype(np.float32)
    ds = lgb.Dataset(X, label=(X[:, 0] > 0).astype(np.float32),
                     params=dict(params, verbose=-1))
    plan = lgb.Booster(dict(params, verbose=-1), ds)._gbdt.execution_plan()
    if "sampling" not in plan:
        raise NoSampledPath(
            "this program's execution_plan() does not name its row "
            f"sampling ({plan}): it has no sampled per-tree path that a "
            "run of this cell could end on")


def setup(ctx) -> State:
    import lightgbm_tpu as lgb
    shape, params = ctx.config["shape"], dict(ctx.config["params"])
    _needs_the_sampled_path(lgb, params)
    rows = int(shape["rows"])
    gen = ctx.config["generator"]
    with ctx.stage("generate"):
        X, y = ctx.load("generators", gen["name"]).make(
            rows + int(shape["heldout_rows"]), seed=ctx.seed,
            cols=int(shape["cols"]), **gen["args"])
    Xtrain = X[:rows]
    with ctx.stage("construct"):
        ds = lgb.Dataset(Xtrain, label=y[:rows],
                         params=dict(params)).construct()
    with ctx.stage("first_call"):
        bst = lgb.train(dict(params), ds, num_boost_round=1,
                        verbose_eval=False, keep_training_booster=True)
        _train._block(bst)
    with ctx.stage("warmup"):
        for _ in range(int(ctx.traffic["warmup_iters"])):
            bst.update()
        _train._block(bst)
    ctx.say(f"execution plan: {bst._gbdt.execution_plan()}")
    return State(bst, ds, X, Xtrain, y, rows,
                 {"bundle_groups": int(ds._handle.bins.shape[1])})


# ------------------------------------------------------------- the check

def _in_blocks(fn, n: int, block: int = BLOCK) -> list:
    """fn(lo, hi) over [0, n) in blocks, in threads, in order."""
    edges = list(range(0, n, block)) + [n]
    with ThreadPoolExecutor() as pool:
        return list(pool.map(lambda i: fn(edges[i], edges[i + 1]),
                             range(len(edges) - 1)))


def _leaf_of(tree: ref.Tree, csr) -> np.ndarray:
    return np.concatenate(_in_blocks(
        lambda lo, hi: goss.leaf_of_csr(tree, csr, lo, hi), csr.shape[0]))


def _upper_bounds(st: State) -> dict:
    """{original column: the bin upper bounds the dataset gave it}; a
    column the dataset dropped as constant is not in it."""
    h = st.ds._handle
    return {int(col): np.asarray(h.bin_mappers[i].bin_upper_bound, np.float64)
            for col, i in h.inner_feature_index.items()}


def _bundles(st: State, seen_rows, take: np.ndarray, ub: dict,
             bands: dict) -> list:
    """EFB against the raw columns: few groups, and on a sample of rows
    every column's bin, decoded from its group's code, is the bin the
    reference gives the value the row holds in `seen_rows` (the raw rows,
    but for the indicators `_as_bundled` says a bundle loses), with no
    more rows changed than the bundler's conflict budget allows. A
    decoded bin that differs is a fault, of the bundles or of the
    reference's rule for what they lose."""
    h = st.ds._handle
    codes = np.asarray(h.bins[take]).astype(np.int64)
    bt = h.bundles
    groups = codes.shape[1]
    Xs = seen_rows[take]

    def differ(lo, hi):
        dense = Xs[lo:hi].toarray()
        wrong = 0
        for col, i in h.inner_feature_index.items():
            want = goss.column_bins(dense[:, col], ub[col])
            if bt is None:
                got = codes[lo:hi, i]
            else:
                # a bundle's code holds one member's non-default bin, or 0
                rel = codes[lo:hi, bt.group_of[i]] - bt.offset_of[i]
                inband = (rel >= 0) & (rel < bt.nslots_of[i])
                default = bt.skip_of[i]
                got = np.where(inband, rel + (rel >= default), default)
            wrong += int(np.sum(got != want))
        return wrong

    wrong = int(np.sum(_in_blocks(differ, len(take))))
    lost = int(np.count_nonzero(st.Xtrain[take].data)
               - np.count_nonzero(Xs.data))
    allowed = int(bands["bundle_conflict_rate"] * len(take) * groups)
    cols = st.X.shape[1]
    return [("bundles",
             groups <= bands["max_groups"] and wrong == 0 and lost <= allowed,
             f"{cols} columns ({len(ub)} not constant) in {groups} groups "
             f"(allowed {bands['max_groups']}); on {len(take)} sampled rows "
             f"{wrong} decoded bins differ from the reference's bin of the "
             f"value the row holds once bundled, and {lost} indicators read "
             f"as their default where a later member of the bundle took the "
             f"row (the bundler's conflict budget allows {allowed})")]


def _as_bundled(st: State, ub: dict) -> tuple:
    """(training rows as the bundles present them, rows changed): a scipy
    CSR copy in which an indicator that lost its row to another member of
    its bundle stores 0.0. The bundler may put two columns into one bundle
    that a few rows set together (its conflict budget); such a row's code
    holds one of the two, and the trainer routes the row by what the code
    holds. Which of the two keeps the row is the reference's rule
    (`goss.bundle_keeps`) on the bundles' member lists and the raw
    values; the binned codes are not read here (`_bundles` holds them to
    the outcome on a sample, the leaf counts on every row)."""
    h = st.ds._handle
    bt = h.bundles
    X = st.Xtrain
    if bt is None:
        return X, 0
    inner = np.full(X.shape[1], -1, np.int64)
    for col, i in h.inner_feature_index.items():
        inner[col] = i
    # per used feature: its bundle (-1 for a group of one) and its place
    bundle_of = np.full(len(bt.group_of), -1, np.int64)
    rank_of = np.zeros(len(bt.group_of), np.int64)
    for g, members in enumerate(bt.groups):
        if len(members) > 1:
            bundle_of[list(members)] = g
            rank_of[list(members)] = np.arange(len(members))
    default_of = np.asarray(bt.skip_of, np.int64)
    ptr = X.indptr

    def lost(lo, hi):
        """Positions in X.data of stored entries that lose their row."""
        cols, _ = goss._padded(X, lo, hi)
        feat = np.where(cols >= 0, inner[cols], -1)
        # dropped columns, padding and groups of one: a bundle each
        grp = np.where((feat >= 0) & (bundle_of[feat] >= 0), bundle_of[feat],
                       len(bt.groups) + np.arange(cols.shape[1]))
        srt = np.sort(grp, axis=1)
        rows = lo + np.flatnonzero(np.any(srt[:, 1:] == srt[:, :-1], axis=1))
        out = []
        for r in rows:              # a few rows in ten thousand
            at = np.arange(ptr[r], ptr[r + 1])
            f = inner[X.indices[at]]
            at, f = at[f >= 0], f[f >= 0]
            claims = np.array([
                goss.column_bins(X.data[p:p + 1], ub[int(X.indices[p])])[0]
                for p in at]) != default_of[f]
            out.append(at[~goss.bundle_keeps(bundle_of[f], rank_of[f],
                                             claims)])
        return np.concatenate(out) if out else np.zeros(0, np.int64)

    gone = np.concatenate(_in_blocks(lost, st.rows, block=1 << 18))
    seen = X.copy()
    seen.data[gone] = 0.0
    return seen, int(len(np.unique(np.searchsorted(ptr, gone, "right"))))


def _tree0(st: State, seen_rows, tree: ref.Tree, ub: dict, bands: dict,
           params: dict) -> list:
    """Tree 0 (grown on every row) against the reference: leaf counts
    exactly, leaf values from float64 sums, and the root's gain against
    the best over all the raw columns' own histograms."""
    X, y, n = st.Xtrain, st.y[:st.rows], st.rows
    L = tree.num_leaves
    init = ref.binary_init_score(y)
    p0 = float(ref.sigmoid(init))
    leaf = _leaf_of(tree, seen_rows)
    rows_in = np.bincount(leaf, None, L)
    pos_in = np.bincount(leaf, y, L)
    counts_ok = np.array_equal(rows_in.astype(np.int64), tree.leaf_count)
    value = init + float(params["learning_rate"]) * ref.leaf_output(
        rows_in * p0 - pos_in, rows_in * p0 * (1.0 - p0),
        float(params.get("lambda_l2", 0.0)))
    err = np.abs(tree.leaf_value - value)
    tol = bands["leaf_value_atol"] + bands["leaf_value_rtol"] * np.abs(value)

    g, h = ref.binary_grad_hess(y, np.full(n, init))
    limits = dict(min_data_in_leaf=int(params["min_data_in_leaf"]),
                  min_sum_hessian=float(params["min_sum_hessian_in_leaf"]),
                  lambda_l2=float(params.get("lambda_l2", 0.0)))
    csc = X.tocsc()
    totals = np.array([g.sum(), h.sum(), n], np.float64)
    cols = sorted(ub)
    with ThreadPoolExecutor() as pool:
        found = list(pool.map(
            lambda c: ref.best_threshold(
                goss.csc_histogram(csc, c, ub[c], g, h, totals), **limits),
            cols))
    best_gain, best_col = max((gain, c) for c, (gain, _) in zip(cols, found))
    gain_err = abs(tree.split_gain[0] - best_gain) / best_gain
    return [
        ("tree0_leaf_counts", counts_ok,
         f"{L} leaves, {int(np.sum(rows_in != tree.leaf_count))} counts "
         "differ from numpy's routing of every training row"),
        ("tree0_leaf_values", bool(np.all(err <= tol)),
         f"max |value - reference| {err.max():.3g} (worst in units of its "
         f"tolerance {np.max(err / tol):.3g})"),
        ("tree0_root_gain", gain_err <= bands["root_gain_rtol"],
         f"model {tree.split_gain[0]:.6g} on column "
         f"{int(tree.split_feature[0])}, reference best {best_gain:.6g} on "
         f"column {best_col} of {len(cols)}: off by {gain_err:.3g} of it "
         f"(allowed {bands['root_gain_rtol']})")]


def _watched_iteration(st: State) -> dict:
    """One more sampled iteration, with what went in and what came out."""
    import jax
    gbdt = st.bst._gbdt
    score = np.asarray(jax.block_until_ready(gbdt.device_score_state()),
                       np.float64)[0]
    st.bst.update()
    _train._block(st.bst)
    bag_cnt = int(gbdt.bag_data_cnt)
    return {"score": score, "bag": np.asarray(gbdt._perm)[:bag_cnt],
            "grad_after": np.asarray(gbdt._grad[0], np.float64),
            "tree_index": st.bst.num_trees() - 1}


def _sampled_tree(st: State, seen_rows, seen: dict, tree: ref.Tree,
                  bands: dict, params: dict) -> list:
    """The watched iteration's bag against the GOSS rule, and its tree
    against the reference's sums over that bag."""
    y, n = st.y[:st.rows], st.rows
    g, h = ref.binary_grad_hess(y, seen["score"])
    w = goss.weight(g, h)
    top_k, other_k = goss.counts(n, float(params["top_rate"]),
                                 float(params["other_rate"]))
    mult = goss.multiplier(n, top_k, other_k)
    bag = seen["bag"].astype(np.int64)
    # a row the sampler drew as "other" came back with its gradient
    # multiplied; one it kept as "top" came back as it was
    is_other = np.abs(seen["grad_after"][bag]) > \
        0.5 * (1.0 + mult) * np.abs(g[bag])
    got = goss.check_sample(w, bag, is_other, top_k, other_k,
                            tie_rtol=bands["goss_tie_rtol"])
    out = [("goss_sample", got["ok"],
            f"bag {got['bag_size']} rows ({got['distinct']} distinct) of "
            f"{n}, want {top_k} + {other_k}; float64 threshold "
            f"{got['threshold']:.6g} with {got['ties']} rows within "
            f"{bands['goss_tie_rtol']:g} of it (ties, allowed either way); "
            f"{got['left_out']} rows above it left out; {got['others']} "
            f"rows weighted x{mult:.6g}, {got['top_as_other']} of them from "
            f"the top set; {got['light_as_top']} light rows kept unweighted; "
            "others per 64 row blocks at worst "
            f"{got['worst_block_sigmas']:.2f} sigma off the binomial "
            "(allowed 5)")]

    L = tree.num_leaves
    order = np.argsort(bag, kind="stable")
    leaf = np.empty(len(bag), np.int64)
    leaf[order] = _leaf_of(tree, seen_rows[bag[order]])
    scale = np.where(is_other, mult, 1.0)
    lr, l2 = float(params["learning_rate"]), float(params.get("lambda_l2", 0))

    def off_by(gb, hb):
        """(leaf counts, the tree's leaf values against the float64 Newton
        step over these gradients and hessians of the bag rows, worst leaf
        in units of its tolerance, and as a number): THE comparison of
        `sampled_leaf_values`; the controls below go through it too."""
        cnt, sum_g, sum_h = goss.leaf_sums(leaf, gb, hb, L)
        value = lr * ref.leaf_output(sum_g, sum_h, l2)
        err = np.abs(tree.leaf_value - value)
        tol = (bands["sampled_leaf_value_atol"]
               + bands["sampled_leaf_value_rtol"] * np.abs(value))
        return cnt, float(np.max(err / tol)), float(err.max())

    cnt, worst, err = off_by(g[bag] * scale, h[bag] * scale)
    counts_ok = np.array_equal(cnt.astype(np.int64), tree.leaf_count)
    # two controls through the same comparison. It has to refuse the sums
    # a sampler would have grown the tree on had it left the weight of its
    # "other" rows at 1 (held: the line fails if it does not). The second
    # reading says how tight the limit is and is printed, not held, since
    # it judges the limit and not the program: the sums of gradients and
    # hessians kept in the precision below bfloat16's (float8 e4m3: 4
    # significant bits where the histogram kernels keep 8)
    _, flat, _ = off_by(g[bag], h[bag])
    _, coarse, _ = off_by(goss.round_to_bits(g[bag] * scale, 4),
                          goss.round_to_bits(h[bag] * scale, 4))
    out += [
        ("sampled_leaf_counts", counts_ok,
         f"tree {seen['tree_index']}: {L} leaves, "
         f"{int(np.sum(cnt != tree.leaf_count))} counts differ from numpy's "
         f"routing of the {len(bag)} bag rows (by "
         f"{int(np.sum(np.abs(cnt - tree.leaf_count)))} rows in all)"),
        ("sampled_leaf_values", worst <= 1.0,
         f"max |value - reference| {err:.3g} (worst in units of its "
         f"tolerance {worst:.3g})"),
        ("sampled_leaf_values_controls", flat > 1.0,
         "the same comparison on two controls: with the weights left at 1 "
         f"it reads {flat:.3g} tolerances (held: above 1, refused); from "
         "gradients and hessians rounded to float8's 4 significant bits "
         f"{coarse:.3g} ({'refused' if coarse > 1.0 else 'NOT refused'}; "
         "printed, not held)")]
    return out


def check(ctx, st: State) -> list:
    import jax
    bands, params = ctx.config["correct"], ctx.config["params"]
    plan = st.bst._gbdt.execution_plan()
    on_tpu = jax.default_backend() == "tpu"
    kernels_ok = not on_tpu or ("pallas" in str(plan["hist"])
                                and "pallas" in str(plan["partition"]))
    sampling = (f"goss(top_rate={float(params['top_rate']):g}, "
                f"other_rate={float(params['other_rate']):g})")
    out = [("per_tree_tier",
            plan["tier"] == "per-tree-fused" and kernels_ok
            and plan.get("sampling") == sampling,
            f"tier {plan['tier']}, hist {plan['hist']}, partition "
            f"{plan['partition']}, learner {plan['learner']}, sampling "
            f"{plan.get('sampling')} (configuration: {sampling})")]

    t0 = now()
    seen = _watched_iteration(st)
    trees = st.artifacts["trees"] = ref.parse_model(st.bst.model_to_string())
    take = np.sort(np.random.default_rng(ctx.seed).choice(
        st.rows, min(SAMPLE_ROWS, st.rows), replace=False))
    ub = _upper_bounds(st)
    parts = {"watched iteration": now() - t0}

    def timed(name, fn, *args):
        t = now()
        got = fn(*args)
        parts[name] = now() - t
        return got

    seen_rows, changed = timed("rows as bundled", _as_bundled, st, ub)
    ctx.say(f"rows as the bundles present them: {changed} of {st.rows} rows "
            "set two members of one bundle and lose the earlier one's "
            "indicator; the reference walks them so")
    out += timed("bundles", _bundles, st, seen_rows, take, ub, bands)
    out += timed("tree 0", _tree0, st, seen_rows, trees[0], ub, bands, params)
    out += timed("sampled tree", _sampled_tree, st, seen_rows, seen,
                 trees[seen["tree_index"]], bands, params)
    t = now()

    # the trainer's running scores against its own model, walked by the
    # reference over the sampled training rows
    (_, _, loss, _), = st.bst.eval_train()
    Xs, ys = seen_rows[take], st.y[take]
    raw = np.concatenate(_in_blocks(
        lambda lo, hi: goss.predict_raw_csr(trees, Xs, lo, hi), len(take)))
    walked = ref.binary_logloss(ys, raw)
    p = float(np.mean(st.y[:st.rows]))
    constant = -(p * np.log(p) + (1 - p) * np.log(1 - p))
    out.append(("train_logloss",
                abs(loss - walked) <= bands["logloss_atol"]
                and loss < constant,
                f"eval_train {loss:.6f} over {len(trees)} trees, reference "
                f"walker {walked:.6f} on {len(take)} rows, constant score "
                f"{constant:.6f}"))

    parts["log-loss"] = now() - t
    t = now()
    heldout = st.bst.predict(st.X[st.rows:], num_iteration=AUC_ITERS)
    auc = ref.auc(st.y[st.rows:], heldout)
    lo = max(bands["auc20_floor"], bands["auc20"] - bands["auc20_band"])
    hi = bands["auc20"] + bands["auc20_band"]
    out.append(("heldout_auc", lo <= auc <= hi,
                f"{auc:.5f} at {AUC_ITERS} trees on {len(heldout)} rows "
                f"(want {lo:.5f}..{hi:.5f}); predict path "
                f"{st.bst._gbdt.predict_path}"))
    parts["held-out AUC"] = now() - t
    ctx.say(f"the check took {now() - t0:.1f} s: " + ", ".join(
        f"{k} {v:.1f}" for k, v in parts.items()))
    return out
