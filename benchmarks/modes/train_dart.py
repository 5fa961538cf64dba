"""DART training traffic: `lgb.Dataset` -> `lgb.train(boosting="dart")` ->
`Booster.update()` back to back, no validation set, no callbacks.

Set-up, window and traced sub-window are `modes/train.py`'s (the same
`bench:update` / `bench:sync` spans and `traced_trees` artifact, so the
same readers work), after a warm-up long enough that the forest is old:
DART's own work (the dropped trees' replay) grows with the trees there
are to drop. The traced sub-window also reads the program's counter of
trees replayed, and keeps the drop sets of its iterations for the
per-lane-split reader (harness/work_dart.py).

The check holds the program to `reference/dart_numpy.py`, whose schedule
follows from the parameters alone: every iteration's dropped set, the
tree weights and every tree's scale in the saved model (tree 0's bias
included) against the reference's; the training score against the
model's trees at the reference's scales on a seeded sample of rows (two
controls refused: the add-back dropped, and 1/(k+1) for k/(k+1)); and ONE
more iteration dropping at least five trees, run under the check's eyes:
its gradients against the log-loss's at the score without the dropped
trees, its tree's leaf counts against numpy's routing of every row, its
leaf values against the float64 Newton step at shrinkage lr / (k + 1).

What this file reads of the program beyond its public API, all of it
here: `bst._gbdt.device_score_state()`, `.execution_plan()`,
`.drop_history`, `.tree_weight`, `.sum_weight`, `._grad` / `._hess`,
`predict_path`, `ds._handle._device_bins` (never uploaded) and `obs`'
counters and sync tracing.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.harness import loader
from benchmarks.harness.clock import now
from benchmarks.reference import dart_numpy as dart
from benchmarks.reference import gbdt_numpy as ref
from benchmarks.reference.lambdarank_numpy import round_bf16

_train = loader.load_module("modes", "train")
window = _train.window

SAMPLE_ROWS = 262_144       # training rows the score and gradient lines
HELD_ROWS = 65_536          # held-out rows the raw-score line compares
WATCH_MIN_DROPS = 5         # the watched iteration drops at least this many
WATCH_WITHIN = 60           # ... and comes within this many iterations


@dataclasses.dataclass
class State:
    bst: object
    ds: object
    X: np.ndarray               # train rows, then the held-out rows
    y: np.ndarray
    rows: int
    tree0_birth: np.ndarray     # tree 0's leaf values as first grown
    artifacts: dict


class NoDartPlan(RuntimeError):
    """The program cannot run this mode's cells inside a run's time."""


def _needs_the_dart_program(lgb, params: dict) -> None:
    """Asked of a 64-row booster, before anything is generated: does the
    program's plan name a device replay of the dropped trees? One that
    does not materializes every tree and walks the dropped ones over a
    row-major table uploaded for it, minutes an iteration at this size
    (PERF.md section 6), so it fails here, at once and with a reason,
    instead of running out a run's time."""
    X = np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32)
    quiet = dict(params, verbose=-1)
    ds = lgb.Dataset(X, label=np.arange(64) % 2, params=quiet)
    plan = lgb.Booster(quiet, ds)._gbdt.execution_plan()
    if "dart" not in plan:
        raise NoDartPlan(
            "this program's execution_plan() names no device replay of "
            f"DART's dropped trees ({plan}): its drops materialize the "
            "forest and walk a row-major table, which a run of this cell "
            "would not end on")


def _dart_params(params: dict) -> dict:
    return {k: params[k] for k in (
        "learning_rate", "drop_rate", "max_drop", "skip_drop",
        "uniform_drop", "xgboost_dart_mode", "drop_seed")}


def setup(ctx) -> State:
    import lightgbm_tpu as lgb
    shape, params = ctx.config["shape"], dict(ctx.config["params"])
    _needs_the_dart_program(lgb, params)
    rows = int(shape["rows"])
    gen = ctx.config["generator"]
    with ctx.stage("generate"):
        X, y = ctx.load("generators", gen["name"]).make(
            rows + int(shape["heldout_rows"]), seed=ctx.seed,
            cols=int(shape["cols"]), **gen["args"])
    with ctx.stage("construct"):
        ds = lgb.Dataset(X[:rows], label=y[:rows],
                         params=dict(params)).construct()
    with ctx.stage("first_call"):
        bst = lgb.train(dict(params), ds, num_boost_round=1,
                        verbose_eval=False, keep_training_booster=True)
        _train._block(bst)
    # tree 0 as grown (its bias included), to hold its final scale to
    birth = ref.parse_model(bst.model_to_string())[0].leaf_value.copy()
    with ctx.stage("warmup"):
        for _ in range(int(ctx.traffic["warmup_iters"])):
            bst.update()
        _train._block(bst)
    ctx.say(f"execution plan: {bst._gbdt.execution_plan()}")
    return State(bst, ds, X, y, rows, birth, {})


def traced(ctx, st: State) -> dict:
    from lightgbm_tpu import obs
    before = obs.active()
    reg = obs.activate(obs.MetricsRegistry())
    try:
        got = _train.traced(ctx, st)
    finally:
        obs.deactivate(reg)
        if before is not None:
            obs.activate(before)
    got["counters"]["dart_trees_replayed"] = \
        reg.counters.get("dart.trees_replayed", 0)
    first, last = st.artifacts["traced_trees"]
    st.artifacts["traced_syncs"] = got["counters"]["blocking_syncs"]
    st.artifacts["traced_drops"] = [
        d for it, d in st.bst._gbdt.drop_history if first <= it < last]
    return got


# ------------------------------------------------------------- the check

def schedule_lines(seen: dict, want: dict, birth0: np.ndarray,
                   with_bias: bool, rtol: float) -> list:
    """The program's bookkeeping against the reference's: `seen` holds
    `drops` (iteration -> dropped tuple, as many as it kept),
    `tree_weight`, `sum_weight`, `shrinkage` (every tree's, from the
    model text) and `tree0` (tree 0's leaf values now); `want` is
    dart_numpy.schedule over as many iterations."""
    n = len(want["drops"])
    drops_ok = all(tuple(seen["drops"][it]) == want["drops"][it]
                   for it in seen["drops"]) and len(seen["drops"]) > 0
    diff = [it for it in sorted(seen["drops"])
            if tuple(seen["drops"][it]) != want["drops"][it]]
    shrink = dart.model_shrinkage(want, with_bias)
    got_s = np.asarray(seen["shrinkage"], np.float64)
    s_err = float(np.max(np.abs(got_s - shrink) / np.abs(shrink))) \
        if len(got_s) == n else np.inf
    w_want = want["tree_weight"]
    w_got = np.asarray(seen["tree_weight"], np.float64)
    w_err = float(np.max(np.abs(w_got - w_want) / np.abs(w_want),
                         initial=0.0)) if len(w_got) == len(w_want) else np.inf
    sw_err = abs(seen["sum_weight"] - want["sum_weight"]) / max(
        abs(want["sum_weight"]), 1e-300) if len(w_want) else 0.0
    t0_want = birth0 * want["factor"][0]
    t0_err = float(np.max(np.abs(seen["tree0"] - t0_want)
                          / np.maximum(np.abs(t0_want), 1e-12)))
    rounds = sum(1 for d in want["drops"] if d)
    return [
        ("drop_schedule", drops_ok and s_err <= rtol and w_err <= rtol
         and sw_err <= rtol,
         f"{len(seen['drops'])} of {n} iterations' dropped sets against "
         f"the reference's from drop_seed ({rounds} rounds dropped "
         f"{sum(len(d) for d in want['drops'])} trees; differ at "
         f"{diff[:5]}); every tree's shrinkage off by {s_err:.3g} of "
         f"itself at worst, tree_weight by {w_err:.3g}, sum_weight by "
         f"{sw_err:.3g} (allowed {rtol:g})"),
        ("tree0_scaled_whole", t0_err <= rtol,
         f"tree 0's leaf values against its values as grown x its factor "
         f"{want['factor'][0]:.6g} (its bias included): off by "
         f"{t0_err:.3g} of themselves (allowed {rtol:g})")]


def walk(trees_leaves, ratios: dict) -> dict:
    """{name: sum over trees t of ratios[name][t] x tree t's output} over
    `trees_leaves`, an iterable of every tree's leaf values on the rows,
    in tree order, walked once for all the names."""
    acc = {}
    for t, v in enumerate(trees_leaves):
        for name, r in ratios.items():
            acc[name] = acc.get(name, 0.0) + r[t] * v
    return acc


def score_lines(score: np.ndarray, walked: dict, bands: dict) -> list:
    """The training score on sampled rows against `walked["reference"]`,
    the model's trees at the reference's scales; every other entry of
    `walked`, and the reference rounded to bfloat16 (the precision below
    the float32 the score is kept in), is a control that goes through the
    same comparison and must be refused."""
    def worst(w):
        tol = bands["score_atol"] + bands["score_rtol"] * np.abs(w)
        return float(np.max(np.abs(score - w) / tol))

    got = worst(walked["reference"])
    walked = dict(walked, bfloat16=round_bf16(walked["reference"]))
    ctl = {name: worst(w) for name, w in walked.items()
           if name != "reference"}
    return [
        ("train_score", got <= 1.0,
         f"the program's float32 training score on {len(score)} rows "
         f"against its trees at the reference's scales: worst {got:.3g} "
         f"of the tolerance ({bands['score_atol']:g} + "
         f"{bands['score_rtol']:g} x |score|)"),
        ("train_score_controls", all(v > 1.0 for v in ctl.values()),
         "the same comparison against " + ", ".join(
             f"{k} {v:.3g}" for k, v in ctl.items())
         + " tolerances (held: every one above 1, refused)")]


def _leaf_values(tree: ref.Tree, X: np.ndarray) -> np.ndarray:
    return tree.leaf_value[ref.leaf_of(tree, X)]


def _model(st: State):
    text = st.bst.model_to_string()
    return ref.parse_model(text), dart.parse_shrinkage(text)


def _watch(ctx, st: State, params: dict, bands: dict) -> list:
    """Runs on to the next iteration the reference's schedule says drops
    at least WATCH_MIN_DROPS trees, then that one iteration with the
    score read before it: its gradients, its tree's leaf counts and
    values against the reference's."""
    import jax
    gbdt = st.bst._gbdt
    done = st.bst.num_trees()
    plan = dart.schedule(done + WATCH_WITHIN, _dart_params(params))
    target = next((it for it in range(done, done + WATCH_WITHIN)
                   if len(plan["drops"][it]) >= WATCH_MIN_DROPS), None)
    if target is None:
        return [("watched_iteration", False,
                 f"no iteration in {done}..{done + WATCH_WITHIN} drops "
                 f"{WATCH_MIN_DROPS} trees by the reference's schedule")]
    while st.bst.num_trees() < target:
        st.bst.update()
    trees, _ = _model(st)
    score = np.asarray(jax.block_until_ready(gbdt.device_score_state()),
                       np.float64)[0]
    st.bst.update()
    _train._block(st.bst)
    it, dropped = gbdt.drop_history[-1]
    g = np.asarray(gbdt._grad[0], np.float64)
    h = np.asarray(gbdt._hess[0], np.float64)
    k = len(dropped)
    new = ref.parse_model(st.bst.model_to_string())[-1]

    X, y, n = st.X[:st.rows], st.y[:st.rows], st.rows
    take = np.sort(np.random.default_rng(ctx.seed + 2).choice(
        n, min(SAMPLE_ROWS, n), replace=False))
    Xs = X[take]
    off = np.sum([_leaf_values(trees[j], Xs) for j in dropped], axis=0) \
        if dropped else np.zeros(len(take))
    want_g, want_h = ref.binary_grad_hess(y[take], score[take] - off)
    kept_g, _ = ref.binary_grad_hess(y[take], score[take])
    tol = bands["grad_atol"]
    worst = max(float(np.max(np.abs(g[take] - want_g))),
                float(np.max(np.abs(h[take] - want_h)))) / tol
    control = float(np.max(np.abs(g[take] - kept_g))) / tol
    coarse = float(np.max(np.abs(g[take] - round_bf16(want_g)))) / tol

    leaf = np.concatenate(_train._in_blocks(
        lambda lo, hi: ref.leaf_of(new, X[lo:hi]), n))
    L = new.num_leaves
    rows_in = np.bincount(leaf, None, L)
    sum_g, sum_h = np.bincount(leaf, g, L), np.bincount(leaf, h, L)
    abs_g = np.bincount(leaf, np.abs(g), L)
    lr, l2 = float(params["learning_rate"]), float(params.get("lambda_l2", 0))
    shrink = lr / (k + 1.0) if not params.get("xgboost_dart_mode") \
        else lr / (lr + k)
    newton = ref.leaf_output(sum_g, sum_h, l2)
    vtol = bands["leaf_value_atol"] + shrink * bands["leaf_value_bf16_ulps"] \
        * 2.0 ** -9 * (abs_g + np.abs(sum_g)) / (sum_h + l2)
    verr = float(np.max(np.abs(new.leaf_value - shrink * newton) / vtol))
    verr_lr = float(np.max(np.abs(new.leaf_value - lr * newton) / vtol))
    return [
        ("watched_iteration", it == target and k >= WATCH_MIN_DROPS,
         f"iteration {it} (the reference's first from {done} dropping "
         f">= {WATCH_MIN_DROPS}: {target}) dropped {k} trees"),
        ("watched_gradients", worst <= 1.0 and control > 1.0
         and coarse > 1.0,
         f"on {len(take)} rows against the log-loss's at the score less "
         f"the {k} dropped trees: worst {worst:.3g} of {tol:g}; controls "
         f"(refused above 1): at the score with them {control:.3g}, the "
         f"reference rounded to bfloat16 {coarse:.3g}"),
        ("watched_leaf_counts", np.array_equal(rows_in, new.leaf_count),
         f"{L} leaves, {int(np.sum(rows_in != new.leaf_count))} counts "
         "differ from numpy's routing of every training row"),
        ("watched_leaf_values", verr <= 1.0 and verr_lr > 1.0,
         f"against the float64 Newton step over the program's gradients x "
         f"lr/(k+1) = {shrink:.6g}: worst {verr:.3g} of its bfloat16 "
         f"tolerance; at x lr (control, refused above 1) {verr_lr:.3g}")]


def _f32_thresholds(tree: ref.Tree) -> ref.Tree:
    """The tree as PathForest reads it: its thresholds rounded to the
    float32 its inputs come in."""
    return dataclasses.replace(
        tree, threshold=tree.threshold.astype(np.float32).astype(np.float64))


def _heldout(ctx, st: State, trees: list, shrink: np.ndarray,
             bands: dict) -> list:
    """The saved model's raw scores on sampled held-out rows against the
    float64 walker's over its text, and the held-out AUC. PathForest
    compares float32 inputs with thresholds rounded to float32, so the
    walker rounds them too: then every row agrees to `heldout_raw_atol`.
    The rows that take another branch at the text's float64 thresholds
    are counted beside it, the witness that they differ by that rounding
    alone. Controls, the same comparison: the walker rounded to
    bfloat16, and the walker over the trees at their shrinkage as grown
    (DART's rescaling undone)."""
    gbdt = st.bst._gbdt
    held = st.X[st.rows:]
    raw = st.bst.predict(held, raw_score=True)
    take = np.sort(np.random.default_rng(ctx.seed + 3).choice(
        len(held), min(HELD_ROWS, len(held)), replace=False))
    Xh, got = held[take], raw[take]
    n = len(trees)
    grown = np.ones(n)
    grown[1:] = shrink[1:] / np.asarray(
        dart.schedule(n, _dart_params(ctx.config["params"]))["birth"])[1:]

    def leaves(ts):
        return (np.concatenate(_train._in_blocks(
            lambda lo, hi, tr=tr: _leaf_values(tr, Xh[lo:hi]), len(take),
            16)) for tr in ts)

    walked = walk(leaves([_f32_thresholds(tr) for tr in trees]),
                  {"reference": np.ones(n), "as_grown": 1 / grown})
    walked["bfloat16"] = round_bf16(walked["reference"])
    exact = walk(leaves(trees), {"float64": np.ones(n)})["float64"]
    atol = bands["heldout_raw_atol"]
    off = {k: float(np.max(np.abs(got - w))) for k, w in walked.items()}
    moved = np.abs(got - exact) > atol
    auc = ref.auc(st.y[st.rows:], raw)
    lo = bands["auc_final"] - bands["auc_final_band"]
    hi = bands["auc_final"] + bands["auc_final_band"]
    return [("heldout_raw", off["reference"] <= atol
             and off["bfloat16"] > atol and off["as_grown"] > atol,
             f"the saved model's raw scores on {len(take)} held-out rows "
             f"against the float64 walker's over its {n} trees at the "
             f"thresholds rounded to float32: worst {off['reference']:.3g} "
             f"(allowed {atol:g}); at the text's float64 thresholds "
             f"{int(moved.sum())} rows take another branch (off by up to "
             f"{float(np.max(np.abs(got - exact))):.3g}); controls (refused above it): the walker "
             f"rounded to bfloat16 {off['bfloat16']:.3g}, at the trees' "
             f"shrinkage as grown {off['as_grown']:.3g}; predict path "
             f"{gbdt.predict_path}"),
            ("heldout_auc", lo <= auc <= hi,
             f"{auc:.5f} at {n} trees on {len(held)} rows (want "
             f"{lo:.5f}..{hi:.5f})")]


def check(ctx, st: State) -> list:
    import jax
    bands, params = ctx.config["correct"], ctx.config["params"]
    gbdt = st.bst._gbdt
    plan = gbdt.execution_plan()
    on_tpu = jax.default_backend() == "tpu"
    kernels_ok = not on_tpu or ("pallas" in str(plan["hist"])
                                and "pallas" in str(plan["partition"]))
    replay = (plan.get("dart") or {}).get("replay")
    row_major = st.ds._handle._device_bins
    out = [("per_tree_tier",
            plan["tier"] == "per-tree-fused" and kernels_ok
            and (not on_tpu or plan["device_count"] == 1)
            and replay == ("pallas" if on_tpu else "xla")
            and row_major is None,
            f"tier {plan['tier']}, hist {plan['hist']}, partition "
            f"{plan['partition']}, {plan['device_count']} device(s), dart "
            f"{plan.get('dart')}, the row-major table "
            f"{'never uploaded' if row_major is None else 'UPLOADED'}")]
    if st.artifacts.get("traced_syncs") is not None:
        out.append(("no_blocking_sync", st.artifacts["traced_syncs"] == 0,
                    f"{st.artifacts['traced_syncs']} blocking syncs in the "
                    "traced iterations"))
    t0, parts = now(), {}

    # the score as the window left it, and the model it came from
    score = np.asarray(jax.block_until_ready(gbdt.device_score_state()),
                       np.float64)[0]
    trees, shrink = _model(st)
    n_trees, dp = len(trees), _dart_params(params)
    with_bias = abs(ref.binary_init_score(st.y[:st.rows])) > 1e-15
    want = dart.schedule(n_trees, dp)
    factor_model = shrink / np.where(np.arange(n_trees) == 0,
                                     1.0 if with_bias else want["birth"][0],
                                     want["birth"])
    take = np.sort(np.random.default_rng(ctx.seed).choice(
        st.rows, min(SAMPLE_ROWS, st.rows), replace=False))
    Xs = st.X[take]
    t = now()
    ratios = {"reference": want["score_factor"] / factor_model}
    for c in ("no_add_back", "one_over_k_plus_1"):
        ctl = dart.schedule(n_trees, dp, mutant=c, drops=want["drops"])
        ratios[c] = ctl["score_factor"] / factor_model
    leaves = (np.concatenate(_train._in_blocks(
        lambda lo, hi, tr=tr: _leaf_values(tr, Xs[lo:hi]), len(take), 16))
        for tr in trees)
    out += score_lines(score[take], walk(leaves, ratios), bands)
    parts["score"] = now() - t

    t = now()
    out += _watch(ctx, st, params, bands)
    parts["watched iteration"] = now() - t

    trees, shrink = _model(st)
    want = dart.schedule(len(trees), dp)
    seen = {"drops": {it: d for it, d in gbdt.drop_history},
            "tree_weight": list(gbdt.tree_weight),
            "sum_weight": float(gbdt.sum_weight), "shrinkage": shrink,
            "tree0": trees[0].leaf_value}
    out += schedule_lines(seen, want, st.tree0_birth, with_bias,
                          bands["schedule_rtol"])
    st.artifacts["trees"] = trees
    st.artifacts["tree_splits"] = [tr.num_leaves - 1 for tr in trees]

    t = now()
    out += _heldout(ctx, st, trees, shrink, bands)
    parts["held out"] = now() - t
    ctx.say(f"the check took {now() - t0:.1f} s: " + ", ".join(
        f"{k} {v:.1f}" for k, v in parts.items()))
    return out
