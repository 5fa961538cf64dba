"""The compile manager's executable table (docs/OBSERVABILITY.md "The
executable table"): every registered executable named where it is
dispatched, each call's wall and calling-thread CPU seconds, a row per
build with its source, what jax builds outside any entry under
`(unregistered)`, and the marks at the end of each update()."""
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.compile import get_manager, reset_manager
from lightgbm_tpu.compile.manager import UNREGISTERED

from test_obs_scopes import _host_events

BASE = dict(num_leaves=4, min_data_in_leaf=1, verbose=-1)
# tier -> (params, Dataset keywords, {entry: calls an iteration}, labels
# of the `instrument_kernel` wrappers and spans around those entries)
TIERS = {
    "persistent": (dict(BASE, objective="binary"), {},
                   {"fused/train_iter": 1}, set()),
    "goss": (dict(BASE, objective="binary", boosting="goss",
                  learning_rate=1.0), {},
             {"objective/get_gradients/binary": 1, "boosting/goss_sample": 1,
              "fused/grow_tree": 1, "boosting/score_add": 1},
             {"gbdt/boosting (gradients)"}),
    "bagging": (dict(BASE, objective="binary", bagging_fraction=0.5,
                     bagging_freq=1), {},
                {"objective/get_gradients/binary": 1, "fused/grow_tree": 1,
                 "boosting/score_add": 1}, {"gbdt/boosting (gradients)"}),
    "multiclass": (dict(BASE, objective="multiclass", num_class=3), {},
                   {"objective/get_gradients/multiclass": 1,
                    "fused/grow_tree": 3, "boosting/score_add": 3},
                   {"gbdt/boosting (gradients)"}),
    "rank": (dict(BASE, objective="lambdarank"), {"group": [8] * 8},
             {"objective/rank_grad": 1, "fused/grow_tree": 1,
              "boosting/score_add": 1},
             {"gbdt/boosting (gradients)", "rank_grad"}),
    "data_parallel": (dict(BASE, objective="binary", tree_learner="data"),
                      {}, {"mc/train_iter": 1}, {"fused/train_iter"}),
}


def _rows(n=64, seed=0, classes=2):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 4)).astype(np.float32)
    return X, rng.integers(0, classes, size=n).astype(np.float32)


def _booster(tier, seed=0):
    params, kw, _, _ = TIERS[tier]
    X, y = _rows(seed=seed, classes={"multiclass": 3, "rank": 4}.get(tier, 2))
    return lgb.train(params, lgb.Dataset(X, label=y, **kw),
                     num_boost_round=1, keep_training_booster=True)


def _gained(before, after, field="calls"):
    zero = {"calls": 0, "call_wall_s": 0.0, "call_cpu_s": 0.0, "builds": []}
    out = {}
    for name, row in after.items():
        old = before.get(name, zero)
        gain = row[field][len(old[field]):] if field == "builds" \
            else row[field] - old[field]
        if gain:
            out[name] = gain
    return out


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_every_call_of_an_update_is_a_named_row_and_a_named_interval(
        tier, tmp_path):
    """Three update()s: every entry the tier dispatches gains 3 x its
    calls an iteration, wall >= CPU >= 0, the marks gain three, and a
    profile of the same run shows exactly those names inside
    `lgbm:update` (plus the wrappers' and spans' own), one interval a
    call."""
    if tier == "data_parallel" and len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    _, _, per_iter, outer = TIERS[tier]
    bst = _booster(tier)
    mgr = get_manager()
    before, marks = mgr.snapshot_entries(), len(mgr.marks)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            bst.update()
    finally:
        jax.profiler.stop_trace()
    after = mgr.snapshot_entries()
    assert _gained(before, after) == {n: 3 * k for n, k in per_iter.items()}
    wall = _gained(before, after, "call_wall_s")
    cpu = _gained(before, after, "call_cpu_s")
    for name in per_iter:
        assert wall[name] >= cpu.get(name, 0.0) >= 0.0, name
    assert mgr.phase == "steady"
    assert len(mgr.marks) == min(marks + 3, mgr.marks.maxlen)
    at, cpu_at, totals = mgr.marks[-1]
    assert at <= time.perf_counter() and cpu_at <= time.thread_time()
    assert mgr.updates >= 4
    assert {n: totals[n][0] for n in per_iter} \
        == {n: after[n]["calls"] for n in per_iter}

    events = _host_events(tmp_path)
    updates = [e for e in events if e[0] == "lgbm:update"]
    assert len(updates) == 3
    inside = [name[len("lgbm:"):] for name, t0, t1 in events
              if name != "lgbm:update"
              and any(a <= t0 and t1 <= b for _, a, b in updates)]
    assert set(inside) == set(per_iter) | outer
    for name, k in per_iter.items():
        assert inside.count(name) == 3 * k, name


# ------------------------------------------------------------- builds

@pytest.fixture
def fresh_store(tmp_path, monkeypatch):
    """A manager of its own over an empty AOT store that keeps every
    compile (jax's persistent cache stays the suite's)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("LGBM_TPU_WARMUP", "0")
    monkeypatch.setenv("LGBM_TPU_AOT_MIN_COMPILE_S", "0")
    reset_manager()
    yield
    reset_manager()


def _entry(salt, name="probe/scale"):
    """A program no cache has seen: `salt` is a constant of its text."""
    return get_manager().shared_entry(
        name, ("probe", salt), lambda: jax.jit(lambda x: x * salt + 1.0))


def _builds(name):
    return get_manager().snapshot_entries().get(name, {"builds": []})["builds"]


@pytest.mark.parametrize("case", ["compiled", "same_signature", "aot_store",
                                  "jax_cache"])
def test_a_build_row_says_where_the_executable_came_from(case, fresh_store,
                                                         monkeypatch):
    salt = float(np.random.default_rng().integers(1, 2 ** 20))
    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_allclose(_entry(salt)(x), np.arange(8) * salt + 1.0)
    first, = _builds("probe/scale")
    assert first["source"] == "compiled" and first["phase"] == "construct"
    assert first["trace_lower_s"] > 0 and first["xla_s"] > 0
    assert first["at"] <= time.perf_counter()
    if case == "compiled":
        return
    if case == "same_signature":
        # the process's second entry of the signature: nothing is built
        _entry(salt)(x)
        assert _builds("probe/scale") == [first]
        assert get_manager().snapshot_entries()["probe/scale"]["calls"] == 2
        return
    if case == "jax_cache":
        # a manager that may not read the store asks jax's cache
        monkeypatch.setattr(type(get_manager().store), "load",
                            lambda self, key: None)
    reset_manager()
    get_manager().phase = "first_call"
    _entry(salt)(x)
    again, = _builds("probe/scale")
    assert again["source"] == case and again["phase"] == "first_call"
    if case == "aot_store":
        assert again["load_s"] > 0 and "xla_s" not in again
    else:
        assert again["trace_lower_s"] > 0 and again["xla_s"] > 0


@pytest.mark.parametrize("case", ["one_build", "other_labels_build_again",
                                  "same_booster_builds_once"])
def test_the_gradient_program_is_in_the_table(case):
    """`objective/get_gradients/<name>` is a registered entry. Its `self`
    is static and carries the labels, so a second dataset builds it again
    (ROADMAP A6: the test that PR flips)."""
    name = "objective/get_gradients/binary"
    before = get_manager().snapshot_entries()
    bst = _booster("bagging", seed=1)
    built = _gained(before, get_manager().snapshot_entries(), "builds")
    assert [b["source"] in ("compiled", "jax_cache") and b["call_s"] > 0
            for b in built[name]] == [True]
    assert built[name][0]["phase"] == "first_call"
    if case == "same_booster_builds_once":
        bst.update()
    elif case == "other_labels_build_again":
        _booster("bagging", seed=2)
    again = _gained(before, get_manager().snapshot_entries(), "builds")
    assert len(again[name]) == (2 if case == "other_labels_build_again"
                                else 1)


@pytest.mark.parametrize("case", ["eager_op", "in_an_entry"])
def test_what_jax_builds_outside_any_entry_is_unregistered(case):
    mgr = get_manager()
    mgr.phase = "probe"
    n = int(np.random.default_rng().integers(1 << 20, 1 << 24))

    def probe():
        rows = [b for b in _builds(UNREGISTERED) if b["phase"] == "probe"]
        return sum(b["count"] for b in rows), sum(b["xla_s"] for b in rows)
    ones = jnp.ones(8).block_until_ready()
    count, seconds = probe()
    if case == "eager_op":
        jnp.arange(n, dtype=jnp.float32).sum().block_until_ready()
        row = [b for b in _builds(UNREGISTERED) if b["phase"] == "probe"][-1]
        assert probe()[0] > count and probe()[1] > seconds
        assert 1 <= len(row["slowest"]) <= 3
        assert row["slowest"] == sorted(row["slowest"])
        assert all(s > 0 and what.startswith("jit(")
                   for s, what in row["slowest"])
        assert row["source"] in ("compiled", "jax_cache")
    else:
        entry = mgr.jit_entry("probe/jit", jax.jit(lambda x: x.sum() * n))
        entry(ones).block_until_ready()
        assert probe() == (count, seconds)
        assert [b["call_s"] > 0 for b in _builds("probe/jit")][-1]
    mgr.phase = "steady"


@pytest.mark.parametrize("fake", ["sleeps", "spins"])
def test_wall_less_cpu_is_the_time_blocked(fake):
    """A witness each way: an executable that sleeps reads blocked, one
    that spins reads CPU (the best of three: the sandbox's cores are
    shared)."""
    def sleeps():
        time.sleep(0.05)

    def spins():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.05:
            pass
    name = "probe/" + fake
    entry = get_manager().jit_entry(name, {"sleeps": sleeps,
                                           "spins": spins}[fake])
    best = None
    for _ in range(3):
        before = get_manager().snapshot_entries().get(
            name, {"call_wall_s": 0.0, "call_cpu_s": 0.0})
        entry()
        row = get_manager().snapshot_entries()[name]
        wall = row["call_wall_s"] - before["call_wall_s"]
        cpu = row["call_cpu_s"] - before["call_cpu_s"]
        assert wall >= 0.05 and 0.0 <= cpu <= wall
        blocked = wall - cpu
        best = blocked if best is None else (
            max(best, blocked) if fake == "sleeps" else min(best, blocked))
    if fake == "sleeps":
        assert best >= 0.045 and wall - best < 0.005 + (wall - 0.05)
    else:
        assert best < 0.005
    assert get_manager().snapshot_entries()[name]["builds"] == []


@pytest.mark.parametrize("case", ["wrapped", "unwrapped", "other_label"])
def test_one_annotation_a_call(case):
    """`instrument_kernel` names an entry it wraps under the entry's own
    name; an unwrapped entry, or one wrapped under another label, is
    named by the manager."""
    entry = get_manager().jit_entry("probe/note", lambda: None)
    assert entry.annotation == "lgbm:probe/note"
    if case == "wrapped":
        obs.instrument_kernel(entry, "probe", name="probe/note")
        assert entry.annotation is None
    elif case == "other_label":
        obs.instrument_kernel(entry, "probe", name="note")
        assert entry.annotation == "lgbm:probe/note"


def test_the_setup_line_names_each_build_over_a_second():
    obs.setup_line()
    get_manager().book_build("probe/slow", "compiled", trace_lower_s=0.5,
                             xla_s=0.75)
    get_manager().book_build("probe/quick", "jax_cache", trace_lower_s=0.1,
                             xla_s=0.2)
    line = obs.setup_line()
    assert re.search(r"; built: (.*, )?probe/slow compiled 1\.2", line)
    assert "probe/quick" not in line
    assert "probe/slow" not in obs.setup_line()
