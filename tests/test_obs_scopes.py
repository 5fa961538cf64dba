"""What the program says about itself to a profiler and to an operator:
the `lgbm.*` scopes of the iteration program, the `lgbm:` host spans, the
always-on set-up stage table and its `set-up:` line (docs/OBSERVABILITY.md).
CPU, tiny data; the scopes are read from the lowered text, where a named
scope is a `loc`, so no compile cache can stand in the way.
"""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.compile import get_manager
from lightgbm_tpu.compile.manager import _metadata_in_cache_key
from lightgbm_tpu.utils import log, timer

SCOPES = {
    # scope -> the configuration that reaches it
    "lgbm.grad": "binary", "lgbm.root_hist": "binary",
    "lgbm.split_scan": "binary", "lgbm.pick_leaf": "binary",
    "lgbm.bookkeeping": "binary", "lgbm.partition": "binary",
    "lgbm.hist": "binary", "lgbm.pool": "binary",
    "lgbm.score_update": "binary",
    "lgbm.renew": "regression_l1",      # an objective with a renew spec
    "lgbm.allreduce": "data_parallel",  # psum over the 8 host devices
}
PARAMS = {
    "binary": {"objective": "binary"},
    "regression_l1": {"objective": "regression_l1"},
    "data_parallel": {"objective": "binary", "tree_learner": "data"},
}
STAGES = ("construct/sample", "construct/find_bins", "construct/bundle",
          "construct/bin_rows", "construct/occupancy", "state/pack_codes",
          "state/build_data")


def _data(n=3000, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1] ** 2 + 0.2 * rng.randn(n) > 0.3)
    return X, y.astype(np.float32)


def _booster(params, rounds=1):
    X, y = _data()
    params = dict(params, num_leaves=7, max_bin=31, verbose=-1)
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=rounds,
                     keep_training_booster=True)


_LOWERED = {}


def _iteration_program_text(config: str) -> str:
    """The iteration program of a one-round booster, lowered again with
    its locations."""
    if config not in _LOWERED:
        gbdt = _booster(PARAMS[config])._gbdt
        g = gbdt._fused
        common = (g.feature_masks_for_tree(), jnp.float32(0.1),
                  jnp.float32(0.0))
        if config == "data_parallel":
            if len(jax.devices()) < 8:
                pytest.skip("needs 8 (virtual) devices")
            lowered = g._iter_mc_entry.jit_fn().lower(
                gbdt._fused_state, g._n_per_shard, *common)
        else:
            lowered = jax.jit(g._entry_train_iter).lower(
                g._tables(), gbdt._fused_state, *common,
                jnp.int32(g.actual_rows))
        _LOWERED[config] = lowered.as_text(debug_info=True)
    return _LOWERED[config]


@pytest.mark.parametrize("scope", sorted(SCOPES))
def test_iteration_program_carries_scope(scope):
    text = _iteration_program_text(SCOPES[scope])
    # a whole path segment of an op's name stack
    assert re.search(r'[/"]' + re.escape(scope) + r'[/"]', text), scope


def test_scope_names_are_whole_segments_and_no_prefix_of_another():
    for a in SCOPES:
        assert "/" not in a and a.startswith("lgbm.")
        assert not any(b != a and b.startswith(a) for b in SCOPES)


# ------------------------------------------------------------ host spans

def _host_events(trace_dir, prefix="lgbm:"):
    from jax.profiler import ProfileData
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert files, "the profiler session wrote no trace"
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith(prefix)]
    return out


def test_every_collective_of_the_sharded_iteration_is_under_allreduce():
    """`lgbm.allreduce` is the one collective scope: every all_reduce of
    the lowered data-parallel iteration carries it in its location, so a
    reader that sums the ops under the scope misses none."""
    text = _iteration_program_text("data_parallel")
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    ops = re.findall(r'"stablehlo\.(all_reduce|all_gather|reduce_scatter|'
                     r'all_to_all|collective_permute)"\(.*?\n\s*\}\) :'
                     r'[^\n]* loc\((#loc\d+)\)', text, re.S)
    ops += re.findall(r'"stablehlo\.(all_gather|all_to_all|'
                      r'collective_permute)"\([^\n]* loc\((#loc\d+)\)$',
                      text, re.M)
    assert len(ops) >= 4        # root histogram + count, split histogram + count
    for kind, loc in ops:
        assert "lgbm.allreduce" in locs[loc].split("/"), (kind, locs[loc])


def test_the_score_sync_keeps_its_collective_apart_from_the_iterations():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    gbdt = _booster(PARAMS["data_parallel"])._gbdt
    text = jax.jit(gbdt._fused._sync_scores).lower(
        gbdt._fused_state).as_text(debug_info=True)
    named = re.findall(r'loc\("([^"]*all_gather[^"]*)"', text)
    assert named and all(
        n.split("/")[-3:-1] == ["lgbm.score_sync", "lgbm.allreduce"]
        for n in named), named
    assert "all_reduce" not in text


def test_execution_plan_says_what_a_sharded_run_is():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    plan = _booster(PARAMS["data_parallel"])._gbdt.execution_plan()
    assert plan["learner"] == "FusedDataParallelGrower"
    assert plan["tier"] == "persistent-fused" and plan["device_count"] == 8
    assert plan["shard_rows"] == -(-3000 // 8)
    assert plan["codes_pack"] == "host"
    serial = _booster(PARAMS["binary"])._gbdt.execution_plan()
    assert serial["codes_pack"] == "host" and "shard_rows" not in serial


def test_collective_accounting_is_the_work_functions_bytes():
    """The host-side estimate of a sharded iteration's allreduce traffic
    is benchmarks/harness/work_dp.py's formula at a full tree."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    from benchmarks.harness import work_dp
    g = _booster(PARAMS["data_parallel"])._gbdt._fused
    assert g._tree_psum_bytes == int(work_dp.allreduce_bytes(
        [g.num_leaves - 1], g.num_features, g.max_num_bin - 1, g.num_shards))


@pytest.mark.parametrize("config", ["binary", "data_parallel"])
def test_updates_show_as_nested_spans_under_anyones_profiler(tmp_path,
                                                             config):
    if config == "data_parallel" and len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    bst = _booster(PARAMS[config])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        bst.update()
        bst.update()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    updates = [e for e in events if e[0] == "lgbm:update"]
    calls = [e for e in events if e[0] == "lgbm:fused/train_iter"]
    assert len(updates) == 2 and len(calls) == 2
    for _, t0, t1 in updates:
        assert sum(1 for _, a, b in calls if t0 <= a and b <= t1) == 1


def test_spans_without_a_profiler_session_write_nothing(tmp_path,
                                                        monkeypatch):
    monkeypatch.chdir(tmp_path)
    bst = _booster(PARAMS["binary"])
    bst.update()
    with obs.span("anything"):
        pass
    assert list(tmp_path.iterdir()) == []


def test_timer_scope_keeps_its_table_and_leaves_annotating_to_span(
        tmp_path):
    t = timer.Timer(enabled=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with t.scope("timer-only scope"):
            pass
        with obs.span("span scope"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert t.cnt["timer-only scope"] == 1
    names = {n for n, _, _ in _host_events(tmp_path, prefix="")}
    assert "lgbm:span scope" in names
    assert not any("timer-only scope" in n for n in names)


# ------------------------------------------------------------ stage table

@pytest.fixture(scope="module")
def staged_run():
    """One Dataset.construct() and one lgb.train round; the table before
    and after (it is process-global, and a worker runs many tests)."""
    X, y = _data(seed=1)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "verbose": -1}
    before = obs.stage_seconds()
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params).construct()
    construct_wall = time.perf_counter() - t0
    lgb.train(params, ds, num_boost_round=1)
    return before, obs.stage_seconds(), construct_wall


@pytest.mark.parametrize("stage", STAGES)
def test_stage_is_entered_once_per_setup(staged_run, stage):
    before, after, _ = staged_run
    s0, n0 = before.get(stage, (0.0, 0))
    s1, n1 = after[stage]
    assert n1 - n0 == 1
    assert s1 - s0 >= 0.0


def test_construct_stages_fit_inside_construct(staged_run):
    before, after, wall = staged_run
    spent = sum(after[s][0] - before.get(s, (0.0, 0))[0]
                for s in STAGES if s.startswith("construct/"))
    assert 0.0 < spent <= wall


def test_stage_is_counted_with_everything_else_off_and_when_the_body_raises():
    assert obs.active() is None and obs.active_tracer() is None
    n0 = obs.stage_seconds().get("test/raises", (0.0, 0))[1]
    with pytest.raises(KeyError):
        with obs.span("a set-up site", stage="test/raises"):
            raise KeyError("boom")
    assert obs.stage_seconds()["test/raises"][1] == n0 + 1


def test_setup_line_reports_the_gain_since_the_last_line():
    obs.setup_line()
    with obs.span("a set-up site", stage="probe/sleeps"):
        time.sleep(0.06)
    line = obs.setup_line()
    assert line.startswith("set-up: ")
    gained = re.search(r"probe (\d+\.\d) s \(sleeps (\d+\.\d)\)", line)
    assert gained and gained.group(1) == gained.group(2), line
    assert float(gained.group(1)) >= 0.1    # a loaded host sleeps longer
    assert "compile " in line and "lower " in line and "xla " in line
    assert re.search(r"probe 0\.0 s \(sleeps 0\.0\)", obs.setup_line())


def test_setup_line_is_logged_once_per_booster():
    lines = []
    log.register_log_callback(lines.append)
    try:
        X, y = _data(seed=2)
        params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
                  "verbose": 1}
        bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=1,
                        keep_training_booster=True)
        first = [ln for ln in lines if "set-up: " in ln]
        assert len(first) == 1
        for part in ("construct ", "find_bins ", "bin_rows ", "state ",
                     "pack_codes ", "compile ", "lower ", "xla "):
            assert part in first[0], (part, first[0])
        bst.update()
        assert len([ln for ln in lines if "set-up: " in ln]) == 1
    finally:
        log.register_log_callback(None)


# ------------------------------------------ scopes survive a compile cache

@pytest.mark.parametrize("profiled", [True, False])
def test_manager_keys_a_profiled_program_with_its_metadata(profiled):
    """jax's persistent cache ignores metadata in its key by default: a
    hit would hand back the scopes of whichever build compiled first.
    The manager turns the key's metadata on around the compile of a
    `profiled` entry (the iteration program), for its own thread only:
    an eager op another thread compiles meanwhile keeps the default key
    (and its cache hit), and so does every other entry."""
    import threading
    flag = "jax_compilation_cache_include_metadata_in_key"
    seen = {}

    def elsewhere():
        seen["other thread"] = getattr(jax.config, flag)

    class Lowered:
        def compile(self):
            seen["compile"] = getattr(jax.config, flag)
            t = threading.Thread(target=elsewhere)
            t.start()
            t.join(timeout=10)
            return lambda x: x

    class Jitted:
        def lower(self, *args, **kwargs):
            return Lowered()

    assert getattr(jax.config, flag) is False
    entry = get_manager().shared_entry(
        "test/metadata_key", {"v": int(profiled)}, Jitted, store=False,
        profiled=profiled)
    x = jnp.ones((4,), jnp.float32)
    assert entry(x) is x
    assert seen == {"compile": profiled, "other thread": False}
    assert getattr(jax.config, flag) is False
    with pytest.raises(KeyError):
        with _metadata_in_cache_key():
            raise KeyError("boom")
    assert getattr(jax.config, flag) is False
