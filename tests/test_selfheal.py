"""Self-healing training tests (docs/ROBUSTNESS.md "Self-healing").

Covers the hang watchdog (deadman timer, phase-aware stall
classification, trace flush, cooperative raise, checkpoint
auto-resume byte-identity), the on-device numeric-health sentinels
(grad/hess-plane and leaf-value checks, runtime overflow limit,
quarantine-and-continue, quantized tripwire, degraded-mode ladder),
the hang/nan/overflow fault-grammar extensions, the keep-K prune
race tolerance, the self-heal config knobs (aliases, clamps, AOT
signature + model-text exclusion), schema minor 8, and the
fail-fast ingest validation of labels / features / init scores.
"""
import os
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.compile import get_manager
from lightgbm_tpu.compile.signature import config_signature
from lightgbm_tpu.config import Config
from lightgbm_tpu.network import collective_span
from lightgbm_tpu.obs.registry import MetricsRegistry
from lightgbm_tpu.obs.sink import SCHEMA_MINOR, validate_record
from lightgbm_tpu.robust import FaultPlan, install_plan
from lightgbm_tpu.robust import faultinject as fi
from lightgbm_tpu.robust.sentinel import (DEGRADED_LADDER, NumericSentinel,
                                          apply_degraded_rung)
from lightgbm_tpu.robust.watchdog import (HangTimeout, Watchdog,
                                          activate_watchdog, classify_stall,
                                          deactivate_watchdog, watch_phase)
from lightgbm_tpu.utils.log import LightGBMError


@pytest.fixture(autouse=True)
def _no_residual_fault_plan(monkeypatch):
    """No fault plan (or watchdog) leaks between tests."""
    monkeypatch.delenv(fi.ENV_VAR, raising=False)
    install_plan(None)
    fi._ENV_CACHE = None
    yield
    install_plan(None)
    fi._ENV_CACHE = None
    deactivate_watchdog()


def _make_data(n=400, f=5, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (1.2 * X[:, 0] - X[:, 1] + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "verbose": -1, "min_data_in_leaf": 5,
        "checkpoint_interval": 2}


def _train(params, X, y, rounds, ckpt_dir=None):
    return lgb.train(dict(params), lgb.Dataset(X, label=y),
                     num_boost_round=rounds, verbose_eval=False,
                     checkpoint_dir=ckpt_dir)


def _auc(y, p):
    order = np.argsort(p)
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    npos = int(y.sum())
    nneg = len(y) - npos
    return (ranks[y == 1].sum() - npos * (npos + 1) / 2) / (npos * nneg)


# -- fault grammar: hang / nan / overflow --------------------------------

class TestSelfHealFaultGrammar:
    def test_parse(self):
        plan = FaultPlan.parse(
            "train.iteration:hang=2.5@3; sentinel.check:nan,"
            "collective.dispatch:overflow@*")
        assert [(s.seam, s.mode, s.arg, s.trigger) for s in plan.specs] == [
            ("train.iteration", "hang", 2.5, 3),
            ("sentinel.check", "nan", 0.0, 1),
            ("collective.dispatch", "overflow", 0.0, None),
        ]

    def test_hang_blocks_then_disarms(self):
        plan = FaultPlan.parse("collective.dispatch:hang=0.05@*")
        t0 = time.monotonic()
        spec = plan.check("collective.dispatch")
        assert spec is not None and spec.mode == "hang"
        assert time.monotonic() - t0 >= 0.05
        assert spec.disarmed
        # one-shot: the auto-resumed replay must not hang again
        assert plan.check("collective.dispatch") is None

    def test_nan_is_returned_to_the_caller(self):
        plan = FaultPlan.parse("train.iteration:nan@4")
        assert plan.check("train.iteration", index=3) is None
        spec = plan.check("train.iteration", index=4)
        assert spec is not None and spec.mode == "nan"


# -- watchdog ------------------------------------------------------------

class TestStallClassification:
    def test_classes(self):
        assert classify_stall("collective:psum") == "collective"
        assert classify_stall("dispatch:update") == "dispatch"
        assert classify_stall("readback:eval scalars") == "readback"
        assert classify_stall("host-callback:after") == "host-callback"
        assert classify_stall("something:else") == "iteration"
        assert classify_stall(None) == "iteration"


class TestWatchdog:
    def test_timeout_must_be_positive(self):
        with pytest.raises(ValueError):
            Watchdog(0.0)

    def test_deadman_trips_between_heartbeats(self):
        wd = Watchdog(0.08, poll_s=0.02).start()
        try:
            # no heartbeat: the deadman thread trips on its own; under a
            # loaded machine (six test workers) it can be scheduled late,
            # so wait for the trip and not for a fixed quarter second
            deadline = time.monotonic() + 10.0
            while wd.tripped is None and time.monotonic() < deadline:
                time.sleep(0.02)
            with pytest.raises(HangTimeout) as ei:
                wd.check()
            d = ei.value.diagnosis
            assert d["stall_class"] == "iteration"
            assert "hang_timeout" in d["message"]
            wd.clear()
            wd.check()                       # re-armed, no residual trip
        finally:
            wd.stop()

    def test_phase_exit_is_a_cooperative_check_point(self):
        wd = Watchdog(0.08, poll_s=0.02).start()
        try:
            with pytest.raises(HangTimeout) as ei:
                with wd.phase("readback:eval scalars"):
                    time.sleep(0.25)
            d = ei.value.diagnosis
            assert d["stall_class"] == "readback"
            assert d["phase"] == "readback:eval scalars"
        finally:
            wd.stop()

    def test_trip_bumps_counters(self):
        from lightgbm_tpu.obs import registry as obs_registry
        reg = obs_registry.activate(MetricsRegistry())
        wd = Watchdog(0.05, poll_s=0.02).start()
        try:
            time.sleep(0.2)
            with pytest.raises(HangTimeout):
                wd.check()
            assert reg.counters["watchdog.trips"] == 1
            assert reg.counters["watchdog.stall_iteration"] == 1
        finally:
            wd.stop()
            obs_registry.deactivate()

    def test_warmup_grace_tolerates_cold_compiles(self):
        """Before WARMUP_ITERS beats the effective timeout is the grace
        budget — iteration-0 whole-program compiles are not hangs (and
        there is no checkpoint to resume from yet)."""
        wd = Watchdog(0.05, poll_s=0.02, warmup_grace_s=30.0).start()
        try:
            wd.beat(0)
            time.sleep(0.2)                  # would trip without grace
            wd.check()
            for i in range(1, Watchdog.WARMUP_ITERS + 1):
                wd.beat(i)
            time.sleep(0.2)                  # warm now: strict timeout
            with pytest.raises(HangTimeout):
                wd.check()
        finally:
            wd.stop()

    def test_watch_phase_is_free_without_a_watchdog(self):
        deactivate_watchdog()
        with watch_phase("collective:psum") as wd:
            assert wd is None


def test_collective_hang_is_classified_and_trace_flushed(tmp_path):
    """The acceptance drill: an injected collective.dispatch hang is
    detected, classified as a 'collective' stall, and the runtime trace
    is flushed for post-mortem."""
    trace_path = str(tmp_path / "wd_trace.json")
    tr = obs.Tracer()
    obs.activate_tracer(tr)
    wd = activate_watchdog(
        Watchdog(0.15, poll_s=0.04, trace_path=trace_path).start())
    install_plan("collective.dispatch:hang=0.6")
    try:
        with pytest.raises(HangTimeout) as ei:
            with collective_span("psum", 1024):
                pass
    finally:
        deactivate_watchdog(wd)
        wd.stop()
        obs.deactivate_tracer(tr)
    d = ei.value.diagnosis
    assert d["stall_class"] == "collective"
    assert d["phase"].startswith("collective:")
    assert d["trace_file"] == trace_path and os.path.exists(trace_path)


class TestTrainingHang:
    def test_hang_raises_actionable_timeout_without_auto_resume(self):
        X, y = _make_data()
        install_plan("train.iteration:hang=0.6@3")
        with pytest.raises(HangTimeout) as ei:
            _train(dict(BASE, hang_timeout=0.25), X, y, 5)
        d = ei.value.diagnosis
        assert d["stall_class"] in ("iteration", "dispatch")
        assert d["iteration"] is not None
        assert "trace_file" in d and "slowest_rank" in d

    def test_auto_resume_is_byte_identical(self, tmp_path):
        """Hang mid-train with auto_resume: the watchdog restores the
        last checkpoint in-process and the finished model is
        byte-identical to a run that never hung."""
        X, y = _make_data()
        d = str(tmp_path / "ck")
        # wide margins: a loaded single-core box shows natural ~0.7 s
        # inter-heartbeat stalls, which must not trip the watchdog during
        # the post-resume replay — only the injected hang may.
        install_plan("train.iteration:hang=3.0@4")
        healed = _train(dict(BASE, hang_timeout=1.2, auto_resume=True),
                        X, y, 6, ckpt_dir=d)
        install_plan(None)
        fresh = _train(BASE, X, y, 6)
        assert healed.model_to_string() == fresh.model_to_string()


# -- numeric sentinels ---------------------------------------------------

class TestNumericSentinel:
    def test_host_nan_and_overflow_verdicts(self):
        s = NumericSentinel(overflow_limit=1e30)
        s.dispatch([np.array([1.0, np.nan, 2.0])], 3)
        assert s.pop_trips() == [(3, "nan")]
        s.dispatch([np.array([1.0, 2e30])], 4)
        assert s.pop_trips() == [(4, "overflow")]
        assert (s.trips, s.total_trips) == (2, 2)
        s.reset_trips()
        assert (s.trips, s.total_trips) == (0, 2)

    def test_device_verdicts_ride_batched_fetches(self):
        import jax
        import jax.numpy as jnp
        s = NumericSentinel()
        s.dispatch([jnp.asarray([1.0, float("nan"), 2.0])], 1)
        assert s.has_pending
        pending = s.take_pending()
        assert not s.has_pending
        vals = jax.device_get([r for _, r in pending])
        s.resolve(pending, vals)
        assert s.pop_trips() == [(1, "nan")]

    def test_overflow_limit_is_a_runtime_operand(self):
        """Changing the limit never recompiles the health reduction."""
        import jax.numpy as jnp
        arr = jnp.asarray(np.full(8, 100.0, np.float32))
        NumericSentinel(overflow_limit=1e30).dispatch([arr], 0)
        base = get_manager().stats.get("jit_compiles", 0)
        s = NumericSentinel(overflow_limit=50.0)
        s.dispatch([arr], 1)
        assert get_manager().stats.get("jit_compiles", 0) == base
        import jax
        pending = s.take_pending()
        s.resolve(pending, jax.device_get([r for _, r in pending]))
        assert s.pop_trips() == [(1, "overflow")]

    def test_seam_poisons_the_checked_plane(self):
        install_plan("sentinel.check:nan")
        s = NumericSentinel()
        s.dispatch([np.zeros(4)], 2)
        assert s.pop_trips() == [(2, "nan")]

    def test_drop_pending_abandons_the_old_timeline(self):
        import jax.numpy as jnp
        s = NumericSentinel()
        s.dispatch([jnp.asarray([float("nan")])], 0)
        s.dispatch([np.array([np.nan])], 1)      # host: trips immediately
        assert s.has_pending and s._trips_out
        s.drop_pending()
        assert not s.has_pending and s.pop_trips() == []

    def test_quant_tripwire(self):
        from lightgbm_tpu.obs import registry as obs_registry
        reg = obs_registry.activate(MetricsRegistry())
        try:
            s = NumericSentinel(quant_escalation_limit=32)
            reg.inc("hist.quant_overflow_escalations", 10)
            assert not s.poll_quant_tripwire()    # first poll sets the base
            reg.inc("hist.quant_overflow_escalations", 40)
            assert s.poll_quant_tripwire()
            assert not s.poll_quant_tripwire()    # warns once
            assert reg.counters["health.quant_tripwire"] == 1
        finally:
            obs_registry.deactivate()


class TestDegradedLadder:
    def test_rungs_strip_capabilities_in_order(self):
        class G:
            _pipeline = True
            _device_eval = True

        g = G()
        mgr = get_manager()
        old_aot, old_env = mgr.aot_enabled, os.environ.get("LGBM_TPU_AOT")
        try:
            assert apply_degraded_rung(g, 0) == "pipeline"
            assert g._pipeline is False
            assert apply_degraded_rung(g, 1) == "device_eval"
            assert g._device_eval is False
            assert apply_degraded_rung(g, 2) == "aot_store"
            assert os.environ["LGBM_TPU_AOT"] == "0"
            assert apply_degraded_rung(g, len(DEGRADED_LADDER)) is None
        finally:
            mgr.aot_enabled = old_aot
            if old_env is None:
                os.environ.pop("LGBM_TPU_AOT", None)
            else:
                os.environ["LGBM_TPU_AOT"] = old_env


# -- quarantine-and-continue --------------------------------------------

class TestQuarantine:
    def test_nan_gradient_quarantines_exactly_one_tree(self):
        """A NaN gradient plane trips the sentinel; exactly the poisoned
        iteration's tree is quarantined, training continues on clean
        recomputed gradients, and accuracy survives."""
        X, y = _make_data()
        params = dict(BASE, tpu_fused=False, numeric_sentinels=True)
        install_plan("train.iteration:nan@3")
        poisoned = _train(params, X, y, 6)
        install_plan(None)
        clean = _train(params, X, y, 6)
        assert poisoned.num_trees() == clean.num_trees() - 1
        p = poisoned.predict(X)
        assert np.isfinite(p).all()
        assert abs(_auc(y, p) - _auc(y, clean.predict(X))) <= 1e-3

    def test_fused_path_leaf_sentinel_quarantines(self):
        X, y = _make_data()
        install_plan("sentinel.check:nan@3")
        bst = _train(dict(BASE, numeric_sentinels=True), X, y, 6)
        install_plan(None)
        assert bst.num_trees() == 5
        assert np.isfinite(bst.predict(X)).all()

    def test_sentinel_dispatches_in_the_iteration_that_made_the_tree(self):
        """Persistent tier, no valid set: every update() leaves one
        PendingTree holding its own device arrays, and the sentinel's
        check of that tree is dispatched in that iteration, under that
        iteration's number (nothing is queued for a later flush)."""
        import jax
        from lightgbm_tpu.treelearner.fused import PendingTree
        X, y = _make_data()
        bst = lgb.Booster(dict(BASE, numeric_sentinels=True),
                          lgb.Dataset(X, label=y))
        g = bst._gbdt
        assert g.execution_plan()["tier"] == "persistent-fused"
        for i in range(3):
            bst.update()
            t = g.models[i]
            assert isinstance(t, PendingTree) and t._tree is None
            assert isinstance(t.tree_arrays["leaf_value"], jax.Array)
            assert g._sentinel.checks == i + 1
            assert [it for it, _ in g._sentinel._pending] \
                == list(range(i + 1))
        leaves = [t.tree_arrays["leaf_value"] for t in g.models]
        assert len({id(a) for a in leaves}) == 3
        g.sentinel_drain()
        assert g._sentinel._pending == [] and not g._sentinel.pop_trips()
        assert bst.num_trees() == 3

    def test_quarantine_iter_bounds_and_rebuild(self):
        X, y = _make_data()
        bst = _train(BASE, X, y, 4)
        g = bst._gbdt
        assert not g.quarantine_iter(99)
        assert g.quarantine_iter(2)
        assert bst.num_trees() == 3
        assert np.isfinite(bst.predict(X)).all()

    def test_dart_quarantine_drops_tree_weight(self):
        X, y = _make_data()
        bst = _train(dict(BASE, boosting="dart", drop_rate=0.3,
                          tpu_fused=False), X, y, 3)
        g = bst._gbdt
        n, w, sw = len(g.models), len(g.tree_weight), g.sum_weight
        assert g.quarantine_iter(1)
        assert len(g.models) == n - 1
        assert len(g.tree_weight) == w - 1
        assert g.sum_weight < sw
        assert np.isfinite(bst.predict(X)).all()


# -- steady-state cost: syncs + compiles --------------------------------

P_PIPE = {"objective": "binary", "metric": "binary_logloss", "verbose": -1,
          "min_data_in_leaf": 20, "num_leaves": 7, "learning_rate": 0.3,
          "numeric_sentinels": True}


def _sentinel_run(tracer=None):
    rng = np.random.RandomState(9)
    X = rng.randn(500, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.randn(500) > 0).astype(np.float64)
    ds = lgb.Dataset(X[:350], label=y[:350])
    vs = ds.create_valid(X[350:], label=y[350:])
    callbacks = []
    if tracer is not None:
        def mark(env):
            tracer.iteration = env.iteration
        mark.before_iteration = True
        mark.order = 0
        callbacks = [mark]
    lgb.train(dict(P_PIPE), ds, num_boost_round=12, valid_sets=[vs],
              callbacks=callbacks, verbose_eval=False)


def test_sentinels_keep_single_sync_and_zero_new_compiles(monkeypatch):
    """Sentinel verdicts ride the existing trailing fetches: a
    sentinel-enabled steady state still makes at most ONE blocking host
    sync per iteration, and a warmed run compiles nothing new."""
    from collections import Counter
    monkeypatch.setenv("LGBM_TPU_PIPELINE", "1")
    _sentinel_run()                              # warm every program
    compiles_before = get_manager().stats.get("jit_compiles", 0)

    tr = obs.Tracer()
    obs.activate_tracer(tr)
    assert obs.install_sync_tracing()
    try:
        _sentinel_run(tracer=tr)
    finally:
        obs.uninstall_sync_tracing()
        obs.deactivate_tracer(tr)

    assert get_manager().stats.get("jit_compiles", 0) == compiles_before
    per_iter = Counter()
    for ph, name, cat, ts, dur, it, args in tr.buf:
        if cat == "sync" and it >= 0:
            per_iter[it] += 1
    offenders = {i: per_iter[i] for i in range(3, 10) if per_iter[i] > 1}
    assert not offenders, offenders


# -- checkpoint prune race (satellite) ----------------------------------

class TestPruneRace:
    def _mgr(self, tmp_path, **kw):
        from lightgbm_tpu.robust import CheckpointManager
        kw.setdefault("interval", 2)
        kw.setdefault("barrier", lambda: None)
        kw.setdefault("process_index", 0)
        return CheckpointManager(str(tmp_path / "ck"), **kw)

    def test_prune_never_unlinks_the_kept_window(self, tmp_path):
        m = self._mgr(tmp_path, keep=3)
        for it in (1, 3, 5, 7, 9):
            m.save(it, {"x": it}, "m")
        names = sorted(os.listdir(m.directory))
        assert names == ["ckpt_0000005.lgbckpt", "ckpt_0000007.lgbckpt",
                         "ckpt_0000009.lgbckpt"]

    def test_load_latest_tolerates_concurrent_prune(self, tmp_path,
                                                    monkeypatch):
        """A reader racing a writer's keep-K prune sees
        FileNotFoundError on an already-unlinked entry; that is not an
        invalid checkpoint — walk on to the next-newer survivor."""
        from lightgbm_tpu.obs import registry as obs_registry
        m = self._mgr(tmp_path)
        m.save(1, {"x": 1}, "one")
        m.save(3, {"x": 3}, "three")
        orig = m._read

        def racing_read(path):
            if path.endswith("0000003.lgbckpt"):
                raise FileNotFoundError(path)
            return orig(path)

        monkeypatch.setattr(m, "_read", racing_read)
        reg = obs_registry.activate(MetricsRegistry())
        try:
            it, _, model = m.load_latest()
        finally:
            obs_registry.deactivate()
        assert (it, model) == (1, "one")
        assert "ckpt.invalid" not in reg.counters


# -- config knobs --------------------------------------------------------

class TestSelfHealConfig:
    def test_aliases(self):
        c = Config.from_params({"watchdog_timeout": 5, "auto_restart": True,
                                "sentinels": True})
        assert c.hang_timeout == 5.0
        assert c.auto_resume is True
        assert c.numeric_sentinels is True
        c = Config.from_params({"hang_timeout_s": 2,
                                "numeric_health_checks": 1})
        assert c.hang_timeout == 2.0 and c.numeric_sentinels is True

    def test_clamps(self):
        c = Config.from_params({"hang_timeout": -3, "auto_resume_attempts": 0,
                                "sentinel_max_trips": 0,
                                "sentinel_overflow_limit": -1})
        assert c.hang_timeout == 0.0
        assert c.auto_resume_attempts == 1
        assert c.sentinel_max_trips == 1
        assert c.sentinel_overflow_limit == 1e30

    def test_fields_are_outside_the_aot_signature(self):
        a = config_signature(Config.from_params({"objective": "binary"}))
        b = config_signature(Config.from_params(
            {"objective": "binary", "hang_timeout": 9.0, "auto_resume": True,
             "auto_resume_attempts": 7, "numeric_sentinels": True,
             "sentinel_overflow_limit": 7.0, "sentinel_max_trips": 5}))
        assert a == b

    def test_fields_are_outside_the_model_text(self):
        X, y = _make_data()
        plain = _train(BASE, X, y, 1)
        knobs = _train(dict(BASE, numeric_sentinels=True,
                            sentinel_overflow_limit=123.0,
                            sentinel_max_trips=5), X, y, 1)
        text = knobs.model_to_string()
        assert "sentinel" not in text
        assert text == plain.model_to_string()


# -- schema minor 8 ------------------------------------------------------

class TestSchemaMinor8:
    def test_minor_is_8(self):
        assert SCHEMA_MINOR >= 8

    def test_selfheal_fields_flow_through(self):
        reg = MetricsRegistry()
        reg.inc("watchdog.trips")
        reg.inc("watchdog.stall_collective")
        reg.inc("health.checks", 3)
        reg.inc("health.quarantined")
        reg.set_gauge("coll.slowest_rank", 2)
        reg.add_time("sentinel", 0.01)
        reg.begin_iteration(0)
        rec = reg.end_iteration()
        assert validate_record(rec) == []
        assert rec["gauges"]["coll.slowest_rank"] == 2
        bench = reg.bench_fields()
        assert bench["watchdog_trips"] == 1
        assert bench["watchdog_stall_collective"] == 1
        assert bench["health_checks"] == 3
        assert bench["health_quarantined"] == 1
        assert bench["phase_sentinel_s"] > 0


# -- ingest validation ---------------------------------------------------

class TestIngestValidation:
    def test_nan_label_is_rejected_naming_the_row(self):
        X, y = _make_data(50)
        y = y.copy()
        y[7] = np.nan
        with pytest.raises(LightGBMError, match="non-finite"):
            lgb.Dataset(X, label=y).construct()

    def test_inf_feature_is_rejected_naming_the_column(self):
        X, y = _make_data(50)
        X = X.copy()
        X[5, 2] = np.inf
        with pytest.raises(LightGBMError, match="column 2"):
            lgb.Dataset(X, label=y).construct()

    def test_nan_feature_stays_legal_as_missing(self):
        X, y = _make_data()
        X = X.copy()
        X[::7, 1] = np.nan
        bst = _train(BASE, X, y, 1)
        assert np.isfinite(bst.predict(X)).all()

    def test_nonfinite_init_score_is_rejected(self):
        X, y = _make_data(50)
        init = np.zeros(50)
        init[3] = -np.inf
        with pytest.raises(LightGBMError, match="init_score"):
            lgb.Dataset(X, label=y, init_score=init).construct()
