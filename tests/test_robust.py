"""Preemption-safe training tests (docs/ROBUSTNESS.md).

Covers the fault-plan grammar, the atomic checkpoint writer and its
torn/partial/corrupt fallbacks, resume bit-identity across learner
variants (resumed training must produce byte-identical model text to an
uninterrupted run), the SIGKILL chaos smoke (a real child process is
killed mid-train and resumed), guarded multi-host bring-up (machine
list validation, retry/backoff, failure classification, the startup
health barrier), and the never-fatal telemetry/AOT-store seams.
"""
import errno
import hashlib
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.robust import (CheckpointError, CheckpointManager,
                                 FaultPlan, install_plan)
from lightgbm_tpu.robust import faultinject as fi
from lightgbm_tpu.utils.log import LightGBMError


@pytest.fixture(autouse=True)
def _no_residual_fault_plan(monkeypatch):
    """No fault plan leaks between tests (or in from the environment)."""
    monkeypatch.delenv(fi.ENV_VAR, raising=False)
    install_plan(None)
    fi._ENV_CACHE = None
    yield
    install_plan(None)
    fi._ENV_CACHE = None


# -- fault plan grammar -------------------------------------------------

class TestFaultPlan:
    def test_parse_grammar(self):
        plan = FaultPlan.parse(
            "checkpoint.write:enospc@2; store.load:corrupt,"
            "train.iteration:delay=0.5@3")
        assert [(s.seam, s.mode, s.arg, s.trigger) for s in plan.specs] == [
            ("checkpoint.write", "enospc", 0.0, 2),
            ("store.load", "corrupt", 0.0, None),   # bytes filters: every hit
            ("train.iteration", "delay", 0.5, 3),
        ]

    def test_default_and_explicit_triggers(self):
        assert FaultPlan.parse("sink.write:ioerror").specs[0].trigger == 1
        assert FaultPlan.parse("sink.write:ioerror@*").specs[0].trigger is None
        assert FaultPlan.parse("store.load:truncate").specs[0].trigger is None

    def test_bad_entry_names_itself(self):
        with pytest.raises(ValueError, match="garbage"):
            FaultPlan.parse("garbage")
        with pytest.raises(ValueError, match="explode"):
            FaultPlan.parse("checkpoint.write:explode")

    def test_hit_count_trigger(self):
        plan = FaultPlan.parse("sink.write:ioerror@2")
        assert plan.check("sink.write") is None          # hit 1: quiet
        with pytest.raises(OSError) as ei:
            plan.check("sink.write")                     # hit 2: fires
        assert ei.value.errno == errno.EIO
        assert plan.fired == ["sink.write:ioerror@2"]
        assert plan.check("other.seam") is None

    def test_indexed_seam_matches_iteration(self):
        plan = FaultPlan.parse("train.iteration:enospc@3")
        assert plan.check("train.iteration", index=0) is None
        assert plan.check("train.iteration", index=2) is None
        with pytest.raises(OSError) as ei:
            plan.check("train.iteration", index=3)
        assert ei.value.errno == errno.ENOSPC

    def test_filter_bytes_truncate_and_corrupt(self):
        payload = bytes(range(200))
        out = FaultPlan.parse("store.load:truncate").filter_bytes(
            "store.load", payload)
        assert out == payload[:100]
        out = FaultPlan.parse("store.load:corrupt").filter_bytes(
            "store.load", payload)
        assert len(out) == len(payload) and out != payload
        assert out[:100] == payload[:100]                # flips the middle

    def test_firing_bumps_counters(self):
        from lightgbm_tpu.obs import registry as obs_registry
        reg = obs_registry.activate(obs_registry.MetricsRegistry())
        try:
            plan = FaultPlan.parse("store.load:truncate")
            plan.filter_bytes("store.load", b"0123456789")
            assert reg.counters["fault.fired"] == 1
            assert reg.counters["fault.store.load"] == 1
        finally:
            obs_registry.deactivate()

    def test_install_plan_overrides_env(self, monkeypatch):
        monkeypatch.setenv(fi.ENV_VAR, "sink.write:ioerror")
        env_plan = fi.active_plan()
        assert env_plan is not None and env_plan.text == "sink.write:ioerror"
        assert fi.active_plan() is env_plan              # cached per text
        mine = install_plan("trace.export:ioerror")
        assert fi.active_plan() is mine
        install_plan(None)
        assert fi.active_plan() is env_plan


# -- checkpoint manager -------------------------------------------------

def _mgr(tmp_path, **kw):
    kw.setdefault("interval", 2)
    kw.setdefault("barrier", lambda: None)
    kw.setdefault("process_index", 0)
    return CheckpointManager(str(tmp_path / "ck"), **kw)


def _state(seed=0):
    rng = np.random.RandomState(seed)
    return {"iter": 7, "score": rng.randn(64).astype(np.float32),
            "nested": {"rng": rng.randint(0, 2 ** 31, 8, dtype=np.int64),
                       "names": ["a", "b"], "flag": True}}


class TestCheckpointManager:
    def test_due_schedule(self, tmp_path):
        m = _mgr(tmp_path, interval=3)
        assert [i for i in range(9) if m.due(i)] == [2, 5, 8]
        assert not any(_mgr(tmp_path, interval=0).due(i) for i in range(9))

    def test_save_load_round_trip_is_bit_exact(self, tmp_path):
        m = _mgr(tmp_path)
        st = _state()
        path = m.save(5, st, "tree\nv=1\n")
        assert path and os.path.exists(path)
        it, got, model = m.load_latest()
        assert it == 5 and model == "tree\nv=1\n"
        assert got["iter"] == 7 and got["nested"]["names"] == ["a", "b"]
        assert got["nested"]["flag"] is True
        assert got["score"].dtype == np.float32
        assert np.array_equal(got["score"], st["score"])
        assert np.array_equal(got["nested"]["rng"], st["nested"]["rng"])

    def test_prune_keeps_newest_k(self, tmp_path):
        m = _mgr(tmp_path, keep=2)
        for it in (1, 3, 5):
            m.save(it, {"x": 1}, "m")
        names = sorted(os.listdir(m.directory))
        assert names == ["ckpt_0000003.lgbckpt", "ckpt_0000005.lgbckpt"]

    def test_corrupt_newest_falls_back_to_previous(self, tmp_path):
        m = _mgr(tmp_path)
        m.save(1, {"x": 1}, "one")
        m.save(3, {"x": 3}, "three")
        with open(m.path_for(3), "r+b") as fh:
            fh.seek(-1, os.SEEK_END)
            fh.write(b"\xff")                            # hash now mismatches
        it, _, model = m.load_latest()
        assert (it, model) == (1, "one")

    def test_torn_write_falls_back(self, tmp_path):
        m = _mgr(tmp_path)
        m.save(1, {"x": 1}, "one")
        install_plan("checkpoint.write:torn")
        m.save(3, {"x": 3}, "three")                     # renamed but invalid
        install_plan(None)
        assert os.path.exists(m.path_for(3))
        it, _, model = m.load_latest()
        assert (it, model) == (1, "one")

    def test_partial_write_leaves_no_checkpoint(self, tmp_path):
        m = _mgr(tmp_path)
        install_plan("checkpoint.write:partial")
        assert m.save(1, {"x": 1}, "one") is None
        install_plan(None)
        assert not os.path.exists(m.path_for(1))
        assert m.load_latest() is None

    def test_enospc_is_nonfatal(self, tmp_path):
        from lightgbm_tpu.obs import registry as obs_registry
        reg = obs_registry.activate(obs_registry.MetricsRegistry())
        try:
            m = _mgr(tmp_path)
            install_plan("checkpoint.write:enospc")
            assert m.save(1, {"x": 1}, "one") is None    # no raise
            assert reg.counters["ckpt.write_errors"] == 1
        finally:
            obs_registry.deactivate()

    def test_foreign_params_digest_is_refused(self, tmp_path):
        _mgr(tmp_path, params_digest="aaa").save(1, {"x": 1}, "one")
        assert _mgr(tmp_path, params_digest="bbb").load_latest() is None
        it, _, _ = _mgr(tmp_path, params_digest="aaa").load_latest()
        assert it == 1

    def test_empty_directory_rejected(self, tmp_path):
        assert _mgr(tmp_path).load_latest() is None      # no files yet
        with pytest.raises(CheckpointError):
            CheckpointManager("")

    def test_nonwriter_process_skips_write(self, tmp_path):
        m = _mgr(tmp_path, process_index=1)
        assert m.save(1, {"x": 1}, "one") is None
        assert m.load_latest() is None


# -- resume bit-identity ------------------------------------------------

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


def _assert_resume_matches_fresh(tmp_path, extra, rounds=6):
    """Train half the rounds into a checkpoint dir, resume to the full
    count, and demand byte-identical model text vs an uninterrupted
    run — the bar for "resume changed nothing"."""
    X, y = _make_data()
    params = dict(BASE, **extra)
    d = str(tmp_path / "ck")
    _train(params, X, y, rounds // 2, ckpt_dir=d)
    assert any(n.endswith(".lgbckpt") for n in os.listdir(d))
    resumed = _train(params, X, y, rounds, ckpt_dir=d)
    fresh = _train(params, X, y, rounds)
    assert resumed.model_to_string() == fresh.model_to_string()
    return resumed, fresh


class TestResumeBitIdentity:
    def test_fused(self, tmp_path):
        _assert_resume_matches_fresh(tmp_path, {})

    def test_serial(self, tmp_path):
        _assert_resume_matches_fresh(tmp_path, {"tpu_fused": False})

    @pytest.mark.slow
    def test_quantized_grad(self, tmp_path):
        """Slow-marked: resume bit-identity stays tier-1 via
        test_serial; the quantized variant only swaps the gradient
        representation the resume path round-trips."""
        _assert_resume_matches_fresh(tmp_path, {"use_quantized_grad": 1})

    # dart/quantized resume and the SIGKILL chaos drill ride the full
    # run; serial resume keeps bit-identity tier-1
    @pytest.mark.slow
    def test_dart(self, tmp_path):
        _assert_resume_matches_fresh(
            tmp_path, {"boosting": "dart", "drop_rate": 0.5})

    @pytest.mark.slow
    def test_bagging_and_feature_fraction(self, tmp_path):
        _assert_resume_matches_fresh(
            tmp_path, {"bagging_fraction": 0.7, "bagging_freq": 1,
                       "feature_fraction": 0.6, "seed": 9})

    # resume bit-identity stays tier-1 via the serial/quantized variants
    # and the SIGKILL chaos drill; the early-stopping twin is the
    # slowest and rides the full run only
    @pytest.mark.slow
    def test_early_stopping_resume(self, tmp_path):
        X, y = _make_data(600)
        Xv, yv = _make_data(200, seed=8)
        params = dict(BASE, metric="binary_logloss")

        def run(ckpt_dir, rounds):
            ds = lgb.Dataset(X, label=y)
            ev = {}
            bst = lgb.train(dict(params), ds, num_boost_round=rounds,
                            valid_sets=[ds.create_valid(Xv, label=yv)],
                            valid_names=["v"], early_stopping_rounds=3,
                            evals_result=ev, verbose_eval=False,
                            checkpoint_dir=ckpt_dir)
            return bst, ev

        d = str(tmp_path / "ck")
        run(d, 5)
        resumed, ev_r = run(d, 12)
        fresh, ev_f = run(None, 12)
        assert resumed.model_to_string() == fresh.model_to_string()
        assert resumed.best_iteration == fresh.best_iteration
        # the resumed eval history only covers post-resume iterations;
        # its tail must match the fresh run's tail exactly
        tail = len(ev_r["v"]["binary_logloss"])
        assert ev_f["v"]["binary_logloss"][-tail:] == \
            ev_r["v"]["binary_logloss"]
        np.testing.assert_array_equal(resumed.predict(Xv), fresh.predict(Xv))

    def test_init_model_wins_over_resume(self, tmp_path):
        X, y = _make_data()
        d = str(tmp_path / "ck")
        base = _train(BASE, X, y, 4, ckpt_dir=d)
        cont = lgb.train(dict(BASE), lgb.Dataset(X, label=y),
                         num_boost_round=2, init_model=base,
                         verbose_eval=False, checkpoint_dir=d)
        # resume skipped: 4 init + 2 new trees, not 4 + (8 - 4)
        assert cont.num_trees() == 6


# -- chaos smoke: SIGKILL a real training process, resume it ------------

_CHILD = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = rng.randn(400, 5)
    y = (1.2 * X[:, 0] - X[:, 1] + 0.3 * rng.randn(400) > 0).astype(float)
    params = {"objective": "binary", "verbose": -1, "min_data_in_leaf": 5,
              "checkpoint_interval": 2}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6,
                    verbose_eval=False, checkpoint_dir=sys.argv[1])
    with open(sys.argv[2], "w") as fh:
        fh.write(bst.model_to_string())
""")


@pytest.mark.slow
def test_chaos_sigkill_resume_is_bit_identical(tmp_path):
    """Kill a real training process entering iteration 4 (SIGKILL — no
    atexit, no flush), resume it from the surviving checkpoints, and
    demand the final model is byte-identical to an uninterrupted run.

    Slow-marked: resume bit-identity stays tier-1 via
    TestResumeBitIdentity (serial + quantized); this adds the
    subprocess SIGKILL delivery on top of the same resume path."""
    d = str(tmp_path / "ck")
    out = str(tmp_path / "model.txt")
    env = dict(os.environ,
               LGBM_TPU_FAULT_PLAN="train.iteration:sigkill@4")
    proc = subprocess.run([sys.executable, "-c", _CHILD, d, out],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == -signal.SIGKILL, proc.stderr
    assert not os.path.exists(out)
    survivors = sorted(os.listdir(d))
    assert survivors and all(n.endswith(".lgbckpt") for n in survivors)

    env.pop("LGBM_TPU_FAULT_PLAN")
    proc = subprocess.run([sys.executable, "-c", _CHILD, d, out],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    with open(out) as fh:
        resumed_text = fh.read()

    X, y = _make_data()                                 # same data as _CHILD
    fresh = _train(BASE, X, y, 6)
    assert hashlib.sha256(resumed_text.encode()).hexdigest() == \
        hashlib.sha256(fresh.model_to_string().encode()).hexdigest()


# -- checkpoint fields never change the compiled program ----------------

def test_checkpoint_fields_do_not_change_aot_signature(tmp_path):
    from lightgbm_tpu.compile import signature as S
    from lightgbm_tpu.config import Config
    a = Config.from_params({"objective": "binary"})
    b = Config.from_params({"objective": "binary",
                            "checkpoint_dir": str(tmp_path),
                            "checkpoint_interval": 7, "checkpoint_keep": 5})
    assert S.config_signature(a) == S.config_signature(b)


def test_params_string_excludes_checkpoint_fields(tmp_path):
    X, y = _make_data()
    bst = _train(dict(BASE, checkpoint_keep=3), X, y, 2,
                 ckpt_dir=str(tmp_path / "ck"))
    assert "checkpoint" not in bst.model_to_string()


# -- guarded multi-host bring-up ----------------------------------------

class TestBringUp:
    def test_machine_list_validation(self):
        from lightgbm_tpu.network import parse_machine_list
        assert parse_machine_list("a:1, b:2") == ["a:1", "b:2"]
        assert parse_machine_list("fe80::1:500") == ["fe80::1:500"]
        for bad in ("hostonly", "h:", ":80", "h:0", "h:65536", "h:abc"):
            with pytest.raises(LightGBMError):
                parse_machine_list(f"ok:80,{bad}")

    def test_classify_init_error(self):
        from lightgbm_tpu.network import _classify_init_error
        cases = [
            (RuntimeError("Deadline Exceeded: timed out"), "timeout"),
            (RuntimeError("failed to connect: Connection refused"),
             "refused"),
            (RuntimeError("process id 3 already registered"),
             "rank mismatch"),
            (RuntimeError("???"), "unknown"),
        ]
        for exc, want in cases:
            kind, hint = _classify_init_error(exc, "h:1", 1, 2)
            assert kind == want and hint

    def test_retry_then_success(self, monkeypatch):
        import lightgbm_tpu.network as net
        monkeypatch.setattr(net, "local_addresses",
                            lambda: ["10.77.0.2", "127.0.0.1"])
        monkeypatch.setenv(net._INIT_RETRIES_ENV, "5")
        calls, delays = [], []

        def flaky_init(**kw):
            calls.append(kw)
            if len(calls) < 3:
                raise RuntimeError("connect timed out")

        out = net.ensure_distributed(
            "10.77.0.1:12400,10.77.0.2:12400", 2,
            _initialize=flaky_init, _sleep=delays.append)
        assert out is True and len(calls) == 3
        assert len(delays) == 2
        # exponential backoff with bounded jitter: base 1s then 2s,
        # each inflated by at most 25%
        assert 1.0 <= delays[0] <= 1.25 and 2.0 <= delays[1] <= 2.5
        assert delays[1] > delays[0]

    def test_exhausted_retries_fail_with_diagnostic(self, monkeypatch):
        import lightgbm_tpu.network as net
        monkeypatch.setattr(net, "local_addresses",
                            lambda: ["10.77.0.2", "127.0.0.1"])
        monkeypatch.setenv(net._INIT_RETRIES_ENV, "2")
        calls = []

        def dead_init(**kw):
            calls.append(kw)
            raise RuntimeError("connect timed out")

        with pytest.raises(LightGBMError, match="2 attempts"):
            net.ensure_distributed("10.77.0.1:12400,10.77.0.2:12400", 2,
                                   _initialize=dead_init,
                                   _sleep=lambda s: None)
        assert len(calls) == 2

    def test_rank_mismatch_fails_immediately(self, monkeypatch):
        import lightgbm_tpu.network as net
        monkeypatch.setattr(net, "local_addresses",
                            lambda: ["10.77.0.2", "127.0.0.1"])
        calls = []

        def dup_init(**kw):
            calls.append(kw)
            raise RuntimeError("process id 1 is already registered")

        with pytest.raises(LightGBMError, match="rank mismatch"):
            net.ensure_distributed("10.77.0.1:12400,10.77.0.2:12400", 2,
                                   _initialize=dup_init,
                                   _sleep=lambda s: None)
        assert len(calls) == 1                           # no pointless retry

    def test_startup_health_barrier_timeout(self, monkeypatch):
        import threading
        from lightgbm_tpu.network import _startup_health_barrier
        _startup_health_barrier(0.5, _barrier=lambda: None)  # fast path
        release = threading.Event()
        with pytest.raises(LightGBMError, match="timed out"):
            _startup_health_barrier(0.05, _barrier=release.wait)
        release.set()                                    # unwedge the thread
        with pytest.raises(LightGBMError, match="barrier failed"):
            _startup_health_barrier(
                5.0, _barrier=lambda: (_ for _ in ()).throw(
                    RuntimeError("peer gone")))

    def test_collective_dispatch_seam(self):
        from lightgbm_tpu.network import collective_span
        install_plan("collective.dispatch:ioerror")
        with pytest.raises(OSError):
            with collective_span("psum", nbytes=8):
                pass


# -- AOT store: corrupt/truncated blobs fall back to recompile ----------

class TestStoreFallback:
    def _store(self, tmp_path):
        from lightgbm_tpu.compile.store import ExecutableStore
        return ExecutableStore(root=str(tmp_path / "aot"))

    def test_truncated_pickle_invalidated(self, tmp_path):
        from lightgbm_tpu.compile.store import CorruptBlobError
        st = self._store(tmp_path)
        assert st.save("k", (b"blob-bytes", {"in": 1}, {"out": 2}), [0])
        assert st.load("k")[0] == b"blob-bytes"
        install_plan("store.load:truncate")
        with pytest.raises(CorruptBlobError, match="truncated or corrupt"):
            st.load("k")
        install_plan(None)
        assert st.load("k") is None                      # invalidated on sight

    def test_corrupt_pickle_invalidated(self, tmp_path):
        from lightgbm_tpu.compile.store import CorruptBlobError
        st = self._store(tmp_path)
        assert st.save("k", (b"blob-bytes", None, None), [0])
        install_plan("store.load:corrupt")
        with pytest.raises(CorruptBlobError):
            st.load("k")
        install_plan(None)
        assert st.load("k") is None


# -- telemetry is never fatal -------------------------------------------

class TestTelemetryNeverFatal:
    def test_sink_open_failure_disables(self, tmp_path):
        from lightgbm_tpu.obs.sink import JsonlSink
        sink = JsonlSink(str(tmp_path / "no" / "such" / "dir" / "m.jsonl"))
        sink.write({"it": 1})                            # no raise
        sink.close()

    def test_sink_write_failure_disables_once(self, tmp_path):
        from lightgbm_tpu.obs.sink import JsonlSink
        path = str(tmp_path / "m.jsonl")
        install_plan("sink.write:ioerror")
        sink = JsonlSink(path)
        sink.write({"it": 1})                            # fault fires, eaten
        install_plan(None)
        sink.write({"it": 2})                            # disabled: no-op
        sink.close()
        with open(path) as fh:
            assert fh.read() == ""

    def test_trace_export_failure_is_warned_not_raised(self, tmp_path):
        from lightgbm_tpu import obs
        install_plan("trace.export:ioerror")
        session = obs.TelemetrySession(
            trace_file=str(tmp_path / "trace.json"))
        session.close()                                  # no raise
