"""AOT compile manager: cache keys, executable store, shape bucketing,
warmup, and the zero-recompile acceptance check (docs/COMPILE_CACHE.md).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.compile import (CorruptBlobError, ExecutableStore,
                                  aot_store_root, bucket_rows, cache_key,
                                  compile_cache_dir, config_signature,
                                  get_manager, reset_manager,
                                  shape_signature, signature_digest)
from lightgbm_tpu.compile.manager import load_executable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def aot_env(tmp_path, monkeypatch):
    """Fresh process-global manager writing to an isolated store (the
    store root follows the one cache directory; jax's own persistent
    cache keeps the directory conftest configured)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("LGBM_TPU_WARMUP", "0")
    # persist every compile regardless of speed: these tests assert the
    # store round-trip itself, not the persistence economics
    monkeypatch.setenv("LGBM_TPU_AOT_MIN_COMPILE_S", "0")
    reset_manager()
    yield tmp_path / "aot"
    reset_manager()


# -- shape bucketing ----------------------------------------------------

def test_bucket_rows_ladder(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_BUCKET_MIN", "1024")
    # below the threshold: exact shape (small jobs compile fast anyway)
    assert bucket_rows(1000) == 1000
    assert bucket_rows(1024) == 1024
    # quarter-power-of-two ladder above it
    assert bucket_rows(1025) == 1280
    assert bucket_rows(1500) == 1536
    assert bucket_rows(1536) == 1536
    assert bucket_rows(5000) == 5120
    assert bucket_rows(5100) == 5120
    for n in (1025, 3000, 10**6, 10**7 + 3):
        b = bucket_rows(n)
        assert b >= n
        assert b <= n * 1.25 + 1  # padding waste bounded by 25%


def test_bucket_rows_disabled(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_BUCKET_MIN", "16")
    monkeypatch.setenv("LGBM_TPU_SHAPE_BUCKETS", "0")
    assert bucket_rows(12345) == 12345


# -- cache keys ---------------------------------------------------------

def test_signature_stable_across_equal_configs():
    p = {"objective": "binary", "num_leaves": 31, "max_bin": 255}
    s1 = config_signature(Config.from_params(dict(p)))
    s2 = config_signature(Config.from_params(dict(p)))
    assert signature_digest("e", s1) == signature_digest("e", s2)


def test_signature_changes_with_trace_relevant_params():
    base = {"objective": "binary", "num_leaves": 31}
    d0 = signature_digest("e", config_signature(Config.from_params(base)))
    for delta in ({"max_bin": 63}, {"num_leaves": 63},
                  {"lambda_l2": 1.5}, {"objective": "regression"}):
        d = signature_digest("e", config_signature(
            Config.from_params({**base, **delta})))
        assert d != d0, f"{delta} must change the compile signature"


def test_signature_ignores_io_and_obs_params(tmp_path):
    base = {"objective": "binary", "num_leaves": 31}
    d0 = signature_digest("e", config_signature(Config.from_params(base)))
    d1 = signature_digest("e", config_signature(Config.from_params(
        {**base, "metrics_file": str(tmp_path / "m.jsonl"),
         "output_model": str(tmp_path / "m.txt"), "verbosity": -1})))
    assert d1 == d0


def test_cache_key_tracks_shapes_and_statics():
    a = jax.ShapeDtypeStruct((128, 4), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 4), jnp.float32)
    k_a = cache_key("d", shape_signature((a,), {}))
    assert k_a == cache_key("d", shape_signature((a,), {}))
    assert k_a != cache_key("d", shape_signature((b,), {}))
    assert k_a != cache_key("d", shape_signature(
        (jax.ShapeDtypeStruct((128, 4), jnp.bfloat16),), {}))
    assert k_a != cache_key("d2", shape_signature((a,), {}))
    assert k_a != cache_key("d", shape_signature((a,), {"flag": True}))


def test_environment_key_tracks_code_identity(monkeypatch):
    """REVIEW fix: the environment key must change when the package's
    own code changes, or a store from an older checkout would silently
    replay stale executables after a kernel bugfix."""
    from lightgbm_tpu.compile import signature as S
    assert S.code_fingerprint()  # non-empty, cached
    k0 = S.environment_key()
    assert k0 == S.environment_key()  # deterministic
    monkeypatch.setattr(S, "_CODE_FINGERPRINT", "0" * 20)
    assert S.environment_key() != k0


# -- the one cache directory --------------------------------------------

def test_cache_dir_obeys_env_else_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    assert aot_store_root() == os.path.join(REPO, ".jax_cache", "aot")
    assert ExecutableStore().root == aot_store_root()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache_dir() == "/some/dir"
    assert aot_store_root() == "/some/dir/aot"
    assert ExecutableStore().root == "/some/dir/aot"


def test_ensure_compile_cache_sets_nothing_when_env_is_set(monkeypatch):
    from lightgbm_tpu.compile import ensure_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert ensure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


# -- executable store ---------------------------------------------------

_ROUND_TRIP = """
import jax, jax.numpy as jnp, numpy as np
from jax.experimental.serialize_executable import serialize
from lightgbm_tpu.compile import ExecutableStore
from lightgbm_tpu.compile.manager import load_executable
assert len(jax.devices()) == 8
exe = jax.jit(lambda x: 2.0 * x + 1.0).lower(
    jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
device_ids = [d.id for d in exe.runtime_executable().local_devices()]
assert device_ids == [jax.devices()[0].id]
store = ExecutableStore()
blob = serialize(exe)
assert store.save("k1", blob, device_ids)
assert store.keys() == ["k1"]
payload = store.load("k1")
# the store's contract: the payload round-trips byte-identically
assert payload[0] == blob[0] and payload[3] == device_ids
loaded = load_executable(payload)
x = jnp.arange(8, dtype=jnp.float32)
np.testing.assert_allclose(np.asarray(loaded(x)), 2.0 * np.arange(8) + 1.0)
print("ROUND TRIP OK")
"""


def test_store_serialize_deserialize_execute(tmp_path):
    """A store-loaded SINGLE-device executable must execute in a process
    that sees 8 devices: it is loaded onto the devices it was compiled
    for, not onto every backend device (jax 0.9 deserialize_and_load's
    default, which rejects the first call with "Expected args ... to
    have 8 shards"). Runs in a process of its own: XLA:CPU can fail to
    re-link a deserialized executable once other deserialized programs
    occupy the process (the late bad-blob case the manager tolerates)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", _ROUND_TRIP], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0 and "ROUND TRIP OK" in proc.stdout, \
        proc.stderr[-2000:]


def test_stored_blob_failing_at_first_call_is_dropped(aot_env):
    """A stored executable that loads but raises at its first call is a
    bad blob found late: warned about, dropped, recompiled — once. An
    executable compiled in this process that raises is a real failure
    (test_executable_call_failure_propagates)."""
    mgr = get_manager()
    entry = mgr.shared_entry("test/lateblob", {"v": 5},
                             lambda: jax.jit(lambda x: x * 3.0))
    x = jnp.ones((8,), jnp.float32)
    key = entry.key_for((x,), {})

    def broken(*a):
        raise RuntimeError("NOT_FOUND: Function fusion not found")

    mgr._remember(key, broken)
    with mgr._lock:
        mgr.unproven.add(key)
    np.testing.assert_allclose(np.asarray(entry(x)), 3.0)
    stats = mgr.snapshot()
    assert stats.get("store_load_errors", 0) == 1
    assert stats.get("cache_misses", 0) == 1
    assert key not in mgr.unproven
    np.testing.assert_allclose(np.asarray(entry(x)), 3.0)
    assert mgr.snapshot().get("store_load_errors", 0) == 1


def test_store_dirs_created_owner_only(aot_env):
    """Blobs are pickled, so the store directory is a code-execution
    surface: it must be created 0700 (module docstring TRUST BOUNDARY)."""
    store = ExecutableStore(str(aot_env))
    exe = jax.jit(lambda x: x + 1.0).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
    from jax.experimental.serialize_executable import serialize
    assert store.save("kperm", serialize(exe), [0])
    for d in (store.root, store.env_dir()):
        assert os.stat(d).st_mode & 0o777 == 0o700, d


def test_store_corrupt_blob_deleted(aot_env):
    store = ExecutableStore(str(aot_env))
    os.makedirs(store.env_dir(), exist_ok=True)
    with open(store.path("bad"), "wb") as fh:
        fh.write(b"this is not a pickled executable")
    with pytest.raises(CorruptBlobError):
        store.load("bad")
    assert not os.path.exists(store.path("bad"))
    assert store.load("bad") is None  # gone, not an error, on retry


def test_manager_corrupt_blob_falls_back_to_compile(aot_env):
    mgr = get_manager()
    if not mgr.aot_enabled:
        pytest.skip("AOT disabled in this environment")
    entry = mgr.shared_entry("test/affine", {"v": 1},
                             lambda: jax.jit(lambda x: x + 3.0))
    x = jnp.ones((16,), jnp.float32)
    key = entry.key_for((x,), {})
    os.makedirs(mgr.store.env_dir(), exist_ok=True)
    with open(mgr.store.path(key), "wb") as fh:
        fh.write(b"garbage" * 100)
    out = entry(x)
    np.testing.assert_allclose(np.asarray(out), 4.0)
    stats = mgr.snapshot()
    assert stats.get("store_load_errors", 0) >= 1
    assert stats.get("cache_misses", 0) >= 1
    # the corrupt file was replaced by the fresh compile's blob
    assert entry(x) is not None
    assert mgr.snapshot().get("cache_hits", 0) >= 1


def test_shared_entry_warmup_spec_precompiles(aot_env):
    from lightgbm_tpu.compile import warmup_entries
    mgr = get_manager()
    if not mgr.aot_enabled:
        pytest.skip("AOT disabled in this environment")
    entry = mgr.shared_entry("test/mul", {"v": 2},
                             lambda: jax.jit(lambda x: x * 5.0))
    entry.add_spec((jax.ShapeDtypeStruct((32,), jnp.float32),))
    summary = warmup_entries()
    assert summary["entries"] >= 1 and summary["compiled"] >= 1
    before = mgr.snapshot().get("cache_misses", 0)
    out = entry(jnp.ones((32,), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 5.0)
    assert mgr.snapshot().get("cache_misses", 0) == before  # warm hit


def test_compile_failure_propagates(aot_env, monkeypatch):
    """A compile error — Mosaic refusing a kernel — must surface with
    the compiler's message from the call AND from warmup; there is no
    plain-jit second attempt and no fallback marker to remember."""
    from lightgbm_tpu.compile import CompileManager, warmup_entries
    mgr = get_manager()

    def refuse(self, entry, key, args, statics):
        raise RuntimeError("Mosaic failed to compile TPU kernel: boom")

    monkeypatch.setattr(CompileManager, "_compile", refuse)
    entry = mgr.shared_entry("test/boom", {"v": 3},
                             lambda: jax.jit(lambda x: x + 1.0))
    x = jnp.ones((8,), jnp.float32)
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        entry(x)
    assert entry.key_for((x,), {}) not in mgr.executables
    entry.add_spec((jax.ShapeDtypeStruct((8,), jnp.float32),))
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        warmup_entries()


def test_executable_call_failure_propagates(aot_env):
    """An executable that raises when called is not retried through
    plain jit (its donated argument may already be consumed)."""
    mgr = get_manager()
    entry = mgr.shared_entry("test/callfail", {"v": 4},
                             lambda: jax.jit(lambda x: x + 1.0))
    x = jnp.ones((8,), jnp.float32)
    entry(x)
    key = entry.key_for((x,), {})

    def raising(*a):
        raise RuntimeError("executable rejected its arguments")

    mgr._remember(key, raising)
    with pytest.raises(RuntimeError, match="rejected its arguments"):
        entry(x)


# -- the acceptance check: zero recompiles on a same-bucket re-train ----

def _make_binary(n, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 12)).astype(np.float32)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(np.float32)
    return X, y


def test_second_same_bucket_train_compiles_nothing(aot_env, monkeypatch):
    """ISSUE acceptance: training a second same-process dataset whose
    row count lands in the same bucket performs ZERO XLA compilations —
    both the AOT miss counter and the plain-jit recompile counter stay
    flat while the hit counter moves."""
    monkeypatch.setenv("LGBM_TPU_BUCKET_MIN", "4096")
    reset_manager()
    reg = obs.MetricsRegistry()
    obs.activate(reg)
    try:
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1}
        X1, y1 = _make_binary(5000, 0)
        b1 = lgb.train(params, lgb.Dataset(X1, label=y1), num_boost_round=4)
        s0 = get_manager().snapshot()
        c0 = dict(reg.counters)

        X2, y2 = _make_binary(5100, 7)  # 5000 and 5100 both bucket to 5120
        b2 = lgb.train(params, lgb.Dataset(X2, label=y2), num_boost_round=4)
        s1 = get_manager().snapshot()
        c1 = dict(reg.counters)
    finally:
        obs.deactivate(reg)

    for ctr in ("cache_misses", "jit_compiles", "programs"):
        assert s1.get(ctr, 0) == s0.get(ctr, 0), \
            f"second train incremented {ctr}: {s0} -> {s1}"
        key = f"compile.{ctr}"
        assert c1.get(key, 0) == c0.get(key, 0)
    assert s1.get("cache_hits", 0) > s0.get("cache_hits", 0)
    # the compile-window budget (PERF_NOTES Round 10): one cold train is
    # a handful of distinct traced programs — the persistent iteration
    # program plus setup — not a per-leaf-capacity ladder. Measured 1 on
    # CPU; 6 leaves slack for backends that split the iteration.
    cold_programs = s0.get("programs", 0)
    assert 1 <= cold_programs <= 6, s0
    assert s0.get("lowering_s", 0) > 0
    # hlo_bytes sizes what the store persisted: fresh compiles only (a
    # warm jax persistent cache serves the rest, and they stay there)
    assert s0.get("hlo_bytes", 0) > 0 or s0.get("jax_cache_hits", 0) > 0
    # both models actually learned on their own data
    acc1 = np.mean((b1.predict(X1) > 0.5) == (y1 > 0))
    acc2 = np.mean((b2.predict(X2) > 0.5) == (y2 > 0))
    assert acc1 > 0.9 and acc2 > 0.9


def test_bucket_padding_does_not_change_predictions(aot_env, monkeypatch):
    """Same data trained with and without row bucketing produces the
    same model (pad lanes are masked by the traced row count)."""
    X, y = _make_binary(5000, 3)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1}

    monkeypatch.setenv("LGBM_TPU_SHAPE_BUCKETS", "0")
    reset_manager()
    p_exact = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=4).predict(X)

    monkeypatch.setenv("LGBM_TPU_SHAPE_BUCKETS", "1")
    monkeypatch.setenv("LGBM_TPU_BUCKET_MIN", "4096")
    reset_manager()
    p_bucket = lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=4).predict(X)
    np.testing.assert_allclose(p_exact, p_bucket, rtol=1e-5, atol=1e-6)


# -- device-side eval (satellite: early-stopping transfer guard) --------

def test_device_sum_matches_float64():
    """REVIEW fix: device metric reductions accumulate with f64-grade
    accuracy (compensated sum on f32 backends), so device eval cannot
    drift from the host float64 path enough to flip early stopping."""
    from lightgbm_tpu.metric.metrics import _sum_dev
    rng = np.random.default_rng(17)
    # non-multiple-of-lane length exercises the padding path; lognormal
    # spread + large N is where a naive f32 running sum drifts
    x = rng.lognormal(mean=0.0, sigma=2.0, size=200_003).astype(np.float32)
    ref = float(np.sum(x.astype(np.float64)))
    got = float(_sum_dev(jnp.asarray(x)))
    np.testing.assert_allclose(got, ref, rtol=2e-6)
    # cancellation-heavy input: alternating large +/- pairs plus a tail
    y = np.repeat([1e6, -1e6], 5000).astype(np.float32)
    y = np.concatenate([y, rng.normal(size=1001).astype(np.float32)])
    ref = float(np.sum(y.astype(np.float64)))
    got = float(_sum_dev(jnp.asarray(y)))
    np.testing.assert_allclose(got, ref, atol=1e-2)

def test_device_eval_transfers_scalars_only(aot_env):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(800, 10)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    ds = lgb.Dataset(X[:600], label=y[:600])
    vs = lgb.Dataset(X[600:], label=y[600:], reference=ds)
    params = {"objective": "binary", "metric": ["auc", "binary_logloss"],
              "num_leaves": 7, "verbose": -1}

    reg = obs.MetricsRegistry()
    obs.activate(reg)
    try:
        res_dev = {}
        lgb.train(dict(params), ds, num_boost_round=4, valid_sets=[vs],
                  valid_names=["v"], evals_result=res_dev,
                  verbose_eval=False, early_stopping_rounds=3)
        counters = dict(reg.counters)
    finally:
        obs.deactivate(reg)
    # the transfer guard: no [N]-sized score pull per iteration, only
    # 0-d metric scalars ride host<-device
    assert counters.get("eval.host_transfer_rows", 0) == 0, counters
    assert counters.get("eval.device_scalars", 0) > 0

    os.environ["LGBM_TPU_DEVICE_EVAL"] = "0"
    try:
        res_host = {}
        lgb.train(dict(params), ds, num_boost_round=4, valid_sets=[vs],
                  valid_names=["v"], evals_result=res_host,
                  verbose_eval=False, early_stopping_rounds=3)
    finally:
        del os.environ["LGBM_TPU_DEVICE_EVAL"]
    for m in ("auc", "binary_logloss"):
        np.testing.assert_allclose(res_dev["v"][m], res_host["v"][m],
                                   rtol=1e-5, atol=1e-6)


# -- warmup CLI (satellite: tier-1 smoke) -------------------------------

def test_warmup_cli_smoke(tmp_path):
    rng = np.random.default_rng(11)
    X = rng.normal(size=(300, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    data = tmp_path / "train.tsv"
    np.savetxt(data, np.column_stack([y, X]), delimiter="\t", fmt="%.6f")
    conf = tmp_path / "warm.conf"
    conf.write_text(f"data = {data}\n"
                    "objective = binary\n"
                    "num_leaves = 7\n")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               LGBM_TPU_AOT_MIN_COMPILE_S="0",
               PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "warmup",
         "--conf", str(conf)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout + proc.stderr
    assert "Warmup compiled" in out or "warmup is disabled" in out
    if "Warmup compiled" in out:
        store = ExecutableStore(str(tmp_path / "aot"))
        # at least one executable persisted for the next process
        blobs = []
        for sub in (os.listdir(store.root)
                    if os.path.isdir(store.root) else []):
            d = os.path.join(store.root, sub)
            blobs += [f for f in os.listdir(d) if f.endswith(".aotx")]
        assert blobs, "warmup persisted no executables"


def test_bench_sidecar_record_schema():
    """The BENCH_BIN63 sidecar record bench.py writes conforms to
    validate_bench_record (scripts/check_metrics_schema.py covers the
    file once a bench run produces it)."""
    rec = {"metric": "higgs_train_wallclock_bin63", "value": 100.0,
           "unit": "seconds", "vs_baseline": 1.06,
           "vs_baseline_with_compile": 0.9, "compile_s": 12.0,
           "rows": 1048576, "iters": 20, "note": "extrapolated"}
    assert obs.validate_bench_record(rec) == []
    assert obs.validate_bench_record(json.loads(json.dumps(rec))) == []


def test_only_what_an_iteration_dispatches_is_warmed_up(aot_env):
    """Background warm-up runs beside the first iterations, so it holds
    only the program they dispatch: a `fused/sync_scores` spec would be
    compiled (tens of seconds at 21M rows) inside the first iterations of
    a cold process, and no iteration needs it."""
    rng = np.random.RandomState(3)
    X = rng.randn(600, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1}
    g = lgb.Booster(params, lgb.Dataset(X, label=y))._gbdt._fused
    assert g.persistent_capable
    assert len(g._iter_entry.specs) == 1
    assert g._sync_entry.specs == [] and g._grow_entry.specs == []
