"""chip_smoke.py's stage functions at a tiny size on the CPU tier, with
the Pallas kernels selected (the dispatcher's TPU probe patched) and run
through the Pallas interpreter — the same code path the chip compiles
with Mosaic, minus the compiler."""
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.compile import reset_manager
from lightgbm_tpu.ops import histogram as H

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke as cs  # noqa: E402


@pytest.fixture
def on_kernels(monkeypatch, tmp_path):
    """Select the TPU kernels off the chip, with an isolated cache."""
    monkeypatch.setattr(H, "_use_tpu", lambda: True)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("LGBM_TPU_WARMUP", "0")
    reset_manager()
    yield
    reset_manager()


def test_generator_is_seeded_and_core_count_free():
    Xa, ya = cs.make_higgs_like(5000, seed=3)
    Xb, yb = cs.make_higgs_like(5000, seed=3)
    np.testing.assert_array_equal(Xa, Xb)
    np.testing.assert_array_equal(ya, yb)
    assert 0.3 < ya.mean() < 0.7
    assert not np.array_equal(Xa, cs.make_higgs_like(5000, seed=4)[0])


def test_stages_tiny_interpret(on_kernels):
    params = dict(cs.PARAMS, num_leaves=15, verbose=-1)
    X, y, Xte, yte = cs.stage_generate(16384, 2048)
    ds = cs.stage_construct(lgb, X, y, params)
    bst, cold_s, _ = cs.stage_train(lgb, ds, params, 2)
    cs.check_main_path(bst, params, 3)
    p, auc_main = cs.stage_predict(bst, Xte, yte)
    cs.stage_save_load(lgb, bst, Xte, p)
    cs.stage_kernel_parity(bst, 4096, interpret=True)
    auc_oracle, auc_slice = cs.stage_oracle(lgb, X, y, Xte, yte, params,
                                            8192, 3)
    assert auc_main > 0.7
    assert abs(auc_main - auc_oracle) <= cs.AUC_ORACLE_BAND
    assert abs(auc_slice - auc_oracle) <= cs.AUC_SAME_ROWS_BAND
    cs.stage_cache(lgb, ds, params, cold_s)
    cs.check_no_fallbacks()


def test_four_chip_stage_tiny_interpret(on_kernels):
    """tree_learner=data over the 8-device CPU mesh: the sharded state,
    one 1/n lane share per device, same root split as one device."""
    params = dict(cs.PARAMS, num_leaves=7, verbose=-1)
    X, y, Xte, yte = cs.stage_generate(32768, 1024)
    ds = cs.stage_construct(lgb, X, y, params)
    # CPU devices report no memory stats: the peak-bytes check is the
    # chip's
    root_n, auc_n = cs.stage_four_chip(lgb, ds, Xte, yte, params, 1,
                                       peak_factor=None)
    bst, _, _ = cs.stage_train(lgb, ds, params, 1)
    assert root_n == cs.root_split(bst)
    assert auc_n > 0.65


def test_refuses_to_run_without_a_tpu(capsys):
    assert cs.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
