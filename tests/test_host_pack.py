"""The code planes are packed on the host (ops/plane.py pack_codes_host):
bit for bit what the device program it replaced produced, and with no
device program at all — the eager reshape -> bitcast -> transpose cost
XLA:TPU ~14.7 s of compile a million rows, once per device (PERF.md §6,
PR 31). CPU, tiny data."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.ops import plane


def _device_pack(codes, layout, lanes):
    """The device program the host pack replaced, kept here as its
    oracle: pad the code bytes to whole words, bitcast, transpose."""
    n, g = codes.shape
    if layout.code_bits == 4:
        c = codes.astype(jnp.uint8)
        if g % 2:
            c = jnp.pad(c, ((0, 0), (0, 1)))
        b = (c[:, 0::2] & 15) | (c[:, 1::2] << 4)
    elif layout.code_bits == 8:
        b = codes.astype(jnp.uint8)
    else:
        b = jax.lax.bitcast_convert_type(
            codes.astype(jnp.uint16), jnp.uint8).reshape(n, g * 2)
    b = jnp.pad(b, ((0, lanes - n), (0, layout.code_planes * 4 - b.shape[1])))
    return jax.lax.bitcast_convert_type(
        b.reshape(lanes, layout.code_planes, 4), jnp.int32).T


@pytest.mark.parametrize("fill", [True, False], ids=["full", "padded"])
@pytest.mark.parametrize("cols", [1, 3, 4, 28, 29])
@pytest.mark.parametrize("bits", [4, 8, 16])
def test_host_pack_equals_the_device_pack_bit_for_bit(bits, cols, fill,
                                                      monkeypatch):
    # several blocks, the last one short, on more than one thread
    monkeypatch.setattr(plane, "PACK_BLOCK_BYTES", 1 << 12)
    n = 1000
    layout = plane.make_layout(cols, bits, n, tile=256)
    lanes = n if fill else layout.num_lanes
    dtype = np.uint16 if bits == 16 else np.uint8
    codes = np.random.RandomState(bits * 100 + cols).randint(
        0, 1 << bits, (n, cols)).astype(dtype)
    got = plane.pack_codes_host(codes, layout, lanes)
    assert got.dtype == np.int32 and got.shape == (layout.code_planes, lanes)
    np.testing.assert_array_equal(
        got, np.asarray(_device_pack(jnp.asarray(codes), layout, lanes)))
    if not fill:
        assert not got[:, n:].any()


def test_build_codes_planes_puts_the_planes_on_the_named_device():
    layout = plane.make_layout(5, 8, 300, tile=256)
    codes = np.random.RandomState(0).randint(0, 200, (300, 5)).astype(np.uint8)
    dev = jax.devices()[-1]
    cp = plane.build_codes_planes(codes, layout, device=dev)
    assert cp.devices() == {dev}
    assert cp.shape == (layout.code_planes, layout.num_lanes)
    np.testing.assert_array_equal(np.asarray(cp),
                                  plane.pack_codes_host(codes, layout))
    # no rows at all (a shard past the end of the table): zero planes
    assert not np.asarray(plane.build_codes_planes(codes[:0], layout)).any()


# ------------------------------------------ the stage compiles nothing

@pytest.fixture(scope="module")
def built():
    """Live count of the executables this process has built or fetched
    (the benchmark's own `window_compiles` counter)."""
    from benchmarks.harness import jaxmon
    return jaxmon.install()


def _train(params, n=3001, rounds=1):
    rng = np.random.RandomState(7)
    X = rng.randn(n, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.randn(n) > 0).astype(np.float32)
    params = dict(params, objective="binary", num_leaves=7, max_bin=31,
                  verbose=-1)
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=rounds,
                     keep_training_booster=True)


def test_the_listener_sees_an_executable_being_built(built):
    before = built.executables
    jax.block_until_ready(jnp.arange(12_347) * 3)   # a shape of its own
    assert built.executables > before


@pytest.mark.parametrize("params", [
    {},                                                  # persistent, serial
    {"boosting": "goss"},                                # per-tree, serial
    {"tree_learner": "data", "tpu_mesh_shape": [4]},     # persistent, sharded
    {"tree_learner": "data", "tpu_mesh_shape": [4],
     "boosting": "goss"},                                # per-tree, sharded
], ids=["serial", "serial-goss", "dp4", "dp4-goss"])
def test_the_pack_stage_builds_no_executable(params, built):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    g = _train(params)._gbdt._fused
    calls = obs.stage_seconds()["state/pack_codes"][1]
    before = built.executables
    if not g.is_multichip:
        g._codes_planes_dev = None
        cp = g.codes_planes()
    elif params.get("boosting") == "goss":
        g._cp_sh = None
        cp = g._codes_planes_sharded()
    else:
        # init_persistent_state's own pack stage
        sharding = jax.sharding.NamedSharding(
            g.mesh, jax.sharding.PartitionSpec(None, "data"))
        shape = (g.layout.num_planes, g.num_shards * g.layout.num_lanes)
        owned, cp = g._pack_codes_per_device(sharding, shape)
        assert [c.devices() for c in cp] == [{dev} for dev, _ in owned]
        assert sorted(d for _, d in owned) == list(range(g.num_shards))
    jax.block_until_ready(cp)
    assert built.executables == before
    assert obs.stage_seconds()["state/pack_codes"][1] == calls + 1


def test_restore_rebuilds_the_planes_on_the_host(built):
    """A checkpoint's lane order gathers the host bins and goes through
    the same pack: the restored state equals the live one plane for plane
    (gradients and hessians are dead between iterations)."""
    bst = _train({}, rounds=3)
    gb = bst._gbdt
    g, Ly = gb._fused, gb._fused.layout
    rowid, score_bits = g.persistent_lane_state(gb._fused_state)
    before = built.executables
    cp = plane.build_codes_planes(
        np.asarray(g.dataset.bins)[rowid[:g.actual_rows]], Ly)
    assert built.executables == before
    live = np.asarray(gb._fused_state)
    np.testing.assert_array_equal(np.asarray(cp)[:, :g.actual_rows],
                                  live[:Ly.code_planes, :g.actual_rows])
    restored = np.asarray(g.restore_persistent_state(rowid, score_bits))
    keep = [p for p in range(Ly.num_planes) if p not in (Ly.grad, Ly.hess)]
    np.testing.assert_array_equal(restored[keep][:, :g.actual_rows],
                                  live[keep][:, :g.actual_rows])
