"""Planar layout + partition kernel tests.

The pallas kernel itself only runs on real TPU hardware; these tests
exercise the layout round-trip and the XLA reference partition on any
backend, and a numpy emulation pins the exact stream semantics the
kernel must reproduce (scripts/kernel_check.py runs kernel-vs-oracle
on the device).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import plane


def make_state(n=5000, g=11, seed=0, code_bits=8, tile=256):
    rng = np.random.RandomState(seed)
    hi = {4: 15, 8: 250, 16: 1000}[code_bits]
    codes = rng.randint(0, hi, size=(n, g)).astype(
        np.uint16 if code_bits == 16 else np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = rng.rand(n).astype(np.float32) + 0.5
    layout = plane.make_layout(g, code_bits, n, with_label=True,
                               with_score=True, tile=tile)
    cp = plane.build_codes_planes(jnp.asarray(codes), layout)
    data = plane.build_data(layout, cp, jnp.asarray(grad), jnp.asarray(hess),
                            label=jnp.asarray(grad * 2),
                            score=jnp.asarray(hess * 3))
    return layout, data, codes, grad, hess


def test_layout_roundtrip():
    layout, data, codes, grad, hess = make_state()
    got_codes, got_gh = plane.window_rowmajor(data, layout, 0,
                                              cap=layout.num_lanes)
    np.testing.assert_array_equal(np.asarray(got_codes)[:len(codes)], codes)
    np.testing.assert_allclose(np.asarray(got_gh)[:len(grad), 0], grad)
    np.testing.assert_allclose(np.asarray(got_gh)[:len(grad), 1], hess)
    np.testing.assert_allclose(
        np.asarray(plane.get_f32(data, layout.label, len(grad))), grad * 2)
    rid = np.asarray(data[layout.rowid])[:len(grad)]
    np.testing.assert_array_equal(rid, np.arange(len(grad)))


def test_layout_roundtrip_u16():
    layout, data, codes, grad, hess = make_state(code_bits=16)
    got_codes, _ = plane.window_rowmajor(data, layout, 0,
                                         cap=layout.num_lanes)
    np.testing.assert_array_equal(np.asarray(got_codes)[:len(codes)], codes)


def test_layout_roundtrip_4bit():
    """IS_4BIT analogue: two codes per byte (dense_bin.hpp:17-21)."""
    layout, data, codes, grad, hess = make_state(code_bits=4)
    got_codes, _ = plane.window_rowmajor(data, layout, 0,
                                         cap=layout.num_lanes)
    np.testing.assert_array_equal(np.asarray(got_codes)[:len(codes)], codes)


def test_partition_ref_4bit():
    layout, data, codes, grad, hess = make_state(code_bits=4)
    feat, thr = 3, 7
    rscal = plane.route_scalars(layout, feat, thr, 1, -1)
    cap = layout.num_lanes - layout.tile
    data2, nleft = plane.partition_ref(data, layout, 123, 4000, rscal,
                                       cap=cap)
    binval = codes[123:4123, feat]
    assert int(nleft) == int(np.sum(binval <= thr))


def np_partition(codes, layout, start, count, feat, thr, dl, miss, n):
    """Numpy emulation of the stream semantics over the FULL window the
    implementations use (tile-aligned superset of the leaf range)."""
    binval = codes[:, feat].astype(np.int64)
    go_left = binval <= thr
    if miss >= 0:
        go_left = np.where(binval == miss, bool(dl), go_left)
    pos = np.arange(len(codes))
    valid = (pos >= start) & (pos < start + count)
    order = np.concatenate([
        pos[pos < start], pos[valid & go_left],
        pos[valid & ~go_left], pos[pos >= start + count]])
    return order, int(np.sum(valid & go_left))


@pytest.mark.parametrize("start,count", [(0, 5000), (123, 1111), (4000, 997),
                                         (0, 1), (4999, 1)])
def test_partition_ref(start, count):
    layout, data, codes, grad, hess = make_state()
    feat, thr, dl, miss = 3, 117, 1, 249
    rscal = plane.route_scalars(layout, feat, thr, dl, miss)
    cap = layout.tile
    while cap < count:
        cap *= 4
    cap = min(cap, layout.num_lanes - layout.tile)
    data2, nleft = plane.partition_ref(data, layout, start, count, rscal,
                                       cap=cap)
    # emulate over the same aligned window
    tile = layout.tile
    nt = cap // tile + 1
    rs = min(start // tile, layout.num_lanes // tile - nt) * tile
    wl = nt * tile
    pad_codes = np.zeros((layout.num_lanes, codes.shape[1]), codes.dtype)
    pad_codes[:len(codes)] = codes
    wcodes = pad_codes[rs:rs + wl]
    order, want_nleft = np_partition(wcodes, layout, start - rs, count,
                                     feat, thr, dl, miss, len(codes))
    assert int(nleft) == want_nleft
    got_codes, got_gh = plane.window_rowmajor(data2, layout, rs, cap=wl)
    np.testing.assert_array_equal(np.asarray(got_codes), wcodes[order])
    # untouched outside the window
    full_codes, _ = plane.window_rowmajor(data2, layout, 0,
                                          cap=layout.num_lanes)
    np.testing.assert_array_equal(np.asarray(full_codes)[:rs], pad_codes[:rs])


def test_partition_ref_efb_decode():
    """EFB bundle decode inside routing matches decode_bins."""
    layout, data, codes, grad, hess = make_state()
    from lightgbm_tpu.io.efb import decode_bins
    g = codes.shape[1]
    group_of = jnp.asarray(np.arange(g) % 4, jnp.int32)
    offset_of = jnp.asarray(np.full(g, 10), jnp.int32)
    nslots_of = jnp.asarray(np.full(g, 100), jnp.int32)
    skip_of = jnp.asarray(np.full(g, 55), jnp.int32)
    efb = (group_of, offset_of, nslots_of, skip_of)
    feat = 6
    rscal = plane.route_scalars(layout, feat, 40, 0, -1, efb_dev=efb)
    data2, nleft = plane.partition_ref(data, layout, 0, len(codes), rscal,
                                       cap=layout.num_lanes - layout.tile)
    col = jnp.asarray(codes[:, int(group_of[feat])].astype(np.int32))
    want = np.sum(np.asarray(decode_bins(col, feat, efb)) <= 40)
    assert int(nleft) == want


def test_gh_update():
    layout, data, codes, grad, hess = make_state()
    g2 = jnp.asarray(grad * 7)
    h2 = jnp.asarray(hess * 5)
    data2 = plane.set_gh(data, layout, g2, h2)
    _, gh = plane.window_rowmajor(data2, layout, 0, cap=layout.num_lanes)
    np.testing.assert_allclose(np.asarray(gh)[:len(grad), 0], grad * 7,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gh)[:len(grad), 1], hess * 5,
                               rtol=1e-6)
    # codes untouched
    c2, _ = plane.window_rowmajor(data2, layout, 0, cap=layout.num_lanes)
    np.testing.assert_array_equal(np.asarray(c2)[:len(codes)], codes)


def test_host_pack_in_blocks_matches_one_block(monkeypatch):
    """The host pack transposes the rows in cache-sized blocks on a few
    threads; whatever the block, the planes are the one-block planes,
    the short last block included."""
    rng = np.random.RandomState(9)
    for n, g, bits, block_bytes in [(5000, 11, 8, 1 << 13),
                                    (3000, 9, 4, 1 << 11),
                                    (2048, 3, 16, 1 << 12)]:
        codes = rng.randint(0, 16 if bits == 4 else 200,
                            size=(n, g)).astype(np.uint16 if bits == 16
                                                else np.uint8)
        layout = plane.make_layout(g, bits, n, tile=512)
        want = plane.pack_codes_host(codes, layout)
        monkeypatch.setattr(plane, "PACK_BLOCK_BYTES", block_bytes)
        assert n > block_bytes // (layout.code_planes * 4)
        np.testing.assert_array_equal(plane.pack_codes_host(codes, layout),
                                      want)
        monkeypatch.undo()
