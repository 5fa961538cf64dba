"""Compile-window collapse: grid-parameterized kernels, the dynamic
(ladder-free) leaf paths, the content-addressed AOT store, and the
program-count accounting (docs/COMPILE_CACHE.md, PERF_NOTES Round 10).

The parity tests pin the load-bearing claim of the collapse: the
dynamic-grid (``cap=None``) planar kernels are BIT-IDENTICAL to the
static-cap ones — integer bin counts and f32 partial sums in the same
reduction order — and both agree with the XLA oracles, so the single
shared program can replace every ladder rung without a numerics review.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.compile import CorruptBlobError, ExecutableStore
from lightgbm_tpu.ops import plane
from lightgbm_tpu.ops.histogram import (histogram_planar_pallas,
                                        histogram_scatter)
from lightgbm_tpu.ops.partition import capacity_ladder


def _make_state(n, g, seed, code_bits=8, tile=512, max_code=250):
    rng = np.random.RandomState(seed)
    codes = rng.randint(0, max_code, size=(n, g)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = rng.rand(n).astype(np.float32)
    layout = plane.make_layout(g, code_bits, n, tile=tile)
    cp = plane.build_codes_planes(jnp.asarray(codes), layout)
    data = plane.build_data(layout, cp, jnp.asarray(grad), jnp.asarray(hess))
    return layout, data, codes


def _cap_for(layout, count, unit):
    cap = -(-max(count, 1) // unit) * unit
    return min(cap, layout.num_lanes - unit)


# -- static-cap grid vs dynamic grid vs the scatter oracle ---------------

@pytest.mark.parametrize("code_bits,num_bins,start,count,quant", [
    (4, 16, 200, 1500, False),   # 4-bit packed codes, interior window
    (8, 64, 0, 2048, False),     # full window
    (8, 255, 1800, 97, False),   # tail window, max radix
    (4, 16, 200, 1500, True),    # packed (qg<<16|qh) integer levels
])
def test_hist_grid_static_dynamic_oracle(code_bits, num_bins, start, count,
                                         quant):
    """The dynamic row-block grid (cap=None) must reproduce the
    static-cap grid EXACTLY, in both the f32 and the quantized integer
    accumulation modes, and both must be the scatter oracle's histogram
    of the window (exact for integer levels, f32 rounding otherwise)."""
    n, g = 2048, 7
    layout, data, codes = _make_state(n, g, seed=code_bits + num_bins,
                                      code_bits=code_bits,
                                      max_code=num_bins)
    sel = slice(start, start + count)
    if quant:
        # any int words will do: the kernels must agree bit-for-bit
        # with the integer scatter whatever the packed levels are
        rng = np.random.RandomState(7)
        words = rng.randint(0, 1 << 24, size=(layout.num_lanes,),
                            dtype=np.int32)
        data = data.at[layout.grad].set(jnp.asarray(words))
        grad, hess = words[sel] >> 16, words[sel] & 0xFFFF
    else:
        grad = np.asarray(plane.get_f32(data, layout.grad))[sel]
        hess = np.asarray(plane.get_f32(data, layout.hess))[sel]
    kw = dict(num_bins=num_bins, num_cols=g, code_bits=code_bits,
              grad_plane=layout.grad, rows_per_block=256, interpret=True,
              quant=quant)
    grid_static = np.asarray(histogram_planar_pallas(
        data, start, count, cap=_cap_for(layout, count, 256), **kw))
    grid_dyn = np.asarray(histogram_planar_pallas(
        data, jnp.int32(start), jnp.int32(count), cap=None, **kw))
    histogram_planar_pallas.clear_cache()
    np.testing.assert_array_equal(grid_dyn, grid_static)
    want = np.asarray(histogram_scatter(
        jnp.asarray(codes[sel]), jnp.asarray(grad), jnp.asarray(hess),
        num_bins))
    if quant:
        np.testing.assert_array_equal(grid_static, want)
    else:
        np.testing.assert_allclose(grid_static, want, rtol=1e-5, atol=1e-4)


def test_hist_grid_body_constant_size_in_width():
    """The compile-window claim itself: the traced program of the
    planar histogram has the SAME equation count at any column width —
    width only moves the grid bounds. This is
    the CPU-side proof that the wide-EFB Mosaic lowering cliff
    (scripts/wide_hbm_repro.py --lower-proof) cannot come back: there
    is nothing width-proportional left to lower."""
    def count_eqns(jaxpr):
        # recursive equation count; params may hold a jaxpr, a closed
        # jaxpr, or a tuple of them (cond branches)
        n = len(jaxpr.eqns)
        for e in jaxpr.eqns:
            for v in e.params.values():
                for w in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(w, "eqns"):
                        n += count_eqns(w)
                    elif hasattr(w, "jaxpr") and hasattr(w.jaxpr, "eqns"):
                        n += count_eqns(w.jaxpr)
        return n

    def eqns_at(cols):
        from lightgbm_tpu.ops.histogram import planar_grid_dims
        # 255-bin geometry: CC=4 chunks per super-chunk, the widest
        # chunk select the body holds
        Fc, SP, CC, CS = planar_grid_dims(255, 8, cols)
        gp = -(-CS * SP // 8) * 8
        data = jax.ShapeDtypeStruct((gp + 8, 2048), jnp.int32)

        def fn(d, start, cnt):
            return histogram_planar_pallas(
                d, start, cnt, num_bins=255, num_cols=cols, code_bits=8,
                grad_plane=gp, cap=None, rows_per_block=256,
                interpret=True)

        return count_eqns(jax.make_jaxpr(fn)(
            data, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).jaxpr)

    counts = [eqns_at(cols) for cols in (4, 32, 128)]
    assert counts[0] == counts[1] == counts[2], counts


# -- dynamic-grid partition vs static cap vs XLA reference --------------

@pytest.mark.parametrize("start,count", [(0, 4096), (1234, 2000), (17, 3)])
def test_partition_dynamic_matches_static_and_ref(start, count):
    layout, data, codes = _make_state(4096, 9, seed=start + count)
    rscal = plane.route_scalars(layout, 3, 117, 1, miss_bin=249)
    cap = _cap_for(layout, count, layout.tile)
    ref, nl_ref = plane.partition_ref(data, layout, start, count, rscal,
                                      cap=cap)
    stat, nl_stat = plane.partition_pallas(data, layout, start, count,
                                           rscal, cap=cap, interpret=True)
    dyn, nl_dyn = plane.partition_pallas(data, layout, jnp.int32(start),
                                         jnp.int32(count), rscal,
                                         cap=None, interpret=True)
    assert int(nl_ref) == int(nl_stat) == int(nl_dyn)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(stat))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(dyn))


def test_capacity_ladder_geometry():
    """The residual ladder (XLA-sliced ref paths only) stays geometric,
    capped by and always ending at the top capacity."""
    assert capacity_ladder(8192, 512, 4) == [512, 2048, 8192]
    assert capacity_ladder(512, 512, 4) == [512]
    assert capacity_ladder(1000, 512, 4) == [512, 1000]
    for caps in (capacity_ladder(1 << 20, 1024, 4),
                 capacity_ladder(12345, 512, 2)):
        assert caps == sorted(caps) and caps[-1] == max(caps)


# -- content-addressed store: GC + corrupt-manifest fallback ------------

@pytest.fixture
def store(tmp_path):
    return ExecutableStore(str(tmp_path / "aot"))


def _fake_triple(seed, nbytes=40_000):
    rng = np.random.RandomState(seed)
    return (rng.bytes(nbytes), None, None)


def test_store_content_addressed_dedup(store):
    """Identical triples under different cache keys share ONE blob (the
    payload excludes the key), so pod-syncing N aliases moves one file."""
    t = _fake_triple(1)
    assert store.save("k1", t, [0]) and store.save("k2", t, [0])
    blobs = [f for f in os.listdir(store.env_dir())
             if f.startswith("sha256-") and f.endswith(".aotx")]
    assert len(blobs) == 1
    assert sorted(store.keys()) == ["k1", "k2"]
    assert store.load("k1")[0] == t[0] and store.load("k2")[0] == t[0]


def test_store_gc_evicts_oldest_first(store):
    for i in range(5):
        assert store.save(f"k{i}", _fake_triple(i), [0])
    # age the blobs oldest-first by key order
    man = store._read_manifest()
    for i in range(5):
        os.utime(os.path.join(store.env_dir(), man[f"k{i}"]["blob"]),
                 (1_000_000 + i, 1_000_000 + i))
    # cap admits ~2 blobs of 40 kB
    assert store.gc(cap_bytes=90_000) >= 3
    assert store.load("k0") is None and store.load("k1") is None
    assert store.load("k4") is not None  # newest survives
    # manifest entries of collected blobs were dropped with them
    assert "k0" not in store._read_manifest()
    assert "k4" in store._read_manifest()


def test_store_gc_disabled_by_zero_cap(store, monkeypatch):
    monkeypatch.setenv("LGBM_TPU_AOT_CACHE_MB", "0")
    for i in range(3):
        assert store.save(f"k{i}", _fake_triple(i), [0])
    assert all(store.load(f"k{i}") is not None for i in range(3))


def test_store_corrupt_manifest_is_empty_not_fatal(store):
    assert store.save("k1", _fake_triple(1), [0])
    with open(store.manifest_path(), "w") as fh:
        fh.write("{ not json")
    # reads fall back to recompile (None), never crash
    assert store.load("k1") is None
    assert store.keys() == []
    # the next save rewrites a valid manifest and the store heals
    assert store.save("k2", _fake_triple(2), [0])
    assert store.load("k2") is not None
    assert "k2" in store._read_manifest()


def test_store_malformed_manifest_entry_recovers(store):
    assert store.save("k1", _fake_triple(1), [0])
    entries = store._read_manifest()
    entries["k1"] = {"typo": True}  # entry without a blob name
    store._write_manifest(entries)
    with pytest.raises(CorruptBlobError):
        store.load("k1")
    assert store.load("k1") is None  # entry dropped, clean miss now


def test_store_manifest_entry_without_blob_recovers(store):
    assert store.save("k1", _fake_triple(1), [0])
    os.unlink(os.path.join(store.env_dir(),
                           store._read_manifest()["k1"]["blob"]))
    with pytest.raises(CorruptBlobError):
        store.load("k1")
    assert store.load("k1") is None


def test_store_blob_digest_mismatch_recovers(store):
    """A partially-synced blob (name no longer matches content) must be
    detected before unpickling and fall back to recompile."""
    assert store.save("k1", _fake_triple(1), [0])
    blob = os.path.join(store.env_dir(), store._read_manifest()["k1"]["blob"])
    with open(blob, "r+b") as fh:
        fh.truncate(1000)
    with pytest.raises(CorruptBlobError, match="truncated or corrupt"):
        store.load("k1")
    assert store.load("k1") is None
