"""Production Pallas kernels vs XLA oracles, in interpret mode on CPU.

The reference's core device-correctness check is GPU_DEBUG_COMPARE
(reference src/treelearner/gpu_tree_learner.cpp:992-1030): kernel-built
histograms compared against the host path. SURVEY §4 names it the
pattern to keep. These tests run the SAME kernel code the TPU executes
— partition_pallas, histogram_radix_pallas, histogram_planar_pallas —
under pallas interpret mode, against partition_ref / histogram_scatter.
On-device equivalents: scripts/kernel_check.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import plane
from lightgbm_tpu.ops.histogram import (histogram_planar_pallas,
                                        histogram_radix_pallas,
                                        histogram_scatter)


@pytest.fixture
def drop_executables():
    """An interpreted kernel is a large XLA:CPU executable, one per
    (kernel, layout, cap); each holds hundreds of memory mappings for
    the life of the process, and a test worker that keeps them all runs
    into the kernel's per-process limit (vm.max_map_count) files later."""
    yield
    plane.partition_pallas.clear_cache()
    plane.partition_pallas2.clear_cache()
    histogram_radix_pallas.clear_cache()


# ---------------------------------------------------------------------------
# partition_pallas vs partition_ref
# ---------------------------------------------------------------------------

def _make_state(n, g, seed, code_bits=8, tile=512, max_code=250,
                persistent=True, edit_codes=None):
    """`persistent`: label and score planes ride along, as in the
    persistent tier's state; the per-tree tier's has neither.
    `edit_codes(codes, rng)` may rewrite the drawn codes in place."""
    rng = np.random.RandomState(seed)
    codes = rng.randint(0, max_code, size=(n, g)).astype(np.uint8)
    if edit_codes is not None:
        edit_codes(codes, rng)
    grad = rng.randn(n).astype(np.float32)
    hess = rng.rand(n).astype(np.float32)
    layout = plane.make_layout(g, code_bits, n, with_label=persistent,
                               with_score=persistent, tile=tile)
    cp = plane.build_codes_planes(jnp.asarray(codes), layout)
    extra = dict(label=jnp.asarray(grad),
                 score=jnp.asarray(hess)) if persistent else {}
    data = plane.build_data(layout, cp, jnp.asarray(grad), jnp.asarray(hess),
                            **extra)
    return layout, data, codes


def _cap_for(layout, count):
    tile = layout.tile
    cap = -(-max(count, 1) // tile) * tile
    return min(cap, layout.num_lanes - tile)


WINDOWS = [
    (0, 4096, 3, 120, 0),        # full window
    (1234, 2000, 7, 60, 1),      # interior window, default-left
    (4000, 96, 0, 200, 0),       # tail window
    (17, 3, 5, 10, 1),           # tiny leaf
    (100, 3900, 3, 5, 0),        # nearly all right (boundary near off)
    (100, 3900, 3, 245, 0),      # nearly all left (boundary near end)
]

# (bundle columns, persistent, P). The cells' states by planes used:
# HIGGS 28 columns + label + score = 12 of 16; the per-tree tier's
# 10 bundles, no label or score = 6 of 8. And two with NO spare plane
# (the count is a multiple of 8 before padding): 8 of 8 and 16 of 16.
GEOMETRIES = {"full8": (12, True, 8), "higgs16": (28, True, 16),
              "pertree8": (10, False, 8), "full16": (44, True, 16)}

# every window on the first geometry with a static cap, as before; two
# windows on every other (geometry, cap mode)
CASES = [("full8", False, w) for w in WINDOWS] + [
    (geom, dynamic, w)
    for geom in GEOMETRIES for dynamic in (False, True)
    if (geom, dynamic) != ("full8", False)
    for w in (WINDOWS[1], WINDOWS[4])]


@pytest.mark.parametrize("kernel", [plane.partition_pallas,
                                    plane.partition_pallas2])
@pytest.mark.parametrize(
    "geom,dynamic,window", CASES,
    ids=[f"{g}-{'dynamic' if d else 'static'}-{w[0]}+{w[1]}f{w[2]}t{w[3]}"
         for g, d, w in CASES])
def test_partition_pallas_interpret_matches_ref(kernel, geom, dynamic,
                                                window, drop_executables):
    start, count, feat, thr, dl = window
    g, persistent, planes = GEOMETRIES[geom]
    layout, data, codes = _make_state(4096, g, seed=start + count,
                                      persistent=persistent)
    assert layout.num_planes == planes
    rscal = plane.route_scalars(layout, feat, thr, dl, miss_bin=249)
    cap = _cap_for(layout, count)
    ref, nl_ref = plane.partition_ref(data, layout, start, count, rscal,
                                      cap=cap)
    # cap=None: the grow loop's dynamic grid over the whole lane extent
    got, nl_got = kernel(data, layout, start, count, rscal,
                         cap=None if dynamic else cap, interpret=True)
    assert int(nl_ref) == int(nl_got)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    # independent semantic check against the raw codes: rows in
    # [start, start+nleft) must all satisfy the split predicate
    rowids = np.asarray(got[layout.rowid])
    window = rowids[start:start + count]
    code = codes[window, feat]
    go_left = np.where(code == 249, bool(dl), code <= thr)
    nl = int(nl_got)
    assert go_left[:nl].all() and not go_left[nl:].any()


# Windows of >= 6 tiles whose kept counts per tile are GIVEN, so the
# carry length of both streams takes the values that matter: (start,
# count, lefts of the window's rows in each 512-lane tile it touches).
# With start = 0 the L stream keeps a tile's lefts and the R stream its
# 512 - lefts, so after each tile the carries are (L, R) = (1, 127),
# (127, 1), (1, 127) by a wrap past 128 on both (127 + 130 = 257,
# 1 + 382 = 383), (1, 127) with nothing / a whole tile kept (no advance
# / a wrap from 127), (1, 127) the other way round, (0, 0) by exact
# fills, then (127, 1). The second window starts inside tile 0 and ends
# inside tile 7: the 385 pre-window rows ride the L stream (carry 1
# before the first left), the tail rows the R stream, whose carry runs
# 127, 126, 127, 127, 126, 127, 124.
CARRY_WINDOWS = {
    "aligned": (0, 4096, [1, 126, 130, 0, 512, 255, 127, 384]),
    "offset": (385, 3500, [0, 1, 127, 128, 129, 511, 3, 300]),
}
CARRY_FEAT, CARRY_THR = 2, 100


def _lefts_per_tile(start, count, lefts, tile=512):
    """edit_codes for `_make_state`: of the window's rows in the t-th
    tile it touches, exactly lefts[t] (seeded positions) go left under
    CARRY_FEAT <= CARRY_THR."""
    def edit(codes, rng):
        for t, nl in enumerate(lefts):
            lo = max(start, (start // tile + t) * tile)
            hi = min(start + count, (start // tile + t + 1) * tile)
            assert 0 <= nl <= hi - lo, (t, nl, lo, hi)
            col = rng.randint(CARRY_THR + 1, 249, size=hi - lo)
            col[rng.permutation(hi - lo)[:nl]] = rng.randint(
                0, CARRY_THR + 1, size=nl)
            codes[lo:hi, CARRY_FEAT] = col
        assert hi == start + count
    return edit


@pytest.mark.parametrize("kernel", [plane.partition_pallas,
                                    plane.partition_pallas2])
@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
@pytest.mark.parametrize("geom", ["higgs16", "pertree8"])
@pytest.mark.parametrize("window", list(CARRY_WINDOWS))
def test_partition_pallas_interpret_carry_lengths(kernel, dynamic, geom,
                                                  window, drop_executables):
    """Both kernels at the cells' plane counts, static `cap` and
    `cap=None`, on windows whose tiles drive the streams' carry through
    0, 1, 127, a wrap past 128, a tile that advances nothing and one
    that is kept whole (CARRY_WINDOWS)."""
    start, count, lefts = CARRY_WINDOWS[window]
    edit = _lefts_per_tile(start, count, lefts)
    g, persistent, planes = GEOMETRIES[geom]
    layout, data, codes = _make_state(4096, g, seed=len(lefts),
                                      persistent=persistent, edit_codes=edit)
    assert layout.num_planes == planes and count >= 6 * layout.tile
    rscal = plane.route_scalars(layout, CARRY_FEAT, CARRY_THR, 0,
                                miss_bin=249)
    cap = _cap_for(layout, count)
    ref, nl_ref = plane.partition_ref(data, layout, start, count, rscal,
                                      cap=cap)
    got, nl_got = kernel(data, layout, start, count, rscal,
                         cap=None if dynamic else cap, interpret=True)
    assert int(nl_ref) == int(nl_got) == sum(lefts)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    rowids = np.asarray(got[layout.rowid])[start:start + count]
    go_left = codes[rowids, CARRY_FEAT] <= CARRY_THR
    assert go_left[:sum(lefts)].all() and not go_left[sum(lefts):].any()
    # stable on both sides (the input's row ids were iota)
    assert (np.diff(rowids[:sum(lefts)]) > 0).all()
    assert (np.diff(rowids[sum(lefts):]) > 0).all()


def test_partition_pallas_interpret_categorical_bitset():
    layout, data, codes = _make_state(2048, 6, seed=11)
    bin_set = {3, 17, 42, 128, 200}
    bitset = np.zeros(plane.CAT_WORDS, dtype=np.uint32)
    for b in bin_set:
        bitset[b // 32] |= np.uint32(1 << (b % 32))
    rscal = plane.route_scalars(layout, 2, 0, 0, miss_bin=-1, is_cat=1,
                                cat_bitset=bitset.astype(np.int32))
    cap = _cap_for(layout, 2048)
    ref, nl_ref = plane.partition_ref(data, layout, 0, 2048, rscal, cap=cap)
    got, nl_got = plane.partition_pallas(data, layout, 0, 2048, rscal,
                                         cap=cap, interpret=True)
    assert int(nl_ref) == int(nl_got)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    rowids = np.asarray(got[layout.rowid])[:2048]
    in_set = np.isin(codes[rowids, 2], list(bin_set))
    nl = int(nl_got)
    assert in_set[:nl].all() and not in_set[nl:].any()


def test_partition_pallas_interpret_4bit_packing():
    """4-bit packed codes (dense_bin.hpp:17-21 IS_4BIT analogue)."""
    layout, data, codes = _make_state(2048, 9, seed=5, code_bits=4,
                                      max_code=16)
    rscal = plane.route_scalars(layout, 4, 7, 0, miss_bin=15)
    cap = _cap_for(layout, 1500)
    ref, nl_ref = plane.partition_ref(data, layout, 300, 1500, rscal,
                                      cap=cap)
    got, nl_got = plane.partition_pallas(data, layout, 300, 1500, rscal,
                                         cap=cap, interpret=True)
    assert int(nl_ref) == int(nl_got)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("kernel", [plane.partition_pallas,
                                    plane.partition_pallas2])
def test_partition_pallas_interpret_stability(kernel):
    """The partition must be STABLE (relative order preserved on both
    sides) — the leaf-window invariants of the fused grower depend on
    it, like the reference's ParallelPartitionRunner stable partition
    (utils/threading.h:80)."""
    layout, data, codes = _make_state(1024, 4, seed=3)
    rscal = plane.route_scalars(layout, 1, 100, 0, miss_bin=249)
    cap = _cap_for(layout, 1024)
    got, nl = kernel(data, layout, 0, 1024, rscal, cap=cap, interpret=True)
    rowids = np.asarray(got[layout.rowid])[:1024]
    nl = int(nl)
    # stable: each side's rowids strictly increasing (input was iota)
    assert (np.diff(rowids[:nl]) > 0).all()
    assert (np.diff(rowids[nl:]) > 0).all()


# ---------------------------------------------------------------------------
# _compact_streams (the kernels' one compaction primitive) vs numpy
# ---------------------------------------------------------------------------

def _compact(x, keeps, carries=None):
    """The helper as a plain function: `jnp.roll` for `pltpu.roll`,
    batched over the leading axis of every keep row; one carry length
    per stream (0 when not given)."""
    carries = [0] * len(keeps) if carries is None else carries
    fn = jax.jit(jax.vmap(
        lambda *ks: plane._compact_streams(
            x, [k[None] for k in ks], [jnp.int32(c) for c in carries],
            roll=jnp.roll)))
    comps, counts = fn(*(jnp.asarray(k) for k in keeps))
    return [np.asarray(c) for c in comps], [np.asarray(c) for c in counts]


def _masks(s, n, seed):
    """n seeded keep masks of every density, then the edge set: all,
    none, one lane, alternating (both phases), first / last lane only,
    all but the first / last."""
    rng = np.random.RandomState(seed)
    m = (rng.rand(n, s) < rng.rand(n, 1)).astype(np.int32)
    lane = np.arange(s)
    edges = [np.ones(s), np.zeros(s), lane == s // 3, lane % 2 == 0,
             lane % 2 == 1, lane == 0, lane == s - 1, lane != 0,
             lane != s - 1]
    return np.concatenate([m, np.asarray(edges, np.int32)])


def _assert_stable_partition(x, keep, comp, count, carry=0):
    """Every mask's kept lanes of x, in order, lie in its compacted
    [P, S + 128] copy from lane `carry` on (every other lane is
    garbage)."""
    s = x.shape[1]
    assert comp.shape[1:] == (x.shape[0], s + plane.LANE)
    np.testing.assert_array_equal(count, keep.sum(axis=1))
    # the kept lanes first, in order: a stable argsort of "dropped"
    want = x[:, np.argsort(1 - keep, axis=1, kind="stable")]
    live = (np.arange(s) < count[:, None])[:, None, :]
    np.testing.assert_array_equal(
        np.where(live, comp[:, :, carry:carry + s], 0),
        np.where(live, want.transpose(1, 0, 2), 0))


_X128 = np.random.RandomState(7).randint(
    -2 ** 31, 2 ** 31, size=(16, 128), dtype=np.int64).astype(np.int32)

CARRIES = (0, 1, 63, 127)       # a stream's carry length is in [0, 128)
CARRY_PAIRS = [(a, b) for a in CARRIES for b in CARRIES]
CARRY_PAIRS_FEW = [(0, 0), (1, 127), (63, 1), (127, 63)]


@pytest.mark.parametrize("lanes,streams,carries", [
    *[(128, "one", (c,)) for c in CARRIES],
    *[(128, "complement", cc) for cc in CARRY_PAIRS],
    *[(128, "independent", cc) for cc in CARRY_PAIRS],
    *[(512, "complement", cc) for cc in CARRY_PAIRS_FEW]])
def test_compact_streams_matches_numpy_stable_partition(lanes, streams,
                                                        carries):
    """K = 1; K = 2 as the v2 kernel stacks it (a row and its
    complement); K = 2 with unrelated rows; every stream at every carry
    offset. 512 lanes reach the rounds that shift by whole 128-lane
    columns."""
    x = np.tile(_X128, (1, lanes // 128)) + np.arange(lanes, dtype=np.int32)
    keep = _masks(lanes, 2000, seed=1)
    keeps = {"one": [keep], "complement": [keep, 1 - keep],
             "independent": [keep, _masks(lanes, 2000, seed=2)]}[streams]
    comps, counts = _compact(x, keeps, carries)
    assert len(comps) == len(counts) == len(keeps)
    for k, comp, count, c in zip(keeps, comps, counts, carries):
        _assert_stable_partition(x, k, comp, count, c)


@pytest.mark.parametrize("carries", CARRY_PAIRS_FEW)
def test_compact_streams_two_rows_equal_two_calls(carries):
    """Stacking changes no lane of either stream, garbage included."""
    keep_l = _masks(128, 2000, seed=3)
    keep_r = 1 - keep_l
    (both_l, both_r), (kl, kr) = _compact(_X128, [keep_l, keep_r], carries)
    (one_l,), (k1,) = _compact(_X128, [keep_l], carries[:1])
    (one_r,), (k2,) = _compact(_X128, [keep_r], carries[1:])
    np.testing.assert_array_equal(both_l, one_l)
    np.testing.assert_array_equal(both_r, one_r)
    np.testing.assert_array_equal(kl, k1)
    np.testing.assert_array_equal(kr, k2)


@pytest.mark.parametrize("carries", CARRY_PAIRS)
def test_compact_streams_exhaustive_on_16_lanes(carries):
    """The LSB-first network behind its 128-lane lead is a stable
    compaction to the carry offset for EVERY keep mask of a 16-lane
    tile (all 65,536), on both stacked streams."""
    x = _X128[:8, :16]
    keep = (np.arange(1 << 16)[:, None] >> np.arange(16) & 1).astype(np.int32)
    (comp_l, comp_r), (kl, kr) = _compact(x, [keep, 1 - keep], carries)
    _assert_stable_partition(x, keep, comp_l, kl, carries[0])
    _assert_stable_partition(x, 1 - keep, comp_r, kr, carries[1])


def _network_pr28(x, keep, subtract):
    """The width-S network as it shipped before the carry rode in it
    (PR 28; with `subtract` as before PR 28, when a moved shift had its
    bit b cleared): kept lanes from lane 0 on."""
    s = keep.shape[0]
    keep = keep[None]
    lane = jnp.arange(s, dtype=jnp.int32)[None]
    ranks = jnp.cumsum(keep, axis=1)
    sh = jnp.where(keep == 1, lane - (ranks - 1), 0)
    comp = jnp.asarray(x)
    b = 1
    while b < s:
        moved = jnp.roll(sh, s - b, 1)
        m1 = (moved & b) != 0
        comp = jnp.where(m1, jnp.roll(comp, s - b, 1), comp)
        sh = jnp.where(m1, moved - b if subtract else moved, sh)
        b *= 2
    return comp


@pytest.mark.parametrize("subtract", [False, True],
                         ids=["pr28", "with_subtract"])
def test_compact_streams_zero_carry_is_the_width_s_network(subtract):
    """With the carries at 0 and the lead cut off it is the function it
    replaced, garbage lanes aside; and that one routed every kept lane
    the same with and without the `- b` on a moved shift (no later
    round tests a bit at or below b)."""
    keep = _masks(128, 2000, seed=4)
    want = np.asarray(jax.jit(jax.vmap(
        lambda k: _network_pr28(_X128, k, subtract)))(jnp.asarray(keep)))
    for keeps in ([keep], [keep, 1 - keep]):
        comps, counts = _compact(_X128, keeps)
        live = (np.arange(128) < counts[0][:, None])[:, None, :]
        np.testing.assert_array_equal(
            np.where(live, comps[0][:, :, :128], 0), np.where(live, want, 0))


# ---------------------------------------------------------------------------
# histogram_radix_pallas vs histogram_scatter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,f,num_bins", [
    (1500, 11, 16), (1500, 11, 63), (1500, 11, 255),
    (1000, 5, 256),     # a full 256-bin radix
    (777, 28, 63),      # the HIGGS width
    (311, 3, 255),      # fewer rows than one block, fewer columns than a chunk
    (500, 7, 16),       # the same at a chunk of 32 columns
    (700, 5, 32),       # one block and a tail that is no power of two
    (1000, 6, 64),
])
def test_histogram_radix_pallas_interpret_matches_scatter(
        r, f, num_bins, drop_executables):
    rng = np.random.RandomState(num_bins)
    bins = rng.randint(0, num_bins, size=(r, f)).astype(np.uint8)
    grad = rng.randn(r).astype(np.float32)
    hess = rng.rand(r).astype(np.float32)
    want = np.asarray(histogram_scatter(jnp.asarray(bins), jnp.asarray(grad),
                                        jnp.asarray(hess), num_bins))
    got = np.asarray(histogram_radix_pallas(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), num_bins,
        interpret=True))       # the block H.histogram dispatches: 512 rows
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_histogram_radix_pallas_interpret_bf16_close():
    """bfloat16 input mode (the default tpu_hist_dtype): inputs rounded
    to 8-bit mantissa, accumulation still f32 — totals must stay within
    bf16 rounding of the exact answer (reference gpu_use_dp=false
    single-precision analogue, GPU-Performance.rst accuracy tables)."""
    rng = np.random.RandomState(0)
    r, f, num_bins = 2000, 8, 64
    bins = rng.randint(0, num_bins, size=(r, f)).astype(np.uint8)
    grad = rng.randn(r).astype(np.float32)
    hess = rng.rand(r).astype(np.float32)
    want = np.asarray(histogram_scatter(jnp.asarray(bins), jnp.asarray(grad),
                                        jnp.asarray(hess), num_bins))
    got = np.asarray(histogram_radix_pallas(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), num_bins,
        dtype=jnp.bfloat16, rows_per_block=256, interpret=True))
    # per-bin relative error bounded by bf16 eps times bin occupancy
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=0.3)
    # totals (sums over bins) must agree to the same tolerance
    np.testing.assert_allclose(got.sum(axis=1), want.sum(axis=1),
                               rtol=1e-2, atol=0.5)


# ---------------------------------------------------------------------------
# histogram_planar_pallas vs histogram_scatter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("code_bits,num_bins", [(8, 255), (8, 64), (4, 16)])
def test_histogram_planar_pallas_interpret_matches_scatter(code_bits,
                                                           num_bins):
    n, g = 2048, 7
    layout, data, codes = _make_state(n, g, seed=code_bits + num_bins,
                                      code_bits=code_bits,
                                      max_code=num_bins)
    rng = np.random.RandomState(1)
    grad = np.asarray(plane.get_f32(data, layout.grad))[:n]
    hess = np.asarray(plane.get_f32(data, layout.hess))[:n]
    start, count = 200, 1500
    cap = _cap_for(layout, count)
    got = np.asarray(histogram_planar_pallas(
        data, start, count, num_bins=num_bins, num_cols=g,
        code_bits=code_bits, grad_plane=layout.grad, cap=cap,
        rows_per_block=256, interpret=True))
    sel = slice(start, start + count)
    want = np.asarray(histogram_scatter(
        jnp.asarray(codes[sel]), jnp.asarray(grad[sel]),
        jnp.asarray(hess[sel]), num_bins))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# traverse_planes_pallas vs traverse_planes_ref
# ---------------------------------------------------------------------------

def _kernel_check():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import kernel_check
    return kernel_check


# features 0..5 in 2 bundle columns, per feature (column, offset, slots,
# skip bin): skip bins at the band's start, inside it and past it. Codes
# below 64 cover each member's band, the other members' (out of band:
# the skip bin) and code 0
EFB6 = [(0, 1, 9, 0), (0, 10, 9, 4), (0, 19, 30, 30),
        (1, 1, 20, 7), (1, 21, 20, 0), (1, 41, 5, 2)]

# name -> (rows, columns, code bits, highest code + 1, leaves, splits,
#          features split on, what else)
TRAVERSE_CASES = {
    # 4 columns a plane at 8 bits: column 1 in the first, 10 in the last
    "numerical_8bit": (5000, 11, 8, 256, 31, 30, [1, 10], {}),
    # 8 columns a plane at 4 bits, 20 columns in 3 planes
    "numerical_4bit": (5000, 20, 4, 16, 31, 30, [3, 19], {}),
    # 2 columns a plane at 16 bits, 5 columns in 3 planes
    "numerical_16bit": (5000, 5, 16, 60000, 31, 30, [0, 4], {}),
    "efb_bundled": (5000, 2, 8, 64, 31, 30, list(range(6)),
                    {"efb": EFB6, "max_bin": 31}),
    # codes 0..7 with bin 7 missing: a fifth of the rows go by
    # default_left, which random_tree draws both ways
    "missing_bin": (5000, 6, 8, 8, 31, 30, list(range(6)),
                    {"miss_bin": 7, "max_bin": 7}),
    "missing_bin_bundled": (5000, 2, 8, 64, 31, 30, list(range(6)),
                            {"efb": EFB6, "miss_bin": 3, "max_bin": 20}),
    # bits drawn in all eight words of the set; numerical splits between
    "categorical": (5000, 6, 8, 256, 31, 30, list(range(6)),
                    {"cat": [1, 2, 5]}),
    "categorical_bundled": (5000, 2, 8, 64, 15, 14, list(range(6)),
                            {"efb": EFB6, "cat": [0, 2, 4]}),
    "stump": (5000, 6, 8, 256, 31, 0, [0], {}),
    "two_leaves": (5000, 6, 8, 256, 2, 1, [3], {}),
    "stopped_short": (5000, 6, 8, 256, 31, 9, list(range(6)), {}),
    # every split of slot 0: 254 left children in a chain
    "left_chain": (3000, 6, 8, 256, 255, 254, list(range(6)),
                   {"chain": True}),
    # 360,448 lanes: a whole tile of 2,048 x 128, a part of a second,
    # and 60,448 pad lanes past the rows
    "two_tiles_and_pad_lanes": (300_000, 6, 8, 256, 15, 14,
                                list(range(6)), {}),
    # 137 columns in 35 planes (msltr137): the tile gives up rows to fit
    # VMEM, 256 x 128 lanes; a whole tile and a part of a second
    "many_planes_smaller_tile": (40_000, 137, 8, 256, 31, 30,
                                 [0, 68, 136], {}),
    # 525 planes: the tile is smaller than a chunk, and the chunk follows
    "planes_past_a_chunk": (3000, 2100, 8, 256, 15, 14, [5, 2099], {}),
}


def _host_leaves(ta, bins):
    """Leaf of every row by walking the tree's nodes (numerical splits,
    no missing bin) over the decoded bins."""
    node = np.zeros(len(bins), np.int64) if int(ta["n_leaves"]) > 1 \
        else np.full(len(bins), -1, np.int64)
    rows = np.flatnonzero(node >= 0)
    feat, thr = np.asarray(ta["split_feature"]), np.asarray(
        ta["threshold_bin"])
    left, right = np.asarray(ta["left_child"]), np.asarray(ta["right_child"])
    while rows.size:
        at = node[rows]
        node[rows] = np.where(bins[rows, feat[at]] <= thr[at], left[at],
                              right[at])
        rows = rows[node[rows] >= 0]
    return ~node


@pytest.mark.parametrize("case", sorted(TRAVERSE_CASES))
def test_traverse_planes_pallas_interpret_matches_ref(case):
    K = _kernel_check()
    n, cols, bits, codes_hi, leaves, splits, features, extra = \
        TRAVERSE_CASES[case]
    rng = np.random.RandomState(len(case))
    codes = rng.randint(0, codes_hi, size=(n, cols)).astype(
        np.uint16 if bits == 16 else np.uint8)
    layout = plane.make_layout(cols, bits, n)
    cp = plane.build_codes_planes(jnp.asarray(codes), layout)
    efb = tuple(jnp.asarray(t, jnp.int32) for t in zip(*extra["efb"])) \
        if "efb" in extra else None
    num_features = len(extra["efb"]) if efb else cols
    ta = K.random_tree(rng, leaves, splits, features,
                       extra.get("max_bin", codes_hi),
                       cat_features=extra.get("cat", ()),
                       chain=extra.get("chain", False))
    miss = jnp.full(num_features, extra.get("miss_bin", -1), jnp.int32)
    got = K.check_traverse(cp, layout, ta, miss, efb, interpret=True)
    assert got["ok"], got
    assert got["leaves_seen"] > min(splits, 3), got
    if not (efb or extra.get("cat") or "miss_bin" in extra):
        leaf = np.asarray(plane.traverse_planes_pallas(
            cp, plane.traverse_table(layout, ta, miss), interpret=True))
        np.testing.assert_array_equal(leaf[:n], _host_leaves(ta, codes))
        # pad lanes hold code 0 in every column
        assert (leaf[n:] == _host_leaves(ta, np.zeros((1, cols), int))).all()
