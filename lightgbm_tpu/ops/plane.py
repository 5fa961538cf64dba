"""Planar training-row layout + the Pallas stable-partition kernel.

This is the TPU answer to the reference's DataPartition::Split
(src/treelearner/data_partition.hpp:72) — the op that dominated
training time in every gather/scatter/sort formulation we measured:
TPU per-row access tolls are ~10 ns/row below ~2M-row tables and
~37-140 ns/row above, so ANY permutation applied row-by-row costs
seconds per iteration at HIGGS scale. The redesign moves rows in
S-lane blocks with DMAs and does the within-block reshuffle in
registers, so no primitive ever pays a per-row toll:

- **Planar layout**: the training state is ONE `[P, R]` int32 array,
  lane-major (row r = lane r). Planes: bin-code bytes (4 packed per
  plane), then grad / hess / label / score / row-id as f32/i32
  bitcasts. Rationale: (a) Mosaic DMA requires tile-aligned slice
  shapes — `[P, S]` blocks with P a multiple of 8 qualify, while
  row-major `[S, W<128]` blocks never can; (b) the radix histogram
  kernel is already lane-major ("NT orientation"); (c) HBM stores
  arrays unpadded, so narrow planes cost exactly their bytes.
- **Stable partition as a carry stream**: grid pass 0 emits
  [pre-window rows | left rows], pass 1 continues with
  [right rows | tail rows] — one contiguous output stream. Each tile
  compacts its kept lanes in-register by ONE plan for all the streams
  it feeds (`_compact_streams`): the streams' keep rows stacked in the
  sublanes of one array, one prefix sum for their ranks, then an
  LSB-first binary shift network — log2(S + 128) rounds of
  `pltpu.roll` + select in which the stacked shifts are rolled, masked
  and selected once for all streams and never decremented
  (tests/test_kernels.py holds it to numpy's stable partition,
  exhaustively at 16 lanes). The length c < 128 of the carry the
  previous step left over rides in the shifts, behind a static
  128-lane lead, so the kept lanes come out of the network at lanes
  [c, c + k) of a `[P, S+128]` chunk with no roll after it;
  `_emit_stream` selects the carry's lanes into the first column,
  stages the chunk once and DMAs it to a 128-aligned offset, and
  reads the next carry as one aligned column of what it staged.
  Consecutive chunks overlap by design (the garbage tail of chunk k
  is rewritten as the carry head of chunk k+1), so writes are
  serialized DMA k.wait -> DMA k+1.start while compute overlaps.
- **Routing in-kernel**: the split column is extracted from the code
  planes by a masked sublane reduction + byte shift (no gather), EFB
  bundle decode (io/efb.py:194) and the missing-bin decision
  (bin.h threshold semantics) are elementwise with prefetched
  scalars.

- **The all-lane traverse** (row sampling: every row's leaf, out-of-bag
  ones included) reuses that routing with the loop nest the other way
  round: `traverse_planes_pallas` holds a lane tile of the code planes
  in VMEM and replays ALL of a tree's splits on it, one pass over the
  planes a tree.

The XLA reference implementations (`partition_ref`,
`traverse_planes_ref`) are the portable CPU path and the correctness
oracles for the kernels.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

LANE = 128          # TPU lane count; DMA offsets/sizes must align to it
# base lane tile of a planar layout: the partition kernel is
# per-step-overhead bound below it, and 8192 gains nothing (bigger
# small-leaf windows offset the fewer steps; docs/PERF_NOTES.md)
DEF_TILE = 4096
# ceiling for the per-ladder-branch processing tile (see fused.py
# _branch_tile): the partition/histogram kernels are per-STEP-overhead
# bound (~4 us/step measured, scripts/part_micro.py), so large leaf
# windows process in tiles up to this size
MAX_TILE = 32768
# scoped-VMEM budget for the partition kernels' staging buffers (the
# hardware limit is 16 MB; leave headroom for the pipeline's own
# double-buffered block)
PART_VMEM_BUDGET = 13_000_000


def partition_vmem_bytes_at(P: int, S: int, method: str = "pallas2") -> int:
    """Scoped-VMEM bytes a partition kernel holds at once for plane
    count P and processing tile S: the staging/carry/output buffers all
    span the full plane count, so wide-EFB states (hundreds of code
    planes) can exceed the 16 MB scoped limit. Widths are CALIBRATED to
    compiler-reported scoped allocations (Mosaic multi-buffers the
    pipeline block on top of the declared scratch): at P=152, S=4096
    the compiler reports 21.97 MB for v2 (~8.8*S lane-widths; re-read
    in PR 32, compiled for a described v5e) and at P=200, S=4096
    19.51 MB for v1 (~6*S; it fits the limit at P=152 since PR 32,
    where it read 18.25 MB before); a margin is added on both."""
    width = 16 * S if method == "pallas2" else 8 * S
    return P * width * 4


def partition_vmem_bytes(layout: "PlaneLayout", method: str = "pallas2") -> int:
    return partition_vmem_bytes_at(layout.num_planes, layout.tile, method)


class PlaneLayout(NamedTuple):
    """Plane indices of the [P, R] int32 training-state array."""
    num_cols: int        # G bundle columns
    code_bits: int       # bits per bin code (4, 8 or 16) — 4-bit is the
                         # reference's DenseBin IS_4BIT packing
                         # (dense_bin.hpp:17-21) for <=16-bin features
    code_planes: int     # ceil(G*bits / 32)
    grad: int
    hess: int
    rowid: int
    label: int           # -1 when absent
    score: int           # -1 when absent
    weight: int          # -1 when absent
    num_planes: int      # P, padded to a multiple of 8
    num_rows: int        # true row count n
    num_lanes: int       # R, n padded to a multiple of max_tile
                         # (+ 1 max_tile of window-read headroom)
    tile: int
    max_tile: int        # largest per-branch processing tile the lane
                         # padding supports (power-of-2 multiple of
                         # tile, <= MAX_TILE, scaled to the row count)
    # row-wise multival code planes (ops/multival.py): K slot planes of
    # int32 flat codes appended after the scalar planes so the
    # partition kernels keep them row-aligned for free. Trailing
    # defaults keep every existing constructor/signature working.
    mv_start: int = -1   # first mv plane (8-aligned), -1 when absent
    mv_planes: int = 0   # K rounded up to the 8-sublane tile


def make_layout(num_cols: int, code_bits: int, n: int,
                with_label: bool = False, with_score: bool = False,
                with_weight: bool = False, tile: int = DEF_TILE,
                mv_planes: int = 0) -> PlaneLayout:
    assert code_bits in (4, 8, 16)
    assert mv_planes % 8 == 0, mv_planes
    cp = -(-num_cols * code_bits // 32)
    p = cp
    if p % 8 == 7:
        # keep grad+hess inside ONE aligned 8-plane block: the planar
        # histogram kernel fetches them as an (8, Rb) tile-aligned
        # BlockSpec (ops/histogram.py), which requires grad % 8 <= 6
        p += 1
    grad, hess = p, p + 1
    p += 2
    rowid = p
    p += 1
    label = score = weight = -1
    if with_label:
        label = p
        p += 1
    if with_score:
        score = p
        p += 1
    if with_weight:
        weight = p
        p += 1
    mv_start = -1
    if mv_planes:
        # mv code planes start 8-aligned: the multival histogram kernel
        # reads them as (8, Rb) tile-aligned BlockSpecs
        p = -(-p // 8) * 8
        mv_start = p
        p += mv_planes
    num_planes = -(-p // 8) * 8
    # lane padding sized for the LARGEST per-branch processing tile:
    # kernels are per-step-overhead bound, so big leaf windows process
    # in tiles up to MAX_TILE (fused.py _branch_tile) — window reads
    # clamp to [0, R - S], so R must carry one max_tile of headroom
    max_tile = tile
    while max_tile * 2 <= min(MAX_TILE, max(tile, n // 8)):
        max_tile *= 2
    num_lanes = (-(-n // max_tile) + 1) * max_tile
    return PlaneLayout(num_cols, code_bits, cp, grad, hess, rowid,
                       label, score, weight, num_planes, n, num_lanes,
                       tile, max_tile, mv_start, mv_planes)


def f32_as_i32(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


def i32_as_f32(x):
    return jax.lax.bitcast_convert_type(x, jnp.float32)


PACK_BLOCK_BYTES = 1 << 22   # host bytes one pack thread transposes at a time
PACK_THREADS = 8


def pack_codes_host(codes: np.ndarray, layout: PlaneLayout,
                    lanes: Optional[int] = None) -> np.ndarray:
    """[n, G] u8/u16 bin codes -> [code_planes, lanes] i32 ON THE HOST
    (little-endian packing: column j occupies bits [j*bits % 32, ...) of
    plane j*bits // 32; 4-bit mode packs two columns per byte). A row's
    code bytes padded to ``code_planes * 4`` ARE its little-endian int32
    words, so the pack is a view and one transposed copy — no device
    program, whose compile grew with the row count (14.7 s a million
    rows on XLA:TPU for the eager reshape -> bitcast -> transpose this
    replaces). Rows are transposed in cache-sized blocks on a few
    threads (numpy releases the GIL in the copy); lanes past ``n`` stay
    zero."""
    codes = np.asarray(codes)
    n, g = codes.shape
    bits = layout.code_bits
    lanes = layout.num_lanes if lanes is None else lanes
    width = layout.code_planes * 4
    out = np.empty((layout.code_planes, lanes), np.int32)
    out[:, n:] = 0
    block = max(LANE, PACK_BLOCK_BYTES // width // LANE * LANE)

    def one(lo: int) -> None:
        c = codes[lo:lo + block]
        if bits == 8 and g == width and c.dtype == np.uint8 \
                and c.flags.c_contiguous:
            b = c               # whole words already: nothing to pad
        else:
            b = np.zeros((c.shape[0], width), np.uint8)
            if bits == 4:
                c = c.astype(np.uint8)
                b[:, :(g + 1) // 2] = c[:, 0::2] & 15
                b[:, :g // 2] |= c[:, 1::2] << 4
            elif bits == 8:
                b[:, :g] = c
            else:
                b[:, :2 * g] = c.astype("<u2").view(np.uint8)
        out[:, lo:lo + c.shape[0]] = b.view("<i4").T

    with ThreadPoolExecutor(PACK_THREADS) as pool:
        list(pool.map(one, range(0, n, block)))
    return out


def build_codes_planes(codes, layout: PlaneLayout, device=None) -> jax.Array:
    """[n, G] u8/u16 bin codes -> [code_planes, R] i32 on ``device`` (the
    default device when None): packed on the host, uploaded in its final
    form, so the device never holds a row-major copy beside it."""
    return jax.device_put(pack_codes_host(codes, layout), device)


def build_data(layout: PlaneLayout, codes_planes: jax.Array,
               grad: jax.Array, hess: jax.Array,
               rowid: Optional[jax.Array] = None,
               label: Optional[jax.Array] = None,
               score: Optional[jax.Array] = None,
               weight: Optional[jax.Array] = None,
               mv: Optional[jax.Array] = None) -> jax.Array:
    """Assemble the [P, R] planar state. grad/hess/... are [n] f32 in
    lane order (already permuted if a bagging permutation applies).
    ``mv``: [mv_planes, n|R] int32 slot-major row-wise codes
    (ops/multival.py) when the layout reserves mv planes — pad lanes
    are filled with the −1 no-contribution code."""
    R = layout.num_lanes
    n = grad.shape[0]

    def lane_pad_f(x):
        x = x.astype(jnp.float32)
        return jnp.pad(x, (0, R - x.shape[0])) if x.shape[0] < R else x

    rows = [codes_planes]
    gap = layout.grad - layout.code_planes
    if gap:
        rows.append(jnp.zeros((gap, R), jnp.int32))
    extra = [f32_as_i32(lane_pad_f(grad))[None], f32_as_i32(lane_pad_f(hess))[None]]
    if rowid is None:
        rowid = jnp.arange(n, dtype=jnp.int32)
    # pad lanes get row ids CONTINUING past the real rows (never 0): a
    # zero fill would let pad lanes alias row 0 in the sync / leaf
    # scatters when the layout is row-bucketed above the actual count
    rid = rowid.astype(jnp.int32)
    if rowid.shape[0] < R:
        rid = jnp.concatenate(
            [rid, jnp.arange(rowid.shape[0], R, dtype=jnp.int32)])
    extra.append(rid[None])
    for idx, val in ((layout.label, label), (layout.score, score),
                     (layout.weight, weight)):
        if idx >= 0:
            v = val if val is not None else jnp.zeros(n, jnp.float32)
            extra.append(f32_as_i32(lane_pad_f(v))[None])
    rows.append(jnp.concatenate(extra, axis=0))
    p_used = layout.grad + len(extra)
    if layout.mv_planes:
        assert mv is not None and mv.shape[0] == layout.mv_planes, \
            (None if mv is None else mv.shape, layout.mv_planes)
        gap_mv = layout.mv_start - p_used
        if gap_mv:
            rows.append(jnp.zeros((gap_mv, R), jnp.int32))
        m = mv.astype(jnp.int32)
        if m.shape[1] < R:
            m = jnp.pad(m, ((0, 0), (0, R - m.shape[1])),
                        constant_values=-1)
        rows.append(m)
        p_used = layout.mv_start + layout.mv_planes
    pad = layout.num_planes - p_used
    if pad:
        rows.append(jnp.zeros((pad, R), jnp.int32))
    return jnp.concatenate(rows, axis=0)


# ---------------------------------------------------------------------------
# routing scalars
# ---------------------------------------------------------------------------

ROUTE_SCALARS = 19      # routing vector length (see route_scalars)
CAT_WORDS = 8           # bitset words -> categorical bins <= 256


def route_scalars(layout: PlaneLayout, feature, threshold, default_left,
                  miss_bin, efb_dev=None, is_cat=None, cat_bitset=None):
    """i32 scalar vector describing one split's routing, for both the
    kernel (prefetched) and the oracle. Layout:
    [plane, shift, mask, thr, dl, miss, efb_use, efb_off, efb_nsl,
     efb_skip, is_cat, bitset_w0..w7]
    """
    feature = jnp.asarray(feature, jnp.int32)
    bits = layout.code_bits
    if efb_dev is not None:
        group_of, offset_of, nslots_of, skip_of = efb_dev
        gidx = group_of[feature]
        efb = [jnp.int32(1), offset_of[feature], nslots_of[feature],
               skip_of[feature]]
    else:
        gidx = feature
        efb = [jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0)]
    bitpos = gidx * bits
    plane = bitpos // 32
    shift = bitpos % 32
    mask = jnp.int32((1 << bits) - 1)
    ic = jnp.asarray(0 if is_cat is None else is_cat, jnp.int32)
    if cat_bitset is None:
        bits = jnp.zeros(CAT_WORDS, jnp.int32)
    else:
        bits = jnp.asarray(cat_bitset, jnp.int32)
        bits = jnp.pad(bits, (0, CAT_WORDS - bits.shape[0]))
    return jnp.concatenate([
        jnp.stack([plane, shift, mask,
                   jnp.asarray(threshold, jnp.int32),
                   jnp.asarray(default_left, jnp.int32),
                   jnp.asarray(miss_bin, jnp.int32), *efb, ic]), bits])


def _code_from_col32(col32, rs):
    """The split column's bin code out of its packed plane word."""
    return jax.lax.shift_right_logical(col32, rs[1]) & rs[2]


def _efb_bin(code, rs):
    """EFB bundle decode (io/efb.py:194): the member feature's bin from
    the bundle column's code; out-of-band codes read as its skip bin."""
    rel = code - rs[7]
    inband = ((rel >= 0) & (rel < rs[8])).astype(jnp.int32)
    dec = rel + (rel >= rs[9]).astype(jnp.int32)
    return jnp.where(inband == 1, dec, rs[9])


def _num_left(binval, rs):
    return (binval <= rs[3]).astype(jnp.int32)


def _is_missing(binval, rs):
    return (binval == rs[5]) & (rs[5] >= 0)


def _cat_left(binval, rs):
    """Bitset membership over the 8 prefetched words (dense_bin.hpp
    Split categorical case): the word is selected by a masked sum, the
    bit by a per-lane variable shift — no gather."""
    widx = jax.lax.shift_right_logical(binval, 5)
    word = jnp.zeros_like(binval)
    for w in range(CAT_WORDS):
        word = word + jnp.where(widx == w, rs[11 + w], 0)
    return jax.lax.shift_right_logical(word, binval & 31) & 1


def _route_numerical(binval, rs):
    """go_left of a numerical split: threshold compare, the missing bin
    by default_left (bin.h threshold semantics)."""
    is_miss = _is_missing(binval, rs).astype(jnp.int32)
    return jnp.where(is_miss == 1, rs[4], _num_left(binval, rs)) == 1


def _route_categorical(binval, rs):
    """go_left of a categorical split. Missing categoricals ignore
    default_left (they are out-of-set -> right), mirroring
    ops/partition._decision_go_left."""
    return _cat_left(binval, rs) == 1


def _route_from_col32(col32, rs):
    """Shared routing math: packed plane word -> go_left (bool), given
    the scalar vector rs (see route_scalars), whatever the split's kind:
    every piece is computed and the scalars rs[6] (EFB) and rs[10]
    (categorical) select among them. The traverse kernel, whose scalars
    sit in SMEM, branches on the same two and calls the same pieces. All
    intermediates stay int32 — Mosaic cannot select/broadcast i1
    vectors."""
    code = _code_from_col32(col32, rs)
    efb_bin = _efb_bin(code, rs)
    binval = jnp.where(rs[6] == 1, efb_bin, code)
    num_left = _num_left(binval, rs)
    cat_left = _cat_left(binval, rs)
    dec_lr = jnp.where(rs[10] == 1, cat_left, num_left)
    is_miss = (_is_missing(binval, rs) & (rs[10] == 0)).astype(jnp.int32)
    return jnp.where(is_miss == 1, rs[4], dec_lr) == 1


# ---------------------------------------------------------------------------
# XLA reference implementation (CPU path + oracle)
# ---------------------------------------------------------------------------

def partition_ref(data: jax.Array, layout: PlaneLayout, start, count,
                  rscal, *, cap: int):
    """Stable 4-way window partition in plain XLA (argsort-based)."""
    P, R = data.shape
    tile = layout.tile
    nt = cap // tile + 1
    assert nt * tile <= R, "cap must top out at num_lanes - tile"
    wl = nt * tile
    rs_blk = jnp.clip(jnp.asarray(start, jnp.int32) // tile, 0,
                      R // tile - nt)
    rs = rs_blk * tile
    off = jnp.asarray(start, jnp.int32) - rs
    win = jax.lax.dynamic_slice(data, (0, rs), (P, wl))
    col32 = jnp.sum(jnp.where(
        jnp.arange(P, dtype=jnp.int32)[:, None] == rscal[0], win, 0), axis=0)
    go_left = _route_from_col32(col32, rscal)
    pos = jnp.arange(wl, dtype=jnp.int32)
    valid = (pos >= off) & (pos < off + count)
    gl = go_left & valid
    gr = (~go_left) & valid
    nleft = jnp.sum(gl).astype(jnp.int32)
    key = jnp.where(pos < off, jnp.int8(0),
                    jnp.where(gl, jnp.int8(1),
                              jnp.where(gr, jnp.int8(2), jnp.int8(3))))
    inv = jnp.argsort(key, stable=True)
    data = jax.lax.dynamic_update_slice(data, win[:, inv], (0, rs))
    return data, nleft


# ---------------------------------------------------------------------------
# the pallas kernel
# ---------------------------------------------------------------------------

def _lane_iota(s):
    return jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)


def _lane_prefix(x, roll):
    """Hillis-Steele inclusive prefix sum along the lanes of every row
    of [K, s] i32."""
    s = x.shape[1]
    b = 1
    while b < s:
        x = x + jnp.where(_lane_iota(s) >= b, roll(x, b, 1), 0)
        b *= 2
    return x


def _compact_streams(x, keeps, carries, roll=None):
    """In-tile stable compaction of the [P, S] tile ``x``, ONE plan for
    all of ``keeps`` (K <= 8 rows of [1, S] i32 0/1, one per output
    stream), each stream placed at its carry offset ``carries[j]`` (an
    i32 scalar in [0, LANE)). Returns (K compacted [P, S + LANE] copies,
    K kept counts): copy j holds the lanes with ``keeps[j] == 1`` in
    their order, in its lanes [carries[j], carries[j] + count j); every
    other lane is garbage.

    The K keep rows are stacked in the sublanes of one [8, S] array (row
    j by sublane iota + select; a [1, S] i32 row costs the vregs of an
    [8, S] one, so the stack is free), which gets ONE prefix sum. The
    tile and the stacked shifts then sit behind a static lead of LANE
    lanes in [., S + LANE] arrays, so lane i of stream j has to move
    DOWN by shift = LANE + i - (carry j + rank - 1) lanes, never up: the
    carry offset rides in the network and no roll follows it. The shifts
    stay non-negative and non-decreasing along a stream's kept lanes in
    steps smaller than the lanes' distance, which is all the LSB-first
    binary network needs to move lanes without collisions: round b moves
    the lanes whose shift has bit b down by b. Every stream rolls by the
    same amount in the same round, so the stacked shifts take one roll,
    one ``& b``, one compare and one select per round for all K, and
    each stream's data takes its own row of the mask, broadcast over the
    planes. The rounds with b >= LANE move whole vreg columns and rotate
    nothing. A moved shift keeps its bit b: no later round tests a bit
    at or below b, so clearing it would be dead work. ``roll`` is
    `pltpu.roll` inside a kernel (tests pass `jnp.roll`)."""
    if roll is None:
        from jax.experimental.pallas import tpu as pltpu
        roll = pltpu.roll
    S = x.shape[1]
    W = S + LANE
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, S), 0)
    keep8 = jnp.broadcast_to(keeps[-1], (8, S))
    carry8 = jnp.full((8, S), carries[-1], jnp.int32)
    for j in range(len(keeps) - 2, -1, -1):
        keep8 = jnp.where(sub == j, keeps[j], keep8)
        carry8 = jnp.where(sub == j, carries[j], carry8)
    ranks = _lane_prefix(keep8, roll)
    sh = jnp.where(keep8 == 1,
                   _lane_iota(S) + (LANE + 1) - (carry8 + ranks), 0)
    sh = jnp.concatenate([jnp.zeros((8, LANE), jnp.int32), sh], axis=1)
    xw = jnp.concatenate([jnp.zeros((x.shape[0], LANE), x.dtype), x], axis=1)
    comps = [xw] * len(keeps)
    b = 1
    while b < W:
        moved = roll(sh, W - b, 1)
        take = moved & b
        for j in range(len(keeps)):
            comps[j] = jnp.where(
                jnp.broadcast_to(take[j:j + 1], xw.shape) != 0,
                roll(comps[j], W - b, 1), comps[j])
        sh = jnp.where(take != 0, moved, sh)
        b *= 2
    return comps, [jnp.sum(k) for k in keeps]


def _emit_stream(comp, k, smem, cursor, carry, slot, asteps, stgs, cbuf,
                 sems, win_ref):
    """One stream's carry-chunk write, for both partition kernels.
    ``comp`` [P, S + LANE] holds the tile's k kept lanes from lane c on
    (`_compact_streams` with the stream's carry length c =
    ``smem[carry]``); the < LANE lanes the previous tile left over lie
    in lanes [0, c) of ``cbuf``. One select on the first column puts
    them in front, the chunk is staged ONCE into this step's buffer
    (``stgs[slot]``: two, so that this step's build overlaps the
    previous step's DMA) and DMA'd to the LANE-aligned cursor
    ``smem[cursor]`` of the scratch window; waiting for the other
    slot's DMA before starting this one serializes the overlapping
    writes. The stream advances by adv = the whole columns of c + k,
    and the next carry is the aligned column [adv, adv + LANE) of the
    buffer just staged."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    W = comp.shape[1]
    c = smem[carry]
    written = pl.multiple_of(smem[cursor], LANE)
    total = c + k
    adv = pl.multiple_of((total // LANE) * LANE, LANE)
    head = jnp.where(_lane_iota(LANE) < c, cbuf[...], comp[:, :LANE])

    for s in (0, 1):
        @pl.when(slot == s)
        def _(s=s):
            stg = stgs[s]
            stg[:, :LANE] = head
            stg[:, LANE:] = comp[:, LANE:]
            # slot alternation follows ACTIVE steps, so the other slot's
            # DMA is outstanding on every step but the first
            @pl.when(asteps > 0)
            def _():
                pltpu.make_async_copy(
                    stgs[1 - s], win_ref.at[:, pl.ds(0, W)],
                    sems.at[1 - s]).wait()
            pltpu.make_async_copy(
                stg, win_ref.at[:, pl.ds(written, W)], sems.at[s]).start()
            cbuf[...] = stg[:, pl.ds(adv, LANE)]

    smem[cursor] = written + adv
    smem[carry] = total - adv


def _partition_kernel(scal, data_ref, dout_ref, win_ref, nleft_ref,
                      stg0, stg1, cbuf, sems, wsems, smem, *, S, P):
    """See module docstring. scal: [off, count, rs_blk, plane, shift,
    mask, thr, dl, miss, efb_use, efb_off, efb_nsl, efb_skip].

    Grid (3, nt): sides 0/1 stream [pre|lefts] then [rights|tail] into
    the scratch window `win_ref`; side 2 DMAs the window back into the
    ALIASED data buffer (in-place update — every read of the window
    happened in sides 0/1, so the write-back cannot race them). This
    keeps the whole split on one buffer: no XLA-level slice +
    dynamic_update_slice, which profiling showed as a full copy of the
    training state per split."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    side = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    t0 = scal[3]
    t1 = scal[4]
    step = side * nt + t

    @pl.when(step == 0)
    def _():
        smem[0] = 0          # lefts seen
        smem[1] = t0 * S     # written lanes (S-aligned stream start)
        smem[2] = 0          # carry length in [0, 128)
        smem[3] = 0          # active stream steps taken

    # blocks outside [t0, t1] hold only pre/tail rows whose stream
    # positions equal their original positions — identity, skipped on
    # every side (their index_map is pinned so nothing is refetched)
    @pl.when((side <= 1) & (t >= t0) & (t <= t1))
    def _stream():
        x = data_ref[...]                      # [P, S] i32
        off = scal[0]
        count = scal[1]
        pos = _lane_iota(S) + t * S
        valid = (pos >= off) & (pos < off + count)

        col32 = jnp.sum(jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (P, S), 0) == scal[5], x, 0),
            axis=0, keepdims=True)
        rsv = [scal[5 + i] for i in range(ROUTE_SCALARS)]
        go_left = _route_from_col32(col32, rsv)

        keep_l = ((pos < off) | (valid & go_left)).astype(jnp.int32)
        keep_r = ((valid & ~go_left) | (pos >= off + count)).astype(jnp.int32)
        keep = jnp.where(side == 0, keep_l, keep_r)
        nl_here = jnp.sum(jnp.where(side == 0,
                                    (valid & go_left).astype(jnp.int32), 0))

        # slot alternation must follow ACTIVE steps (skipped blocks do
        # not run): parity of an SMEM counter, not of the grid step
        asteps = smem[3]
        slot = jax.lax.rem(asteps, 2)
        (comp,), (k,) = _compact_streams(x, [keep], [smem[2]])
        _emit_stream(comp, k, smem, 1, 2, slot, asteps, (stg0, stg1), cbuf,
                     sems, win_ref)
        smem[0] = smem[0] + nl_here
        smem[3] = asteps + 1

        @pl.when((side == 1) & (t == t1))
        def _():
            @pl.when(slot == 0)
            def _():
                pltpu.make_async_copy(
                    stg0, win_ref.at[:, pl.ds(0, S + 128)], sems.at[0]).wait()
            @pl.when(slot == 1)
            def _():
                pltpu.make_async_copy(
                    stg1, win_ref.at[:, pl.ds(0, S + 128)], sems.at[1]).wait()

    # ---- side 2: window -> data write-back (HBM-to-HBM block DMAs) ---
    @pl.when((side == 2) & (t >= t0) & (t <= t1))
    def _writeback():
        rs_blk = scal[2]
        slot2 = jax.lax.rem(t, 2)
        @pl.when(t > t0 + 1)
        def _():
            pltpu.make_async_copy(
                win_ref.at[:, pl.ds(0, S)],
                dout_ref.at[:, pl.ds(0, S)], wsems.at[slot2]).wait()
        pltpu.make_async_copy(
            win_ref.at[:, pl.ds(t * S, S)],
            dout_ref.at[:, pl.ds((rs_blk + t) * S, S)],
            wsems.at[slot2]).start()
        @pl.when(t == t1)
        def _():
            pltpu.make_async_copy(
                win_ref.at[:, pl.ds(0, S)],
                dout_ref.at[:, pl.ds(0, S)], wsems.at[slot2]).wait()
            @pl.when(t1 > t0)
            def _():
                pltpu.make_async_copy(
                    win_ref.at[:, pl.ds(0, S)],
                    dout_ref.at[:, pl.ds(0, S)], wsems.at[1 - slot2]).wait()
            nleft_ref[0, 0] = smem[0]


# tpulint: jit-ok(kernel entry; dispatched through manager-registered learner entries)
@functools.partial(jax.jit,
                   static_argnames=("cap", "layout", "tile", "interpret"))
def partition_pallas(data: jax.Array, layout: PlaneLayout, start, count,
                     rscal, *, cap: Optional[int] = None,
                     tile: Optional[int] = None,
                     interpret: bool = False):
    """Pallas stable window partition. Returns (data', nleft); data' is
    the SAME buffer, updated in place (input/output aliased).
    ``tile`` overrides the processing tile (the kernels are
    per-step-overhead bound, so callers pass bigger tiles for bigger
    windows; with a static ``cap`` the tile must divide it).

    ``cap=None`` (the default) is the dynamic mode: the block sweep
    rides a DYNAMIC grid dimension sized from the traced window
    scalars (`t1 + 1` blocks — exactly the covered blocks, so the
    skipped-step cost model of the old capacity ladder is subsumed: no
    step is ever launched past the window), and ONE lowered program
    serves every leaf size. The scratch window is statically sized for
    the worst case (the whole lane extent), which is what the ladder's
    top capacity branch already allocated. ``cap=<int>`` keeps the
    static `cap//S + 1` sweep for shape-stable callers."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, R = data.shape
    S = tile if tile is not None else layout.tile
    start = jnp.asarray(start, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    if cap is not None:
        assert cap % S == 0, (cap, S)
        nt = cap // S + 1
        wl = nt * S
        rs_blk = jnp.clip(start // S, 0, R // S - nt)
    else:
        # the window [start, start+count) always lies in [0, R), so the
        # unclamped block start fits and every covered block index stays
        # below R // S
        assert R % S == 0, (R, S)
        wl = R
        rs_blk = start // S
    rs = rs_blk * S
    off = start - rs
    t0 = off // S
    t1 = jnp.maximum(off + count - 1, 0) // S
    # kernel scalar layout: [off, count, rs_blk, t0, t1, <10 routing>]
    kern_scal = jnp.concatenate([
        jnp.stack([off, count, rs_blk, t0, t1]),
        rscal.astype(jnp.int32)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(3, nt if cap is not None else t1 + 1),
        in_specs=[pl.BlockSpec(
            (P, S),
            lambda side, t, scal: (0, scal[2] + jnp.clip(t, scal[3],
                                                         scal[4])))],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((P, S + 128), jnp.int32),
            pltpu.VMEM((P, S + 128), jnp.int32),
            pltpu.VMEM((P, 128), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    dout, _win, nleft = pl.pallas_call(
        functools.partial(_partition_kernel, S=S, P=P),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((P, R), jnp.int32),
            jax.ShapeDtypeStruct((P, wl + S + 256), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        input_output_aliases={1: 0},
        interpret=interpret,
    )(kern_scal, data)
    return dout, nleft[0, 0]


def _partition_kernel2(scal, data_ref, dout_ref, win_ref, nleft_ref,
                       stgL0, stgL1, stgR0, stgR1, cbufL, cbufR,
                       semL, semR, rin0, rin1, obuf0, obuf1, lin,
                       rsem, osem, dsem, lsem, smem, *, S, P, RB0):
    """Two-side rewrite of `_partition_kernel` (same contract).

    Side 0 makes ONE pass over the window and emits BOTH streams:
    the L stream [pre|lefts] carry-written into scratch at window
    coordinates (so it is already destination-aligned), and the
    R stream [rights|tail] carry-written into a second scratch region
    at fixed anchor `RB0 + S` (so its coordinates are independent of
    the — still unknown — boundary). Both come from one compaction
    plan per tile (`_compact_streams` with the rows keep_l, keep_r and
    the two carry lengths: one prefix sum, one stacked shift row, the
    two data networks advancing round by round together, each tile
    coming out at its stream's carry offset) and one carry write each
    (`_emit_stream`, v1's own). The two chunk-write chains are
    independent and interleave, halving the per-step wait latency of
    the v1 design, and the window is read once instead of twice.

    Side 1 writes back: blocks wholly below the boundary
    B0 = off + nleft are direct aligned HBM->HBM copies from the L
    region; blocks at/after it are INDEPENDENT realign chunks — read an
    aligned [S+128] slice of the R region, rotate registers by the
    constant (S + t*S - B0) mod 128, splice the boundary block's head
    from the L region, write an aligned [S] chunk. No carry chain on
    this side, so the copies pipeline at bandwidth.

    scal: [off, count, rs_blk, t0, t1, <ROUTE_SCALARS routing>].
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    side = pl.program_id(0)
    t = pl.program_id(1)
    t0 = scal[3]
    t1 = scal[4]

    @pl.when((side == 0) & (t == t0))
    def _():
        smem[0] = t0 * S     # L stream cursor (window coords, 128-mult)
        smem[1] = 0          # L carry length in [0, 128)
        smem[2] = RB0 + S    # R stream cursor (anchor RB0 + S)
        smem[3] = 0          # R carry length
        smem[4] = 0          # lefts seen (valid lanes only)
        smem[5] = 0          # active stream steps taken

    @pl.when((side == 0) & (t >= t0) & (t <= t1))
    def _stream():
        x = data_ref[...]                      # [P, S] i32
        off = scal[0]
        count = scal[1]
        pos = _lane_iota(S) + t * S
        valid = (pos >= off) & (pos < off + count)

        col32 = jnp.sum(jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (P, S), 0) == scal[5], x, 0),
            axis=0, keepdims=True)
        rsv = [scal[5 + i] for i in range(ROUTE_SCALARS)]
        go_left = _route_from_col32(col32, rsv)

        keep_l = ((pos < off) | (valid & go_left)).astype(jnp.int32)
        keep_r = ((valid & ~go_left) | (pos >= off + count)).astype(jnp.int32)
        nl_here = jnp.sum((valid & go_left).astype(jnp.int32))
        asteps = smem[5]
        slot = jax.lax.rem(asteps, 2)

        (compL, compR), (kL, kR) = _compact_streams(
            x, [keep_l, keep_r], [smem[1], smem[3]])
        _emit_stream(compL, kL, smem, 0, 1, slot, asteps, (stgL0, stgL1),
                     cbufL, semL, win_ref)
        _emit_stream(compR, kR, smem, 2, 3, slot, asteps, (stgR0, stgR1),
                     cbufR, semR, win_ref)

        smem[4] = smem[4] + nl_here
        smem[5] = asteps + 1

        @pl.when(t == t1)
        def _():
            # drain: each chain has exactly ONE outstanding DMA (this
            # step's) — every step waited the other slot before starting
            @pl.when(slot == 0)
            def _():
                pltpu.make_async_copy(
                    stgL0, win_ref.at[:, pl.ds(0, S + 128)], semL.at[0]).wait()
                pltpu.make_async_copy(
                    stgR0, win_ref.at[:, pl.ds(0, S + 128)], semR.at[0]).wait()
            @pl.when(slot == 1)
            def _():
                pltpu.make_async_copy(
                    stgL1, win_ref.at[:, pl.ds(0, S + 128)], semL.at[1]).wait()
                pltpu.make_async_copy(
                    stgR1, win_ref.at[:, pl.ds(0, S + 128)], semR.at[1]).wait()
            nleft_ref[0, 0] = smem[4]

    # ---- side 1: write-back ------------------------------------------
    @pl.when((side == 1) & (t >= t0) & (t <= t1))
    def _writeback():
        rs_blk = scal[2]
        B0 = scal[0] + smem[4]            # off + nleft (window coords)
        tB = B0 // S
        slot2 = jax.lax.rem(t, 2)

        # direct copies and realign writes use SEPARATE semaphore pairs
        # (dsem / osem) so every wait's descriptor matches its start
        @pl.when(t < tB)
        def _direct():
            # L region is window-aligned: straight block copy
            @pl.when(t > t0 + 1)
            def _():
                pltpu.make_async_copy(
                    win_ref.at[:, pl.ds(0, S)],
                    dout_ref.at[:, pl.ds(0, S)], dsem.at[slot2]).wait()
            pltpu.make_async_copy(
                win_ref.at[:, pl.ds(t * S, S)],
                dout_ref.at[:, pl.ds((rs_blk + t) * S, S)],
                dsem.at[slot2]).start()

        @pl.when(t >= tB)
        def _realign():
            # R-region source slice for dest block t: lanes
            # [S + t*S - B0, +S) relative to the region base; the read
            # is 128-aligned, registers rotate by the remainder
            src = RB0 + S + t * S - B0
            delta = jax.lax.rem(src, 128)
            a_t = pl.multiple_of(src - delta, 128)
            tb_eff = jnp.maximum(tB, t0)

            @pl.when(t == tb_eff)
            def _():
                # boundary head comes from the L region ([pre|lefts])
                pltpu.make_async_copy(
                    win_ref.at[:, pl.ds(t * S, S)], lin, lsem).start()

            def realign_step(rin, obuf, s):
                # t-2's READ was waited by its own step; only its WRITE
                # (obuf -> dout) is still outstanding on this slot
                @pl.when(t > tb_eff + 1)
                def _():
                    pltpu.make_async_copy(
                        obuf, dout_ref.at[:, pl.ds(0, S)],
                        osem.at[s]).wait()
                pltpu.make_async_copy(
                    win_ref.at[:, pl.ds(a_t, S + 128)], rin,
                    rsem.at[s]).start()
                pltpu.make_async_copy(
                    win_ref.at[:, pl.ds(a_t, S + 128)], rin,
                    rsem.at[s]).wait()
                @pl.when(t == tb_eff)
                def _():
                    pltpu.make_async_copy(
                        win_ref.at[:, pl.ds(t * S, S)], lin, lsem).wait()
                rolled = pltpu.roll(
                    rin[...], jax.lax.rem((S + 128) - delta, S + 128),
                    1)[:, :S]
                pos = _lane_iota(S) + t * S
                obuf[...] = jnp.where(
                    jnp.broadcast_to(pos < B0, (P, S)), lin[...], rolled)
                pltpu.make_async_copy(
                    obuf, dout_ref.at[:, pl.ds((rs_blk + t) * S, S)],
                    osem.at[s]).start()

            @pl.when(slot2 == 0)
            def _():
                realign_step(rin0, obuf0, 0)

            @pl.when(slot2 == 1)
            def _():
                realign_step(rin1, obuf1, 1)

        @pl.when(t == t1)
        def _drain():
            # outstanding writes: direct steps in [t0, min(tB, t1+1)),
            # realign steps in [max(tB, t0), t1] — up to two per family
            tb_eff = jnp.maximum(tB, t0)
            td_last = jnp.minimum(tB - 1, t1)      # last direct step

            def wait_direct(s):
                pltpu.make_async_copy(
                    win_ref.at[:, pl.ds(0, S)],
                    dout_ref.at[:, pl.ds(0, S)], dsem.at[s]).wait()

            @pl.when(td_last >= t0)
            def _():
                wait_direct(jax.lax.rem(td_last, 2))
            @pl.when(td_last - 1 >= t0)
            def _():
                wait_direct(jax.lax.rem(td_last - 1, 2))

            @pl.when(t1 >= tb_eff)
            def _():
                @pl.when(jax.lax.rem(t1, 2) == 0)
                def _():
                    pltpu.make_async_copy(
                        obuf0, dout_ref.at[:, pl.ds(0, S)], osem.at[0]).wait()
                @pl.when(jax.lax.rem(t1, 2) == 1)
                def _():
                    pltpu.make_async_copy(
                        obuf1, dout_ref.at[:, pl.ds(0, S)], osem.at[1]).wait()
            @pl.when(t1 - 1 >= tb_eff)
            def _():
                @pl.when(jax.lax.rem(t1 - 1, 2) == 0)
                def _():
                    pltpu.make_async_copy(
                        obuf0, dout_ref.at[:, pl.ds(0, S)], osem.at[0]).wait()
                @pl.when(jax.lax.rem(t1 - 1, 2) == 1)
                def _():
                    pltpu.make_async_copy(
                        obuf1, dout_ref.at[:, pl.ds(0, S)], osem.at[1]).wait()


# tpulint: jit-ok(kernel entry; dispatched through manager-registered learner entries)
@functools.partial(jax.jit,
                   static_argnames=("cap", "layout", "tile", "interpret"))
def partition_pallas2(data: jax.Array, layout: PlaneLayout, start, count,
                      rscal, *, cap: Optional[int] = None,
                      tile: Optional[int] = None,
                      interpret: bool = False):
    """v2 pallas stable window partition (see _partition_kernel2).
    Same contract as partition_pallas — including the ``cap=None``
    dynamic-grid mode: one lowered program for every leaf size, scratch
    (and the R-region anchor RB0) statically sized for the whole lane
    extent. Returns (data', nleft) with data' the SAME buffer updated
    in place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, R = data.shape
    S = tile if tile is not None else layout.tile
    start = jnp.asarray(start, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    if cap is not None:
        assert cap % S == 0, (cap, S)
        nt = cap // S + 1
        wl = nt * S
        rs_blk = jnp.clip(start // S, 0, R // S - nt)
    else:
        assert R % S == 0, (R, S)
        wl = R
        rs_blk = start // S
    RB0 = wl + S + 256          # R-region anchor inside the scratch
    rs = rs_blk * S
    off = start - rs
    t0 = off // S
    t1 = jnp.maximum(off + count - 1, 0) // S
    kern_scal = jnp.concatenate([
        jnp.stack([off, count, rs_blk, t0, t1]),
        rscal.astype(jnp.int32)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(2, nt if cap is not None else t1 + 1),
        in_specs=[pl.BlockSpec(
            (P, S),
            # side 1 never reads data_ref: pin its index to block t0 so
            # the pipeline does not refetch the whole window a second
            # time (repeated index -> no refetch)
            lambda side, t, scal: (0, scal[2] + jnp.where(
                side == 0, jnp.clip(t, scal[3], scal[4]), scal[3])))],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((P, S + 128), jnp.int32),   # stgL0
            pltpu.VMEM((P, S + 128), jnp.int32),   # stgL1
            pltpu.VMEM((P, S + 128), jnp.int32),   # stgR0
            pltpu.VMEM((P, S + 128), jnp.int32),   # stgR1
            pltpu.VMEM((P, 128), jnp.int32),       # cbufL
            pltpu.VMEM((P, 128), jnp.int32),       # cbufR
            pltpu.SemaphoreType.DMA((2,)),         # semL
            pltpu.SemaphoreType.DMA((2,)),         # semR
            pltpu.VMEM((P, S + 128), jnp.int32),   # rin0
            pltpu.VMEM((P, S + 128), jnp.int32),   # rin1
            pltpu.VMEM((P, S), jnp.int32),         # obuf0
            pltpu.VMEM((P, S), jnp.int32),         # obuf1
            pltpu.VMEM((P, S), jnp.int32),         # lin
            pltpu.SemaphoreType.DMA((2,)),         # rsem
            pltpu.SemaphoreType.DMA((2,)),         # osem
            pltpu.SemaphoreType.DMA((2,)),         # dsem
            pltpu.SemaphoreType.DMA,               # lsem
            pltpu.SMEM((6,), jnp.int32),           # smem
        ],
    )
    dout, _win, nleft = pl.pallas_call(
        functools.partial(_partition_kernel2, S=S, P=P, RB0=RB0),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((P, R), jnp.int32),
            # L region [0, RB0) holds <= wl + S + 128 written lanes;
            # R region cursor starts at RB0 + S and streams up to wl
            # lanes in (S+128)-wide chunks -> needs wl + 2S + 256
            jax.ShapeDtypeStruct((P, RB0 + wl + 2 * S + 256), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        input_output_aliases={1: 0},
        interpret=interpret,
    )(kern_scal, data)
    return dout, nleft[0, 0]


def partition_window(data, layout, start, count, rscal, *, method,
                     cap=None, tile=None, interpret=False):
    if cap is None and method == "ref":
        raise ValueError("partition_ref slices with a STATIC capacity — "
                         "the dynamic cap=None mode is pallas-only")
    if method == "pallas":
        return partition_pallas(data, layout, start, count, rscal,
                                cap=cap, tile=tile, interpret=interpret)
    if method == "pallas2":
        return partition_pallas2(data, layout, start, count, rscal,
                                 cap=cap, tile=tile, interpret=interpret)
    return partition_ref(data, layout, start, count, rscal, cap=cap)


# ---------------------------------------------------------------------------
# all-lane traverse: a tree's splits replayed on a lane tile held in VMEM
# ---------------------------------------------------------------------------

TRAVERSE_REC = 1 + ROUTE_SCALARS   # a split's record: its slot, route_scalars
# 128-lane rows of a tile (256K lanes, 1 MB a plane: 8 MB of VMEM with
# three planes and the leaf ids double-buffered; 4096 rows do not fit),
# and rows routed at a time (sixteen vregs an array). Timed on a v5e at
# 3 planes x 22M lanes x 254 splits (PERF.md, PR 30): chunks of 32 /
# 64 / 128 / 256 / 512 rows 34.1 / 26.6 / 24.3 / 25.6 / 32.8 ms.
TRAVERSE_ROWS = 2048
TRAVERSE_CHUNK = 128
# a tile's planes and leaf ids, double-buffered, stay inside this (the
# scoped limit is 16 MB): past five planes the tile gives up rows, whole
# chunks first (the 35 planes of `msltr137`: 256 rows, where 2,048 were
# refused at 72 MB), then the chunk itself shrinks with the tile
TRAVERSE_VMEM = 12 << 20


def _tree_routes(layout: PlaneLayout, ta, miss_bin, efb_dev, k):
    """route_scalars of node(s) ``k`` of the tree arrays ``ta``
    (treelearner/fused.py: split_feature, threshold_bin, default_left,
    split_cat, split_bits [L - 1, 8]); ``miss_bin`` [F] is the missing
    bin of every feature, -1 without."""
    f = ta["split_feature"][k]
    return route_scalars(layout, f, ta["threshold_bin"][k],
                         ta["default_left"][k], miss_bin[f], efb_dev,
                         is_cat=ta["split_cat"][k],
                         cat_bitset=ta["split_bits"][k])


def traverse_planes_ref(codes_planes: jax.Array, layout: PlaneLayout, ta,
                        miss_bin, efb_dev=None) -> jax.Array:
    """Leaf id of every lane of ``codes_planes`` [C, R] under the tree
    ``ta`` in plain XLA, [R] i32: the splits replayed in the order they
    were made, one elementwise pass over the split column's plane per
    split. Node k split leaf slot s: the left child keeps s, the right
    child is leaf k + 1 (Tree::Split numbering, tree.h:61), and a child
    that is split later inherits its slot. The portable path, and the
    oracle of `traverse_planes_pallas`."""
    L = ta["split_feature"].shape[0] + 1

    def step(k, carry):
        leaf_of_lane, slot_of_node = carry
        slot = slot_of_node[k]
        rs = _tree_routes(layout, ta, miss_bin, efb_dev, k)
        col32 = jax.lax.dynamic_index_in_dim(codes_planes, rs[0], axis=0,
                                             keepdims=False)
        go_right = ~_route_from_col32(col32, rs)
        leaf_of_lane = jnp.where((leaf_of_lane == slot) & go_right, k + 1,
                                 leaf_of_lane)
        lc, rc = ta["left_child"][k], ta["right_child"][k]
        # a leaf child (negative) indexes past the end and is dropped
        slot_of_node = slot_of_node.at[jnp.where(lc >= 0, lc, L)].set(
            slot, mode="drop")
        slot_of_node = slot_of_node.at[jnp.where(rc >= 0, rc, L)].set(
            k + 1, mode="drop")
        return leaf_of_lane, slot_of_node

    leaf_of_lane, _ = jax.lax.fori_loop(
        0, ta["n_leaves"] - 1, step,
        (jnp.zeros(codes_planes.shape[1], jnp.int32),
         jnp.zeros(L - 1, jnp.int32)))
    return leaf_of_lane


def traverse_table(layout: PlaneLayout, ta, miss_bin,
                   efb_dev=None) -> jax.Array:
    """The flat i32 table `traverse_planes_pallas` prefetches into SMEM,
    built once per tree: [n_leaves - 1, then per node k its record (the
    leaf slot it split, its route_scalars vector)]."""
    L = ta["split_feature"].shape[0] + 1
    k = jnp.arange(L - 1, dtype=jnp.int32)
    live = k < ta["n_leaves"] - 1
    # a node's slot: the root's is 0, a right child's its parent's node
    # index + 1, a left child's its parent's — resolved up the chain of
    # left children by pointer doubling, not node by node
    lc = jnp.where(live & (ta["left_child"] > 0), ta["left_child"], L)
    rc = jnp.where(live & (ta["right_child"] > 0), ta["right_child"], L)
    slot = jnp.full(L - 1, -1, jnp.int32).at[0].set(0).at[rc].set(
        k + 1, mode="drop")
    up = k.at[lc].set(k, mode="drop")
    for _ in range((L - 2).bit_length()):
        slot = jnp.where(slot < 0, slot[up], slot)
        up = up[up]
    rscals = jax.vmap(functools.partial(_tree_routes, layout, ta, miss_bin,
                                        efb_dev))(k)
    rec = jnp.concatenate([slot[:, None], rscals], axis=1)
    return jnp.concatenate(
        [jnp.asarray(ta["n_leaves"], jnp.int32).reshape(1) - 1,
         rec.reshape(-1)])


def _replay_split(tbl, k, codes_ref, leaf_ref, *, rows, chunk, value=None):
    """Split ``k`` of the tree whose table ``tbl`` (traverse_table) sits
    in SMEM, replayed over a lane tile: codes_ref [C, rows, 128] (lane r
    of the planes is element (r // 128, r % 128): dense (8, 128) vregs),
    leaf_ref [rows, 128] the leaf slot of every lane so far. The lanes in
    the split's slot that go right move to slot k + 1. With ``value`` =
    (val_ref, left, right) the lanes in the slot also take the output of
    the child they went to, so once every split is replayed val_ref
    holds each lane's leaf value: no lookup by leaf id. One branch per
    kind of split, chosen by the scalars the generic routing selects
    with: a numerical split never pays the bitset."""
    from jax.experimental import pallas as pl

    base = 1 + k * TRAVERSE_REC
    slot = tbl[base]
    rs = [tbl[base + 1 + i] for i in range(ROUTE_SCALARS)]

    def sweep(route):
        def rows_at(i, _):
            r = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
            go_left = route(_code_from_col32(codes_ref[rs[0], r, :], rs))
            leaf = leaf_ref[r, :]
            leaf_ref[r, :] = jnp.where((leaf == slot) & ~go_left,
                                       k + 1, leaf)
            if value is not None:
                val_ref, left, right = value
                val_ref[r, :] = jnp.where(leaf == slot,
                                          jnp.where(go_left, left, right),
                                          val_ref[r, :])
        jax.lax.fori_loop(0, rows // chunk, rows_at, None)

    for efb in (0, 1):
        for cat, route in ((0, _route_numerical), (1, _route_categorical)):
            @pl.when((rs[6] == efb) & (rs[10] == cat))
            def _(efb=efb, route=route):
                sweep(lambda code: route(
                    _efb_bin(code, rs) if efb else code, rs))


def _traverse_kernel(tbl, codes_ref, leaf_ref, *, rows, chunk):
    """One lane tile, one tree: the splits are the inner loop, in the
    order they were made."""
    leaf_ref[...] = jnp.zeros_like(leaf_ref)

    def split(k, _):
        _replay_split(tbl, k, codes_ref, leaf_ref, rows=rows, chunk=chunk)

    jax.lax.fori_loop(0, tbl[0], split, None)


def _replay_tile(C: int, R: int, per_row: int):
    """(padded lane count, rows, chunk) of the replay kernels' lane tile:
    ``per_row`` i32 words of VMEM a 128-lane row beside the C code planes'
    (each block double-buffered)."""
    unit = LANE * TRAVERSE_CHUNK
    nrows = (R + -R % unit) // LANE
    fit = TRAVERSE_VMEM // (4 * LANE * (2 * C + per_row))
    if fit >= TRAVERSE_CHUNK:
        chunk = TRAVERSE_CHUNK
        rows = min(TRAVERSE_ROWS, nrows, fit // chunk * chunk)
    else:
        rows = chunk = max(8, fit // 8 * 8)
    return nrows * LANE, rows, chunk


# tpulint: jit-ok(kernel entry; dispatched through manager-registered learner entries)
@functools.partial(jax.jit, static_argnames=("interpret",))
def traverse_planes_pallas(codes_planes: jax.Array, table: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """Leaf id of every lane of ``codes_planes`` [C, R] under the tree
    that ``table`` describes (traverse_table): [R] i32. The grid runs
    over lane tiles and each tile replays all the splits with its code
    planes and leaf ids resident in VMEM, so the planes are read once
    and the leaf ids written once a TREE, where a loop over the splits
    in XLA makes two passes over all R lanes a SPLIT."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, R = codes_planes.shape
    lanes, rows, chunk = _replay_tile(C, R, 2)
    if lanes != R:      # a layout whose lane tile shrank below the unit
        codes_planes = jnp.pad(codes_planes, ((0, 0), (0, lanes - R)))
    nrows = lanes // LANE
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(nrows, rows),),
        in_specs=[pl.BlockSpec((C, rows, LANE), lambda t, tbl: (0, t, 0))],
        out_specs=pl.BlockSpec((rows, LANE), lambda t, tbl: (t, 0)),
    )
    leaf = pl.pallas_call(
        functools.partial(_traverse_kernel, rows=rows, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrows, LANE), jnp.int32),
        name="traverse_planes_pallas",
        interpret=interpret,
    )(table, codes_planes.reshape(C, nrows, LANE))
    return leaf.reshape(-1)[:R]


# ---------------------------------------------------------------------------
# forest replay: the weighted sum of many trees' outputs in one pass
# ---------------------------------------------------------------------------

def replay_widths(num_leaves: int):
    """(route words, value words) of one tree's row in a forest's tables:
    traverse_table's [1 + (L - 1) x TRAVERSE_REC] i32 and replay_values'
    [1 + 2 (L - 1)] f32, each padded to the 1,024-word tile XLA gives a
    1-D array, which is the block Mosaic must see in SMEM."""
    n = max(num_leaves - 1, 1)
    pad = lambda w: w + -w % 1024
    return pad(1 + n * TRAVERSE_REC), pad(1 + 2 * n)


def replay_values(ta) -> jax.Array:
    """f32 [1 + 2 (L - 1)] of the tree ``ta``: the output a lane takes
    before any split (the root leaf's value: a tree of one leaf is only
    that), then per node k the outputs of its left and right child: a
    leaf's value, an internal node's own. Node k's children are the two
    slots `_replay_split` sends its lanes to."""
    lv = ta["leaf_value"].astype(jnp.float32)
    iv = ta["internal_value"].astype(jnp.float32)
    L = lv.shape[0]

    def out(child):
        leaf = jnp.clip(-child - 1, 0, L - 1)
        node = jnp.clip(child, 0, max(L - 2, 0))
        return jnp.where(child < 0, lv[leaf], iv[node])

    pair = jnp.stack([out(ta["left_child"]), out(ta["right_child"])], axis=1)
    return jnp.concatenate([lv[:1], pair.reshape(-1)])


def replay_forest_ref(codes_planes: jax.Array, routes: jax.Array,
                      values: jax.Array, sel: jax.Array) -> jax.Array:
    """Sum over the trees j < sel[0] of ``values``' tree j's output on
    every lane of ``codes_planes`` [C, R], in plain XLA: [R] f32.
    ``routes`` [T, W] holds the forest's traverse_table rows and tree j's
    is routes[sel[1 + j]]; ``values`` [kmax, Wv] holds the replay_values
    of those trees in that order, already scaled by their weights. The
    portable path, and the oracle of `replay_forest_pallas`."""
    R = codes_planes.shape[1]

    def tree(j, acc):
        tbl = routes[sel[1 + j]]
        vals = values[j]

        def split(k, carry):
            leaf, val = carry
            rec = jax.lax.dynamic_slice(tbl, (1 + k * TRAVERSE_REC,),
                                        (TRAVERSE_REC,))
            rs = rec[1:]
            col32 = jax.lax.dynamic_index_in_dim(codes_planes, rs[0], axis=0,
                                                 keepdims=False)
            go_left = _route_from_col32(col32, rs)
            here = leaf == rec[0]
            val = jnp.where(here, jnp.where(go_left, vals[1 + 2 * k],
                                            vals[2 + 2 * k]), val)
            return jnp.where(here & ~go_left, k + 1, leaf), val

        _, val = jax.lax.fori_loop(
            0, tbl[0], split, (jnp.zeros(R, jnp.int32),
                               jnp.full(R, vals[0], jnp.float32)))
        return acc + val

    return jax.lax.fori_loop(0, sel[0], tree, jnp.zeros(R, jnp.float32))


def _forest_kernel(sel, tbl, vals, codes_ref, out_ref, leaf_ref, val_ref, *,
                   rows, chunk):
    """Grid step (tile, j): tree j's splits replayed over the tile, its
    output added to the tile's sum. The tile's code planes stay in VMEM
    while j runs (their block index does not move with j), tree j's
    route table and weighted values come to SMEM by their own blocks,
    and the steps past the sel[0] trees to replay do nothing."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j < sel[0])
    def _():
        leaf_ref[...] = jnp.zeros_like(leaf_ref)
        val_ref[...] = jnp.full(val_ref.shape, vals[0], jnp.float32)

        def split(k, _):
            _replay_split(tbl, k, codes_ref, leaf_ref, rows=rows,
                          chunk=chunk, value=(val_ref, vals[1 + 2 * k],
                                              vals[2 + 2 * k]))

        jax.lax.fori_loop(0, tbl[0], split, None)
        out_ref[...] += val_ref[...]


# tpulint: jit-ok(kernel entry; dispatched through manager-registered boosting entries)
@functools.partial(jax.jit, static_argnames=("interpret",))
def replay_forest_pallas(codes_planes: jax.Array, routes: jax.Array,
                         values: jax.Array, sel: jax.Array, *,
                         interpret: bool = False) -> jax.Array:
    """`replay_forest_ref` as one Pallas pass: [R] f32. The grid runs
    over (lane tile, tree): a tile's code planes are read from HBM once
    for all the trees, each tree's splits run as in
    `traverse_planes_pallas` (the same `_replay_split`) carrying every
    lane's output along with its slot, and the sum is written once a
    tile. ``sel`` = [count, the row of routes of each tree]; its length
    less one is the grid's static tree extent (rows past the count repeat
    the last, so their blocks are not fetched again)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, R = codes_planes.shape
    T, W = routes.shape
    kmax, Wv = values.shape
    lanes, rows, chunk = _replay_tile(C, R, 4)
    if lanes != R:
        codes_planes = jnp.pad(codes_planes, ((0, 0), (0, lanes - R)))
    nrows = lanes // LANE
    smem = pltpu.SMEM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(nrows, rows), kmax),
        in_specs=[
            pl.BlockSpec((W,), lambda t, j, sel: (sel[1 + j],),
                         memory_space=smem),
            pl.BlockSpec((Wv,), lambda t, j, sel: (j,), memory_space=smem),
            pl.BlockSpec((C, rows, LANE), lambda t, j, sel: (0, t, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANE), lambda t, j, sel: (t, 0)),
        scratch_shapes=[pltpu.VMEM((rows, LANE), jnp.int32),
                        pltpu.VMEM((rows, LANE), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_forest_kernel, rows=rows, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrows, LANE), jnp.float32),
        name="replay_forest_pallas",
        interpret=interpret,
    )(sel, routes.reshape(-1), values.reshape(-1),
      codes_planes.reshape(C, nrows, LANE))
    return out.reshape(-1)[:R]


# ---------------------------------------------------------------------------
# planar window extraction (bridge to the row-major histogram kernel)
# ---------------------------------------------------------------------------

def window_rowmajor(data: jax.Array, layout: PlaneLayout, rs, *, cap: int):
    """[P, R] planar -> (codes [cap, G] u8/u16, gh [cap, 2] f32) for the
    window [rs, rs+cap). rs need not be aligned."""
    cp = layout.code_planes
    cw = jax.lax.dynamic_slice(data, (0, rs), (cp, cap))
    b = jax.lax.bitcast_convert_type(cw, jnp.uint8)       # [C, cap, 4]
    rm = jnp.transpose(b, (1, 0, 2)).reshape(cap, cp * 4)
    if layout.code_bits == 4:
        half = rm[:, :(layout.num_cols + 1) // 2]
        codes = jnp.stack([half & 15, half >> 4],
                          axis=2).reshape(cap, -1)[:, :layout.num_cols]
    elif layout.code_bits == 8:
        codes = rm[:, :layout.num_cols]
    else:
        codes = jax.lax.bitcast_convert_type(
            rm[:, :layout.num_cols * 2].reshape(cap, layout.num_cols, 2),
            jnp.uint16)
    gh = jax.lax.dynamic_slice(data, (layout.grad, rs), (2, cap))
    gh = i32_as_f32(gh).T                                  # [cap, 2]
    return codes, gh


def get_f32(data: jax.Array, plane: int, n: Optional[int] = None):
    v = i32_as_f32(data[plane])
    return v if n is None else v[:n]


def set_f32(data: jax.Array, plane: int, values: jax.Array):
    v = f32_as_i32(values)
    if v.shape[0] < data.shape[1]:
        v = jnp.pad(v, (0, data.shape[1] - v.shape[0]))
    return data.at[plane].set(v)


def set_gh(data: jax.Array, layout: PlaneLayout, grad, hess):
    gh = jnp.stack([f32_as_i32(grad), f32_as_i32(hess)])
    if gh.shape[1] < data.shape[1]:
        gh = jnp.pad(gh, ((0, 0), (0, data.shape[1] - gh.shape[1])))
    return jax.lax.dynamic_update_slice(data, gh, (layout.grad, 0))


def set_gh_packed(data: jax.Array, layout: PlaneLayout, packed_f32):
    """Write an already quantize-packed (qg << 16 | qh) word plane
    (bitcast through f32 lanes) into the gradient row and zero the
    hessian row — the kernels unpack both levels from the one word.
    With the whole-iteration program's state argument donated
    (treelearner/fused.py, donate_argnums=1) this update aliases the
    input planes in place: the next iteration's packed plane lands in
    the buffer the previous one vacated (double buffering without a
    copy) while its host-side consumer readbacks are still in flight.
    """
    gh = jnp.stack([f32_as_i32(packed_f32),
                    jnp.zeros_like(packed_f32, dtype=jnp.int32)])
    if gh.shape[1] < data.shape[1]:
        gh = jnp.pad(gh, ((0, 0), (0, data.shape[1] - gh.shape[1])))
    return jax.lax.dynamic_update_slice(data, gh, (layout.grad, 0))


def sign_route_scalars(plane_idx: int):
    """route_scalars of the "split" on the sign bit of plane
    ``plane_idx``: the bit read as a one-bit code by the logical shift
    of `_code_from_col32`, threshold 0, no missing bin, no EFB, not
    categorical — a lane whose word is non-negative goes left. How a
    per-row flag reaches the partition kernels, which know only
    routing scalars (the bag of a sampled tree, treelearner/fused.py
    _compact_bag)."""
    return jnp.asarray([plane_idx, 31, 1, 0, 0, -1]
                       + [0] * (ROUTE_SCALARS - 6), jnp.int32)
