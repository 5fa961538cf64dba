"""Row-wise multi-value histograms — the reference MultiValBin analogue.

The planar histogram path (ops/histogram.py) pays one-hot compute and
code-plane bandwidth for EVERY bundle column at every split. At the
wide-sparse shape (Allstate/Criteo: hundreds of EFB bundles, a handful
present per row) the reference switches to its row-wise `MultiValBin`
(src/io/multi_val_dense_bin.hpp): each row stores only its PRESENT
(bundle, bin) entries and the histogram pass touches those alone. This
module is the TPU analogue:

Layout ("row-wise codes", built once at dataset bind time):
  - flat code space: group g's bin b maps to ``flat_off[g] + b`` with
    ``T = sum(group_num_bins)`` total cells;
  - per group a DEFAULT code ``d_g`` (its sampled most-frequent code —
    code 0 for multi-feature bundles by construction). A (g, b) entry is
    present iff ``b != d_g``; the default cell is reconstructed exactly
    from the leaf totals (the FixHistogram identity at group level:
    ``hist[g, d_g] = leaf_total − sum(g's other cells)``), which is also
    what makes ANY d_g choice correct — it only moves the nnz;
  - each row packs its present flat codes into a static ``row_capacity``
    K of int32 slots (bucketed like compile/signature row buckets so
    same-shaped datasets share programs). Slot 0 of every row carries
    the SENTINEL code T, so cell T of the flat histogram accumulates
    the leaf (sum_g, sum_h) totals the reconstruction needs — no extra
    reduction pass. Unused slots hold −1 (arithmetic shift keeps the
    high one-hot all-zero, so they contribute nothing regardless of the
    row weight).

Kernel (MXU radix one-hot over the FLAT space, PR 10 grid conventions):
  the flat code splits ``hi = code >> 7`` / ``lo = code & 127``; per
  slot chunk of SK=8 slot planes the body builds the hi one-hot
  [Bh, Rb], scales by the (masked) grad/hess lanes, and contracts with
  the lo one-hot on the MXU — ``out[2*Bh, 128] += concat(g·1hi, h·1hi)
  @ 1lo^T``. Slot chunks and row blocks both ride the grid, so program
  size is constant in the row capacity AND the leaf size (the dynamic
  ``nblk = last_block+1`` mode of PR 10). Bytes per row are K*4 instead
  of the planar path's G code bytes — at the Allstate shape (581
  bundles, ~30 present/row) that is the whole bandwidth argument.

Both paths support the PR 3 quantized pipeline: int32 (qg<<16)|qh words
in the grad lanes, exact integer accumulation in an int32 flat
histogram.

The XLA scatter path (`histogram_multival_xla`) is the CPU/oracle twin:
bit-exact in int space, and exact for integer-valued f32 weights.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MV_SK = 8            # slot planes per grid step (sublane tile)
MV_RB = 1024         # default rows per block
MV_BL = 128          # low-radix lanes of the flat split
MV_BL_BITS = 7

# occupancy-driven dispatch thresholds (ops/histogram.hist_layout):
# multival wants MANY groups with FEW present per row — below ~32
# groups the planar path's per-column cost is already small, and past
# 25% mean occupancy the K*4 B/row code list stops beating G bytes/row
MULTIVAL_MIN_GROUPS = 32
MULTIVAL_MAX_OCCUPANCY = 0.25


class OccupancyStats(NamedTuple):
    """Measured dataset occupancy (io/dataset.py computes this at
    construct time from a bounded deterministic row sample and stores
    it on BinnedDataset; discrete derived values fold into
    trace_signature)."""
    num_groups: int
    row_nnz_mean: float          # mean non-default codes per row
    row_nnz_max: int             # max over the SAMPLE (layout build
                                 # re-measures the exact full-data max)
    default_code: np.ndarray     # [G] int32 per-group default code
    group_density: np.ndarray    # [G] f32 non-default fraction
    sample_rows: int


class MultiValLayout(NamedTuple):
    """Static geometry of one dataset's row-wise code matrix (ints only
    so it is hashable for jit static args / compile signatures)."""
    num_groups: int
    total_bins: int              # T; sentinel code == T
    row_capacity: int            # K slots/row incl. the sentinel slot 0
    num_rows: int
    nnz_max: int                 # exact full-data max present codes/row


def measure_occupancy(bins: np.ndarray, sample_rows: int = 65536
                      ) -> OccupancyStats:
    """Occupancy statistics from a deterministic strided row sample of
    the [N, G] bin-code matrix. The per-group default code is the
    sample's most frequent code (for multi-feature EFB bundles that is
    code 0 by construction; for singleton groups it is the feature's
    most-frequent bin)."""
    n, g = bins.shape
    step = max(1, n // max(1, sample_rows))
    sample = np.asarray(bins[::step][:sample_rows])
    default = np.empty(g, np.int32)
    for j in range(g):
        default[j] = np.argmax(np.bincount(sample[:, j]))
    present = sample != default[None, :]
    nnz = present.sum(axis=1)
    return OccupancyStats(
        num_groups=int(g),
        row_nnz_mean=float(nnz.mean()) if nnz.size else 0.0,
        row_nnz_max=int(nnz.max()) if nnz.size else 0,
        default_code=default,
        group_density=present.mean(axis=0).astype(np.float32),
        sample_rows=int(sample.shape[0]))


def bucket_row_capacity(nnz_max: int) -> int:
    """Static slot capacity K for a measured per-row nnz max: the +1
    sentinel slot, rounded up a coarse ladder (multiples of 8 to 64,
    then quarter-power-of-two steps — the compile/signature.bucket_rows
    shape-bucketing idea) so near-shaped datasets share programs."""
    k = int(nnz_max) + 1
    if k <= 8:
        return 8
    if k <= 64:
        return -(-k // 8) * 8
    step = max(8, (1 << (int(k - 1).bit_length() - 1)) // 4)
    return -(-k // step) * step


def flat_offsets(group_num_bins) -> np.ndarray:
    """[G] int64 start of each group's cells in the flat code space."""
    nb = np.asarray(group_num_bins, np.int64)
    return np.concatenate([[0], np.cumsum(nb)[:-1]]).astype(np.int64)


def build_rowwise_codes(bins: np.ndarray, group_num_bins,
                        default_code, row_capacity: Optional[int] = None,
                        row_chunk: int = 1 << 18
                        ) -> Tuple[np.ndarray, MultiValLayout]:
    """[N, G] bin codes → ([N, K] int32 row-wise flat codes, layout).

    Chunked over rows so the transient present-mask stays bounded. The
    exact full-data nnz max comes from a first full pass — a sampled
    max could truncate a heavy row's code list, which would be a
    CORRECTNESS bug, not a perf one."""
    n, g = bins.shape
    default = np.asarray(default_code, bins.dtype)
    off = flat_offsets(group_num_bins)
    total = int(np.asarray(group_num_bins, np.int64).sum())

    nnz_max = 0
    for lo in range(0, n, row_chunk):
        chunk = np.asarray(bins[lo:lo + row_chunk])
        cnt = (chunk != default[None, :]).sum(axis=1)
        if cnt.size:
            nnz_max = max(nnz_max, int(cnt.max()))
    k = row_capacity if row_capacity is not None \
        else bucket_row_capacity(nnz_max)
    if nnz_max + 1 > k:
        raise ValueError(f"row capacity {k} < measured nnz max "
                         f"{nnz_max} + sentinel")

    codes = np.full((n, k), -1, np.int32)
    codes[:, 0] = total                      # sentinel → leaf totals
    for lo in range(0, n, row_chunk):
        chunk = np.asarray(bins[lo:lo + row_chunk])
        mask = chunk != default[None, :]
        rows, gs = np.nonzero(mask)          # group-ascending per row
        cnt = mask.sum(axis=1)
        starts = np.cumsum(cnt) - cnt
        pos = np.arange(rows.size) - starts[rows]
        codes[lo + rows, 1 + pos] = (off[gs]
                                     + chunk[rows, gs]).astype(np.int32)
    _note_multival_rows(n)
    return codes, MultiValLayout(num_groups=int(g), total_bins=total,
                                 row_capacity=int(k), num_rows=int(n),
                                 nnz_max=int(nnz_max))


def _note_multival_rows(n: int) -> None:
    """hist.multival_rows counter (obs schema minor 10); no-op when
    telemetry is off."""
    from ..obs import active
    reg = active()
    if reg is not None:
        reg.inc("hist.multival_rows", n)


# ---------------------------------------------------------------------------
# flat histogram [T+1, 2] → group histogram [G, Bg, 2]
# ---------------------------------------------------------------------------

def group_tables(group_num_bins, default_code):
    """Device gather tables mapping the flat histogram back to group
    space with each group's default cell reconstructed: (idx, valid,
    default_onehot) — the io/efb.per_feature_hist table idea, one level
    down."""
    nb = np.asarray(group_num_bins, np.int64)
    g = len(nb)
    bg = int(nb.max()) if g else 1
    off = flat_offsets(nb)
    d = np.asarray(default_code, np.int64)
    b_iota = np.arange(bg)[None, :]
    inband = b_iota < nb[:, None]
    is_def = inband & (b_iota == d[:, None])
    idx = np.where(inband & ~is_def, off[:, None] + b_iota, 0)
    return (jnp.asarray(idx.astype(np.int32)),
            jnp.asarray((inband & ~is_def).astype(np.float32)),
            jnp.asarray(is_def.astype(np.float32)))


def group_hist_from_flat(flat: jax.Array, tables) -> jax.Array:
    """[T+1, 2] flat histogram → [G, Bg, 2]; cell T carries the leaf
    (sum_g, sum_h) totals (the sentinel slot), and each group's default
    cell is total − sum(its other cells) — exact in int space, exact
    for integer-valued f32 weights."""
    idx, valid, dmask = tables
    gh = flat[idx] * valid[..., None].astype(flat.dtype)
    total = flat[-1]                                    # [2]
    fill = total[None, :].astype(gh.dtype) - gh.sum(axis=1)
    return gh + dmask[..., None].astype(gh.dtype) * fill[:, None, :]


# ---------------------------------------------------------------------------
# XLA scatter path — the oracle and the non-TPU backend
# ---------------------------------------------------------------------------

def histogram_multival_xla(codes: jax.Array, grad: jax.Array,
                           hess: jax.Array, total_bins: int) -> jax.Array:
    """Row-wise flat histogram via scatter-add: codes [C, K] int32 (−1 =
    pad), grad/hess [C] f32 or int32 levels → [T+1, 2] (cell T = leaf
    totals via the sentinel slot). Exact integer accumulation for int
    inputs — the parity oracle for the pallas kernels."""
    flat = codes.reshape(-1)
    live = flat >= 0
    idx = jnp.where(live, flat, 0)
    zero = jnp.zeros((), grad.dtype)
    g = jnp.where(live, jnp.broadcast_to(
        grad[:, None], codes.shape).reshape(-1), zero)
    h = jnp.where(live, jnp.broadcast_to(
        hess[:, None], codes.shape).reshape(-1), zero)
    out_g = jnp.zeros(total_bins + 1, grad.dtype).at[idx].add(g)
    out_h = jnp.zeros(total_bins + 1, hess.dtype).at[idx].add(h)
    return jnp.stack([out_g, out_h], axis=-1)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _mv_dims(total_bins: int) -> Tuple[int, int, int]:
    """(Bh, Bl, bl_bits) of the flat radix split; Bh is rounded to a
    multiple of 4 so the [2*Bh, Bl] accumulator block keeps an 8-aligned
    sublane extent."""
    bh = -(-(total_bins + 1) // MV_BL)
    bh = -(-bh // 4) * 4
    return bh, MV_BL, MV_BL_BITS


def _mv_accum(x, gh_ref, out_ref, valid, *, Bh, Bl, bl_bits, dtype,
              gh_off, quant):
    """Accumulate one (slot chunk, row block) step: x [SK, Rb] int32
    flat codes, gh lanes from ``gh_ref`` at ``gh_off`` (packed int32
    words when ``quant``), optional [1, Rb] f32 validity mask. Shared by
    the static and dynamic-grid bodies so they stay bit-identical."""
    if quant:
        w = gh_ref[gh_off:gh_off + 1, :]               # [1, Rb] i32
        g_t = (w >> 16).astype(jnp.float32)
        h_t = (w & 0xFFFF).astype(jnp.float32)
    else:
        gh = jax.lax.bitcast_convert_type(
            gh_ref[gh_off:gh_off + 2, :], jnp.float32)
        g_t, h_t = gh[0:1, :], gh[1:2, :]
    if valid is not None:
        g_t = g_t * valid
        h_t = h_t * valid
    g_t = g_t.astype(dtype)
    h_t = h_t.astype(dtype)
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    rb = x.shape[1]
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (Bh, rb), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (Bl, rb), 0)
    partial = jnp.zeros((2 * Bh, Bl), jnp.float32)
    for s in range(x.shape[0]):
        c = x[s:s + 1, :]                              # [1, Rb]
        # pad slots hold −1: the arithmetic shift keeps hi == −1, the
        # hi one-hot is all-zero, and the slot contributes nothing no
        # matter the row weight
        oh_hi = (hi_iota == (c >> bl_bits)).astype(dtype)
        oh_lo = (lo_iota == (c & (Bl - 1))).astype(dtype)
        a = jnp.concatenate([oh_hi * g_t, oh_hi * h_t], axis=0)
        partial = partial + jax.lax.dot_general(
            a, oh_lo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
    out_ref[...] += partial.astype(jnp.int32) if quant else partial


def _mv_kernel(codes_ref, gh_ref, out_ref, *, Bh, Bl, bl_bits, dtype,
               quant):
    """Static-grid body: grid = (KC slot chunks, NB row blocks); weights
    are pre-masked by the caller (invalid rows carry zero)."""
    from jax.experimental import pallas as pl

    kc, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(kc == 0, i == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _mv_accum(codes_ref[...], gh_ref, out_ref, None, Bh=Bh, Bl=Bl,
              bl_bits=bl_bits, dtype=dtype, gh_off=0, quant=quant)


def _mv_kernel_grid(scal, codes_ref, gh_ref, out_ref, *, Bh, Bl, bl_bits,
                    dtype, gh_off, Rb, quant):
    """Dynamic-grid planar body: reads slot planes and the grad/hess
    planes straight off the [P, R] planar state, masking the leaf
    window by the prefetched [rs_blk, off, count, last_rel] scalars —
    the ops/histogram.py PR 10 conventions verbatim."""
    from jax.experimental import pallas as pl

    kc, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(kc == 0, i == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i <= scal[3])
    def _active():
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, Rb), 1) + i * Rb
        valid = ((pos >= scal[1])
                 & (pos < scal[1] + scal[2])).astype(jnp.float32)
        _mv_accum(codes_ref[...], gh_ref, out_ref, valid, Bh=Bh, Bl=Bl,
                  bl_bits=bl_bits, dtype=dtype, gh_off=gh_off,
                  quant=quant)


def _flat_pairs(out: jax.Array, Bh: int, total_bins: int) -> jax.Array:
    """[2*Bh, Bl] accumulator → [T+1, 2] flat histogram."""
    g = out[:Bh].reshape(-1)[:total_bins + 1]
    h = out[Bh:2 * Bh].reshape(-1)[:total_bins + 1]
    return jnp.stack([g, h], axis=-1)


# tpulint: jit-ok(kernel entry; dispatched through manager-registered learner entries)
@functools.partial(jax.jit, static_argnames=("total_bins", "dtype",
                                             "rows_per_block", "interpret",
                                             "quant"))
def histogram_multival_pallas(codes: jax.Array, gh: jax.Array, *,
                              total_bins: int, dtype=jnp.float32,
                              rows_per_block: Optional[int] = None,
                              interpret: bool = False,
                              quant: bool = False) -> jax.Array:
    """Row-wise flat histogram off a slot-major code matrix.

    codes: [Kp, C] int32 (slot-major; Kp a multiple of 8; −1 = pad);
    gh: [8, C] int32 lane planes — rows 0/1 hold bitcast f32 grad/hess,
    or row 0 holds packed (qg<<16)|qh words when ``quant``. Weights are
    pre-masked by the caller (invalid rows zero). Returns [T+1, 2] f32
    (int32 when ``quant``); cell T carries the sentinel leaf totals.
    """
    from jax.experimental import pallas as pl

    kp, c = codes.shape
    assert kp % MV_SK == 0, kp
    rb = rows_per_block if rows_per_block is not None else MV_RB
    if c < rb:
        rb = max(128, -(-c // 128) * 128)
    cp = -(-c // rb) * rb
    if cp > c:
        codes = jnp.pad(codes, ((0, 0), (0, cp - c)), constant_values=-1)
        gh = jnp.pad(gh, ((0, 0), (0, cp - c)))
    bh, bl, bl_bits = _mv_dims(total_bins)

    out = pl.pallas_call(
        functools.partial(_mv_kernel, Bh=bh, Bl=bl, bl_bits=bl_bits,
                          dtype=dtype, quant=quant),
        grid=(kp // MV_SK, cp // rb),
        in_specs=[
            pl.BlockSpec((MV_SK, rb), lambda kc, i: (kc, i)),
            pl.BlockSpec((8, rb), lambda kc, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((2 * bh, bl), lambda kc, i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((2 * bh, bl),
                                       jnp.int32 if quant
                                       else jnp.float32),
        interpret=interpret,
    )(codes, gh)
    return _flat_pairs(out, bh, total_bins)


# tpulint: jit-ok(kernel entry; dispatched through manager-registered learner entries)
@functools.partial(jax.jit, static_argnames=("mv_start", "mv_planes",
                                             "total_bins", "grad_plane",
                                             "dtype", "rows_per_block",
                                             "interpret", "quant"))
def histogram_multival_planar(data: jax.Array, start, count, *,
                              mv_start: int, mv_planes: int,
                              total_bins: int, grad_plane: int,
                              dtype=jnp.float32,
                              rows_per_block: Optional[int] = None,
                              interpret: bool = False,
                              quant: bool = False) -> jax.Array:
    """Leaf-window row-wise histogram straight off the planar state.

    data: [P, R] int32 planar rows whose planes [mv_start, mv_start +
    mv_planes) hold the slot-major row-wise codes (ops/plane.py
    make_layout mv_planes). The leaf window [start, start+count) rides
    the PR 10 dynamic grid: nblk = last_block + 1 from the traced
    scalars, ONE lowered program for every leaf size. Returns [T+1, 2].
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, R = data.shape
    rb = rows_per_block if rows_per_block is not None else MV_RB
    assert mv_start % MV_SK == 0 and mv_planes % MV_SK == 0, \
        (mv_start, mv_planes)
    assert mv_start + mv_planes <= P, (mv_start, mv_planes, P)
    mv_blk = mv_start // MV_SK
    gh_blk, gh_off = grad_plane // 8, grad_plane % 8
    assert gh_off <= 6, grad_plane
    assert rb <= R, (rb, R)
    bh, bl, bl_bits = _mv_dims(total_bins)

    start = jnp.asarray(start, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    rs_blk = start // rb
    off = start - rs_blk * rb
    last_rel = jnp.maximum(off + count - 1, 0) // rb
    nblk = last_rel + 1
    scal = jnp.stack([rs_blk, off, count, last_rel])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mv_planes // MV_SK, nblk),
        in_specs=[
            pl.BlockSpec((MV_SK, rb),
                         lambda kc, i, scal:
                         (mv_blk + kc, scal[0] + jnp.minimum(i, scal[3]))),
            pl.BlockSpec((8, rb),
                         lambda kc, i, scal:
                         (gh_blk, scal[0] + jnp.minimum(i, scal[3]))),
        ],
        out_specs=pl.BlockSpec((2 * bh, bl), lambda kc, i, scal: (0, 0)),
        scratch_shapes=[],
    )
    out = pl.pallas_call(
        functools.partial(_mv_kernel_grid, Bh=bh, Bl=bl, bl_bits=bl_bits,
                          dtype=dtype, gh_off=gh_off, Rb=rb, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((2 * bh, bl),
                                       jnp.int32 if quant
                                       else jnp.float32),
        interpret=interpret,
    )(scal, data, data)
    return _flat_pairs(out, bh, total_bins)


# ---------------------------------------------------------------------------
# Leaf-window entry for the serial learner (row-major codes + perm)
# ---------------------------------------------------------------------------

def slot_major(codes_window: jax.Array) -> jax.Array:
    """[C, K] row-major window → [Kp, C] slot-major with the slot count
    padded to the MV_SK sublane tile (pad slots = −1)."""
    k = codes_window.shape[1]
    kp = -(-k // MV_SK) * MV_SK
    t = codes_window.T
    if kp > k:
        t = jnp.pad(t, ((0, kp - k), (0, 0)), constant_values=-1)
    return t


def gh_planes(grad: jax.Array, hess: jax.Array,
              quant: bool = False) -> jax.Array:
    """Masked [C] grad/hess → the [8, C] int32 lane planes the kernel
    reads: bitcast f32 rows 0/1, or one packed (qg<<16)|qh word row
    when ``quant`` (int32-level inputs)."""
    c = grad.shape[0]
    if quant:
        w = ((grad.astype(jnp.int32) << 16)
             | (hess.astype(jnp.int32) & 0xFFFF))
        top = w[None, :]
        rest = jnp.zeros((7, c), jnp.int32)
    else:
        top = jax.lax.bitcast_convert_type(
            jnp.stack([grad.astype(jnp.float32),
                       hess.astype(jnp.float32)]), jnp.int32)
        rest = jnp.zeros((6, c), jnp.int32)
    return jnp.concatenate([top, rest], axis=0)


def leaf_histogram_multival(codes: jax.Array, perm: jax.Array, start,
                            count, grad: jax.Array, hess: jax.Array,
                            capacity: int, total_bins: int, *,
                            use_pallas: bool, dtype=jnp.float32,
                            rows_per_block: Optional[int] = None,
                            interpret: bool = False) -> jax.Array:
    """Row-wise flat histogram of a permuted leaf window — the
    ops/histogram.leaf_histogram twin for the multival layout. codes:
    [N, K] int32 row-wise flat codes; grad/hess [N] f32 (or int32
    quantized levels — integer accumulation either way). Returns
    [T+1, 2]."""
    from .histogram import gather_leaf_rows

    rows, valid = gather_leaf_rows(perm, start, count, capacity)
    c = codes[rows]
    zero = jnp.zeros((), grad.dtype)
    g = jnp.where(valid, grad[rows], zero)
    h = jnp.where(valid, hess[rows], zero)
    if not use_pallas:
        return histogram_multival_xla(c, g, h, total_bins)
    quant = jnp.issubdtype(grad.dtype, jnp.integer)
    return histogram_multival_pallas(
        slot_major(c), gh_planes(g, h, quant=quant),
        total_bins=total_bins, dtype=dtype,
        rows_per_block=rows_per_block, interpret=interpret, quant=quant)
