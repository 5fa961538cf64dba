"""Histogram construction kernels.

The gradient/hessian histogram is THE hot loop of gradient boosting
(reference: per-feature scatter loops in src/io/dense_bin.hpp:98
``ConstructHistogramInner`` and the row-wise
src/io/multi_val_dense_bin.hpp:54 path, plus the OpenCL local-memory
atomics kernels src/treelearner/ocl/histogram{16,64,256}.cl).

TPU re-design: there are no fast global atomics on TPU, so instead of
scatter-adds we accumulate *privatized* histograms in VMEM, exactly the
shape of the reference GPU kernel's local-memory strategy but mapped to
the TPU memory hierarchy, as radix one-hot matmuls on the MXU. What a
dispatcher (``hist_method``) can select, plus the oracle:

- ``histogram_scatter``: jnp scatter-add formulation — the portable
  reference oracle (mirrors the role of GPU_DEBUG_COMPARE in
  reference gpu_tree_learner.cpp:992-1030) and the CPU-backend path
  (method ``None``).
- ``histogram_radix_pallas``: the Pallas radix kernel over row-major
  ``[rows, F]`` bin codes — the host-loop learners' TPU kernel
  (methods ``radix_pallas`` / ``radix_pallas_bf16`` of ``histogram``).
- ``histogram_planar_pallas``: the same formulation reading the fused
  growers' ``[P, R]`` planar state directly, feature chunks and row
  blocks on the grid; a static ``cap`` or, with ``cap=None``, one
  program for every leaf size.
- ``ops/multival.py`` holds the row-wise multi-value layout
  (``multival_pallas``) and its own oracle.

Histograms hold (sum_gradient, sum_hessian) per (feature, bin); bin
counts are NOT stored — like the reference (bin.h:41-42 GET_GRAD/GET_HESS,
hist entries are pairs), counts are recovered as
``round(hess * num_data / sum_hess)`` at split-scan time
(feature_histogram.hpp cnt_factor).

Output layout: ``[F, B, 2]`` float32, channel 0 = grad, 1 = hess.

Quantized-gradient mode (ops/quantize.py, config use_quantized_grad):
every kernel here also accepts INTEGER grad/hess — the stochastically
rounded levels |qg| <= 31, qh <= 63 — and then accumulates exactly,
returning ``[F, B, 2]`` int32. The MXU formulations keep their one-hot
matmuls (small-integer inputs are exact even in bfloat16, and per-chunk
partial sums stay under 2^24 so the f32 MXU accumulators are exact) and
convert each chunk's partial to int32 before the running accumulation,
so whole-dataset integer sums never round. Dispatch is by input dtype:
``jnp.issubdtype(grad.dtype, jnp.integer)``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# planar-histogram block length (lanes per grid step): the kernel is
# per-step-overhead bound below it (~1.7 us a step) and 2048 gains
# nothing (docs/PERF_NOTES.md)
PLANAR_RB = 1024


def histogram_scatter(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                      num_bins: int) -> jax.Array:
    """Scatter-add histogram: oracle + CPU path.

    bins: [C, F] integer bin codes; grad/hess: [C] float32 (zeros for
    padding rows) or int32 quantized levels. Returns [F, B, 2] in f32,
    or int32 for integer inputs (exact integer scatter-adds).
    """
    c, f = bins.shape
    b = bins.astype(jnp.int32)
    acc = (jnp.int32 if jnp.issubdtype(grad.dtype, jnp.integer)
           else jnp.float32)
    hist = jnp.zeros((f, num_bins, 2), dtype=acc)
    feat_idx = jnp.broadcast_to(jnp.arange(f, dtype=jnp.int32)[None, :], (c, f))
    vals = jnp.stack([grad, hess], axis=-1).astype(acc)          # [C, 2]
    vals = jnp.broadcast_to(vals[:, None, :], (c, f, 2))
    return hist.at[feat_idx.reshape(-1), b.reshape(-1)].add(
        vals.reshape(-1, 2), mode="drop")


# ---------------------------------------------------------------------------
# Radix one-hot matmul histogram — the MXU formulation.
#
# A bin code b < B is split into (hi, lo) nibbles, b = hi * Bl + lo. The
# per-feature histogram factorizes as a rank-revealing outer product:
#   H[f, hi, lo] = sum_r val[r] * onehot_hi[r, f, hi] * onehot_lo[r, f, lo]
# which is exactly a matmul over rows between the grad/hess-weighted hi
# one-hot and the lo one-hot. Features are processed in chunks of Fc so
# the matmul tiles fill the 128x128 MXU: M = 2*Fc*Bh (grad+hess), N =
# Fc*Bl, K = rows. The product computes all (f1, f2) cross blocks; only
# the diagonal f1 == f2 blocks are the histogram — an Fc-fold compute
# overhead traded for ~full MXU utilization, a large net win over both
# VPU masked-MAC (B-fold overhead) and XLA scatter (serialized).
# This replaces the role of the reference's GPU histogram kernels
# (src/treelearner/ocl/histogram256.cl:317 local-memory atomics).
# ---------------------------------------------------------------------------


def _radix_dims(num_bins: int) -> tuple:
    """(bh_bits, bl_bits): pow2 split of the bin space, Bl >= Bh."""
    bits = max(1, (num_bins - 1).bit_length())
    bh_bits = bits // 2
    bl_bits = bits - bh_bits
    return bh_bits, bl_bits


# ---------------------------------------------------------------------------
# Pallas radix histogram — the MXU formulation with VMEM-resident
# one-hots. An XLA einsum of the same formulation materializes the
# one-hot tensors to HBM (~2 KB/row of traffic for 28 uint8 codes); here
# each row block's one-hots live only in VMEM and the [CS, CC, 2FcBh, FcBl]
# accumulator is flushed once per super-chunk. This is the direct
# analogue of the reference GPU kernel's local-memory accumulation
# (src/treelearner/ocl/histogram256.cl:317), mapped to MXU matmuls
# instead of local atomics.
#
# Feature chunks ride the pallas GRID, not the kernel body: the grid is
# (CS super-chunks, nblk row blocks) and the body holds a CONSTANT CC
# chunk iterations, so program size no longer scales with the feature
# count — the round-4 wide-EFB compile blocker (581 bundle columns
# unrolled 73 chunks in the body and exceeded 70 min of lowering; see
# docs/SPARSE_SCALE.md). Grid order matters: row blocks are the INNER
# (fastest) dimension so each super-chunk's accumulator block stays
# VMEM-resident across its whole row sweep.
# ---------------------------------------------------------------------------


def _chunk_onehot_consts(Fc, Bh, Bl, dtype):
    """Loop-invariant expansion matrices + slot iotas for the one-hot
    build: the per-feature code value is spread across its B slots by a
    constant 0/1 expansion matmul and compared against a slot iota.
    Everything lives lane-major [*, Rb] (rows on lanes) so the main
    products are NT matmuls — no Mosaic transposes, no last-two-dim
    reshapes (Mosaic rejects those)."""
    fcl, fch = Fc * Bl, Fc * Bh
    ex_lo = (jax.lax.broadcasted_iota(jnp.int32, (fcl, Fc), 0) // Bl ==
             jax.lax.broadcasted_iota(jnp.int32, (fcl, Fc), 1)).astype(dtype)
    slot_lo = (jax.lax.broadcasted_iota(
        jnp.int32, (fcl, 1), 0) % Bl).astype(jnp.float32)
    ex_hi = (jax.lax.broadcasted_iota(jnp.int32, (fch, Fc), 0) // Bh ==
             jax.lax.broadcasted_iota(jnp.int32, (fch, Fc), 1)).astype(dtype)
    slot_hi = (jax.lax.broadcasted_iota(
        jnp.int32, (fch, 1), 0) % Bh).astype(jnp.float32)
    return ex_lo, slot_lo, ex_hi, slot_hi


def _chunk_partials(lo_c, hi_c, g_t, h_t, *, Fc, Bh, Bl, dtype,
                    int_out=False):
    """One feature chunk's histogram partial: (pg, ph) each
    [Fc*Bh, Fc*Bl], from the chunk's low/high code rows [Fc, Rb] (already
    in ``dtype``) and the masked grad/hess lane rows [1, Rb].

    Shared by the row-major kernel (`_accum_chunks`) and the planar
    grid body (`_radix_planar_kernel_grid`): same operands, same matmul
    shapes, same f32 accumulators."""
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    ex_lo, slot_lo, ex_hi, slot_hi = _chunk_onehot_consts(Fc, Bh, Bl, dtype)
    mlo_t = (jnp.dot(ex_lo, lo_c, preferred_element_type=jnp.float32)
             == slot_lo).astype(dtype)            # [Fc*Bl, Rb]
    mhi_t = (jnp.dot(ex_hi, hi_c, preferred_element_type=jnp.float32)
             == slot_hi)                          # [Fc*Bh, Rb] bool
    ag = mhi_t.astype(dtype) * g_t
    ah = mhi_t.astype(dtype) * h_t
    pg = jax.lax.dot_general(
        ag, mlo_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    ph = jax.lax.dot_general(
        ah, mlo_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    if int_out:
        pg = pg.astype(jnp.int32)
        ph = ph.astype(jnp.int32)
    return pg, ph


def _accum_chunks(ct, g_t, h_t, out_ref, *, CC, Fc, Bh, Bl, bl_bits, dtype,
                  int_out=False):
    """Accumulate CC feature chunks of ``ct`` [CC*Fc, Rb] into
    ``out_ref`` [1, CC, 2*Fc*Bh, Fc*Bl] (one super-chunk's block).

    ``int_out``: out_ref is int32 and g_t/h_t hold quantized levels —
    the per-block matmul partial (exact in its f32 accumulator, bounded
    by Rb * qmax < 2^24) is snapped to int32 before accumulating."""
    lo_t = (ct & (Bl - 1)).astype(dtype)
    hi_t = (ct >> bl_bits).astype(dtype)
    fch = Fc * Bh
    for c in range(CC):
        lo_c = lo_t[c * Fc:(c + 1) * Fc, :]       # [Fc, Rb]
        hi_c = hi_t[c * Fc:(c + 1) * Fc, :]
        pg, ph = _chunk_partials(lo_c, hi_c, g_t, h_t, Fc=Fc, Bh=Bh, Bl=Bl,
                                 dtype=dtype, int_out=int_out)
        out_ref[0, c, 0:fch, :] += pg
        out_ref[0, c, fch:2 * fch, :] += ph


def _radix_pallas_kernel(codes_t_ref, gh_t_ref, out_ref, *, CC, Fc,
                         Bh, Bl, bl_bits, dtype, int_out=False):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ct = codes_t_ref[...].astype(jnp.int32)       # [CC*Fc, Rb]
    g_t = gh_t_ref[0:1, :].astype(dtype)          # [1, Rb]
    h_t = gh_t_ref[1:2, :].astype(dtype)
    _accum_chunks(ct, g_t, h_t, out_ref, CC=CC, Fc=Fc, Bh=Bh, Bl=Bl,
                  bl_bits=bl_bits, dtype=dtype, int_out=int_out)


# tpulint: jit-ok(kernel entry; dispatched through manager-registered learner entries)
@functools.partial(jax.jit, static_argnames=("num_bins", "dtype",
                                             "rows_per_block", "interpret"))
def histogram_radix_pallas(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                           num_bins: int, dtype=jnp.float32,
                           rows_per_block: int = 512,
                           interpret: bool = False) -> jax.Array:
    """Pallas radix histogram. Contract of histogram_scatter.

    Padded features carry code 0 but contribute only to feature slots
    >= f, which the diagonal extraction drops; padded rows carry zero
    grad/hess weights.
    """
    from jax.experimental import pallas as pl

    r, f = bins.shape
    int_out = jnp.issubdtype(grad.dtype, jnp.integer)
    bh_bits, bl_bits = _radix_dims(num_bins)
    Bh, Bl = 1 << bh_bits, 1 << bl_bits
    Fc = max(1, 128 // Bl)
    # super-chunk = the feature rows of one grid step, tile-aligned on
    # the sublane dim (u8 tiles are 32 sublanes, i32 tiles 8)
    use_u8 = num_bins <= 256
    SPf = max(32 if use_u8 else 8, Fc)
    CC = SPf // Fc
    C = -(-f // Fc)
    CS = -(-C // CC)
    Fp = CS * SPf

    b = bins.astype(jnp.uint8) if use_u8 else bins.astype(jnp.int32)
    if Fp > f:
        b = jnp.pad(b, ((0, 0), (0, Fp - f)), constant_values=0)
    nblk = max(1, -(-r // rows_per_block))
    pad_r = nblk * rows_per_block - r
    # quantized levels ride the f32 lanes exactly (|level| < 2^16)
    gh_t = jnp.stack([grad.astype(jnp.float32),
                      hess.astype(jnp.float32)], axis=0)       # [2, r]
    if pad_r:
        b = jnp.pad(b, ((0, pad_r), (0, 0)))
        gh_t = jnp.pad(gh_t, ((0, 0), (0, pad_r)))

    out = pl.pallas_call(
        functools.partial(_radix_pallas_kernel, CC=CC, Fc=Fc, Bh=Bh, Bl=Bl,
                          bl_bits=bl_bits, dtype=dtype, int_out=int_out),
        grid=(CS, nblk),
        in_specs=[
            pl.BlockSpec((SPf, rows_per_block), lambda s, i: (s, i)),
            pl.BlockSpec((2, rows_per_block), lambda s, i: (0, i)),  # tpulint: tile-ok(gh rides as one [2, R] pair block; sublane 2 pads to 8 once per block, far below the 4x cost of row-major replication)
        ],
        out_specs=pl.BlockSpec((1, CC, 2 * Fc * Bh, Fc * Bl),
                               lambda s, i: (s, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((CS, CC, 2 * Fc * Bh, Fc * Bl),
                                       jnp.int32 if int_out
                                       else jnp.float32),
        interpret=interpret,
    )(b.T, gh_t)

    # extract diagonal f1 == f2 blocks → [C, 2, Fc, Bh, Fc, Bl]
    h_all = out.reshape(CS * CC, 2, Fc, Bh, Fc, Bl)
    idx = jnp.arange(Fc)
    hd = h_all[:, :, idx, :, idx, :]          # [Fc, C, 2, Bh, Bl]
    hd = jnp.transpose(hd, (1, 0, 3, 4, 2))   # [C, Fc, Bh, Bl, 2]
    hd = hd.reshape(Fp, Bh * Bl, 2)[:f, :num_bins, :]
    return hd


# ---------------------------------------------------------------------------
# Planar-native radix histogram: same MXU formulation, but reading the
# [P, R] planar training state of ops/plane.py DIRECTLY — the per-
# feature code rows are unpacked from the int32 code planes in-kernel
# (static byte shifts), grad/hess are bitcast from their planes, and the
# leaf window is masked by prefetched [off, count) scalars. This removes
# the planar→row-major bridge (a transpose + two extra HBM passes per
# histogram) that profiling showed as the dominant copy cost after the
# partition kernel landed.
# ---------------------------------------------------------------------------


def planar_grid_dims(num_bins: int, code_bits: int, num_cols: int):
    """Static grid geometry of the planar histogram kernel.

    Returns (Fc, SP, CC, CS): Fc features per matmul chunk, SP planes
    per super-chunk (the sublane extent of one grid step's code block, a
    multiple of 8), CC chunks per super-chunk, CS super-chunks (the grid's
    dimension 0 is the CS * CC flat chunks). The planar path is viable iff
    CS * SP <= layout.num_planes (callers guard on this)."""
    _, bl_bits = _radix_dims(num_bins)
    Bl = 1 << bl_bits
    Fc = max(1, 128 // Bl)
    # chunks must cover whole planes: Fc*code_bits multiple of 32
    while (Fc * code_bits) % 32:
        Fc *= 2
    k = 32 // code_bits                 # codes per plane
    ppc = Fc // k                       # planes per chunk (power of 2)
    SP = max(8, ppc)
    CC = SP // ppc
    C = -(-num_cols // Fc)
    CS = -(-C // CC)
    return Fc, SP, CC, CS


def _radix_planar_kernel_grid(scal, codes_ref, gh_ref, out_ref, *, CC, Fc,
                              Bh, Bl, bl_bits, dtype, code_bits, gh_off,
                              Rb, SP, quant=False):
    """Grid-parameterized planar body: ONE feature chunk per grid step.

    Grid is (C, nblk) with C = CS*CC flat chunks — the chunk loop rides
    the grid, not the body, so the lowered program holds exactly one
    chunk's matmuls no matter how wide the dataset is (the round-4
    70-minute Mosaic lowering cliff is structurally impossible: program
    size is constant in the column count, which only appears in the
    grid bounds).

    The codes block is the chunk's parent SP-plane block (index c//CC),
    so within a super-chunk the same block is fetched once per chunk per
    row block — CC× the DMA of a body that unrolls the super-chunk, but
    the kernel is one-hot-VPU-bound (~16 us compute vs ~80 ns DMA per
    step at Rb=1024) and the pipeline overlaps the refetch. The chunk's
    Fc code rows are selected from the unpacked [CC*Fc, Rb] block by a
    masked sum over the CC static sub-slices (int32-exact; Mosaic has
    no dynamic sublane slice), keyed on the traced chunk id."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    # which of the super-chunk's CC chunks this step owns
    cc = jax.lax.rem(pl.program_id(0), CC)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # blocks past the leaf range contribute nothing: skip their compute
    # entirely (their index_map is pinned to the last active block, so
    # the pipeline does not even refetch them)
    @pl.when(i <= scal[3])
    def _active():
        x = codes_ref[...]                         # [SP, Rb] i32
        off, count = scal[1], scal[2]
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, Rb), 1) + i * Rb
        valid = ((pos >= off) & (pos < off + count)).astype(jnp.float32)

        if quant:
            # packed (qg << 16 | qh) words in the grad plane: one row
            # read instead of two, levels exact in any matmul dtype
            w = gh_ref[gh_off:gh_off + 1, :]       # [1, Rb] i32
            g_t = ((w >> 16).astype(jnp.float32) * valid).astype(dtype)
            h_t = ((w & 0xFFFF).astype(jnp.float32) * valid).astype(dtype)
        else:
            gh = jax.lax.bitcast_convert_type(
                gh_ref[gh_off:gh_off + 2, :], jnp.float32)
            g_t = (gh[0:1, :] * valid).astype(dtype)
            h_t = (gh[1:2, :] * valid).astype(dtype)

        # unpack this super-chunk's feature code rows from its packed
        # planes: k codes per plane, feature f = plane*k + j at bit
        # j*code_bits (ops/plane.py little-endian packing; 4-bit =
        # IS_4BIT analogue)
        k = 32 // code_bits
        mask = (1 << code_bits) - 1
        Fsp = SP * k                               # = CC * Fc
        e = jnp.broadcast_to(x[:, None, :], (SP, k, Rb)).reshape(Fsp, Rb)
        sh = (jax.lax.broadcasted_iota(jnp.int32, (Fsp, 1), 0) % k) \
            * code_bits
        ct = jax.lax.shift_right_logical(e, sh) & mask     # [Fsp, Rb]
        if CC == 1:
            ck = ct
        else:
            ck = jnp.zeros((Fc, Rb), jnp.int32)
            for j in range(CC):
                ck = ck + jnp.where(cc == j, ct[j * Fc:(j + 1) * Fc, :], 0)
        lo_c = (ck & (Bl - 1)).astype(dtype)
        hi_c = (ck >> bl_bits).astype(dtype)
        pg, ph = _chunk_partials(lo_c, hi_c, g_t, h_t, Fc=Fc, Bh=Bh, Bl=Bl,
                                 dtype=dtype, int_out=quant)
        fch = Fc * Bh
        out_ref[0, 0:fch, :] += pg
        out_ref[0, fch:2 * fch, :] += ph


# tpulint: jit-ok(kernel entry; dispatched through manager-registered learner entries)
@functools.partial(jax.jit, static_argnames=("num_bins", "num_cols",
                                             "code_bits", "grad_plane",
                                             "cap", "dtype",
                                             "rows_per_block", "interpret",
                                             "quant"))
def histogram_planar_pallas(data: jax.Array, start, count, *, num_bins: int,
                            num_cols: int, code_bits: int, grad_plane: int,
                            cap: Optional[int] = None, dtype=jnp.float32,
                            rows_per_block: Optional[int] = None,
                            interpret: bool = False,
                            quant: bool = False) -> jax.Array:
    """Leaf-window histogram straight off the planar state.

    data: [P, R] int32 planar training rows; the window is the lane
    range [start, start+count).

    ``cap=None`` (the default) is the grid-parameterized mode: the row
    blocks ride a DYNAMIC grid dimension sized `last_block + 1` from the
    traced window scalars, so ONE lowered program serves every leaf size
    — the capacity ladder that used to pick a static `cap` per leaf
    bucket collapses to this single call. ``cap=<int>`` keeps the static
    `cap//Rb + 1` block sweep (every block past the window skipped via
    the prefetched scalars) for callers that need a shape-stable grid.

    Feature chunks ride the grid too (grid=(CS*CC, nblk)), so program
    size is constant in the column count.

    Returns [num_cols, num_bins, 2] f32 — or int32 when ``quant``, in
    which case the grad plane holds packed ``(qg << 16) | qh`` level
    words (ops/quantize.py) and accumulation is exact integer.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, R = data.shape
    Rb = rows_per_block if rows_per_block is not None else PLANAR_RB
    bh_bits, bl_bits = _radix_dims(num_bins)
    Bh, Bl = 1 << bh_bits, 1 << bl_bits
    Fc, SP, CC, CS = planar_grid_dims(num_bins, code_bits, num_cols)
    if CS * SP > P:
        raise ValueError(
            f"planar histogram needs {CS * SP} readable planes, state has "
            f"{P} — caller must fall back to the row-major path")
    # grad+hess must sit inside one aligned (8, Rb) block
    # (plane.make_layout guarantees grad % 8 <= 6)
    gh_blk, gh_off = grad_plane // 8, grad_plane % 8
    assert gh_off <= 6, grad_plane
    assert Rb <= R, (Rb, R)

    start = jnp.asarray(start, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    if cap is not None:
        assert cap % Rb == 0, (cap, Rb)  # window coverage needs Rb | cap
        nblk = cap // Rb + 1
        assert nblk * Rb <= R
        rs_blk = jnp.clip(start // Rb, 0, R // Rb - nblk)
    else:
        # dynamic mode: the window [start, start+count) always lies in
        # [0, R), so the unclamped block start fits and nblk is exactly
        # the covered block count (>= 1 so the i==0 init always fires)
        rs_blk = start // Rb
    off = start - rs_blk * Rb
    last_rel = jnp.maximum(off + count - 1, 0) // Rb
    if cap is None:
        nblk = last_rel + 1
    scal = jnp.stack([rs_blk, off, count, last_rel])

    in_specs = [
        pl.BlockSpec(
            (SP, Rb),
            lambda c, i, scal: (c // CC,
                                scal[0] + jnp.minimum(i, scal[3]))),
        # the same gh block is re-fetched once per chunk per row
        # block. Deliberate: the kernel is
        # one-hot-VPU-bound (~16 us compute vs ~80 ns DMA per step at
        # Rb=1024), and the alternative — a pre-sliced [2, R] gh
        # operand — costs an XLA copy of two full planes per call
        pl.BlockSpec(
            (8, Rb),
            lambda s, i, scal: (gh_blk,
                                scal[0] + jnp.minimum(i, scal[3]))),
    ]
    grid = (CS * CC, nblk)
    out_specs = pl.BlockSpec((1, 2 * Fc * Bh, Fc * Bl),
                             lambda c, i, scal: (c, 0, 0))
    out_shape = jax.ShapeDtypeStruct((CS * CC, 2 * Fc * Bh, Fc * Bl),
                                     jnp.int32 if quant
                                     else jnp.float32)
    body = functools.partial(
        _radix_planar_kernel_grid, CC=CC, Fc=Fc, Bh=Bh, Bl=Bl,
        bl_bits=bl_bits, dtype=dtype, code_bits=code_bits,
        gh_off=gh_off, Rb=Rb, SP=SP, quant=quant)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[],
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(scal, data, data)

    h_all = out.reshape(CS * CC, 2, Fc, Bh, Fc, Bl)
    idx = jnp.arange(Fc)
    hd = h_all[:, :, idx, :, idx, :]
    hd = jnp.transpose(hd, (1, 0, 3, 4, 2))
    hd = hd.reshape(CS * CC * Fc, Bh * Bl, 2)[:num_cols, :num_bins, :]
    return hd


def _use_tpu() -> bool:
    return jax.default_backend() == "tpu"


def hist_layout(config, dataset=None) -> str:
    """Occupancy-driven histogram LAYOUT decision: "planar" (column
    bin-plane kernels) or "multival" (row-wise packed present-code
    kernels, ops/multival.py). Pure function of config + the dataset's
    construct-time occupancy statistics — no backend check, so tests
    exercise it on CPU and the decision folds into AOT signatures.

    ``tpu_hist_layout`` overrides; "auto" picks multival exactly when
    the shape is wide AND sparse: measured occupancy exists, the group
    count clears MULTIVAL_MIN_GROUPS (narrow shapes like HIGGS keep the
    planar kernel — its per-plane pass is already cheap), and the mean
    present-codes-per-row is at most MULTIVAL_MAX_OCCUPANCY of the
    group count (the multival gather does K*T MAC work per row vs the
    planar kernel's T — it only wins when K << G)."""
    from .multival import MULTIVAL_MIN_GROUPS, MULTIVAL_MAX_OCCUPANCY
    if config.tpu_hist_layout != "auto":
        return config.tpu_hist_layout
    occ = getattr(dataset, "occupancy", None) if dataset is not None \
        else None
    if (occ is not None and occ.num_groups >= MULTIVAL_MIN_GROUPS
            and occ.row_nnz_mean
            <= MULTIVAL_MAX_OCCUPANCY * occ.num_groups):
        return "multival"
    return "planar"


def _note_layout(layout: str, occ) -> None:
    """Telemetry: which layout the dispatcher picked, and the measured
    occupancy behind the decision (obs schema minor 10)."""
    from ..obs import active
    reg = active()
    if reg is None:
        return
    reg.inc(f"hist.layout_{layout}")
    if occ is not None:
        reg.set_gauge("hist.row_nnz_mean", float(occ.row_nnz_mean))


def hist_method(config, dataset=None) -> Optional[str]:
    """The ONE backend/dtype histogram dispatch, shared by every learner
    (serial, host-loop parallel, fused) — they must agree on histogram
    precision or their trees diverge beyond f32 noise. On TPU: the
    pallas radix kernel over the planar layout, bfloat16 inputs by
    default (the reference GPU learner's single-precision histograms,
    gpu_use_dp=false — AUC-neutral, 2x MXU rate) or float32 per
    tpu_hist_dtype; or "multival_pallas" when hist_layout() picks the
    row-wise multi-value layout for this dataset (wide-sparse shapes —
    requires the dataset handle with construct-time occupancy stats;
    callers without one, e.g. the host-loop parallel learners, keep
    planar). ``None`` selects the portable XLA paths — scatter
    histogram and argsort partition, the oracles: on any backend that
    is not a TPU (Mosaic lowers nowhere else; engine.train warns), and
    on a TPU when the user asks for ``device_type=cpu``. Note
    "multival_pallas" does NOT encode a dtype suffix: the multival
    kernels read precision from tpu_hist_dtype directly."""
    if config.device_type != "tpu" or not _use_tpu():
        return None
    occ = getattr(dataset, "occupancy", None) if dataset is not None \
        else None
    layout = hist_layout(config, dataset)
    if layout == "multival" and occ is not None:
        _note_layout("multival", occ)
        return "multival_pallas"
    _note_layout("planar", occ)
    return ("radix_pallas" if config.tpu_hist_dtype == "float32"
            else "radix_pallas_bf16")


def histogram(bins: jax.Array, grad: jax.Array, hess: jax.Array,
              num_bins: int, method: Optional[str] = None) -> jax.Array:
    """Histogram [F, B, 2] of row-major bin codes by ``method``: what
    hist_method returns for a column-major caller. None is the scatter
    oracle; anything else raises."""
    if method == "multival_pallas":
        # the multival kernels take packed row-wise codes, not [n, F]
        # bin matrices — learners route them through ops/multival.py
        # entry points, never through this column-major dispatch
        raise ValueError(
            "multival_pallas is not a column-major histogram method; "
            "use ops.multival.leaf_histogram_multival")
    if method == "radix_pallas":
        return histogram_radix_pallas(bins, grad, hess, num_bins)
    if method == "radix_pallas_bf16":
        return histogram_radix_pallas(bins, grad, hess, num_bins,
                                      dtype=jnp.bfloat16)
    if method is None:
        return histogram_scatter(bins, grad, hess, num_bins)
    raise ValueError(
        f"unknown histogram method {method!r}: hist_method() returns "
        "None, 'radix_pallas' or 'radix_pallas_bf16' for a column-major "
        "caller")


# ---------------------------------------------------------------------------
# Leaf-gather helpers (capacity-padded; reference analogue: the
# ordered_gradients_/ordered_hessians_ gather in serial_tree_learner.cpp
# and DataPartition's contiguous per-leaf index ranges).
# ---------------------------------------------------------------------------

def leaf_window(perm: jax.Array, start, count, capacity: int):
    """Capacity-padded window of the permutation array covering a leaf.

    ``start``/``count`` are traced scalars; ``capacity`` is static
    (count rounded up to a power of two by the caller so jit
    specializations are bounded and reusable). When the window would run
    past the end of ``perm`` the read start is clamped left, so the
    leaf's rows sit at offset ``start - read_start`` inside the window —
    ``valid`` marks exactly the leaf's rows.

    Returns (rows_raw, valid, read_start): raw window contents (NOT
    clamped — positions outside ``valid`` hold other leaves' rows or
    zero padding), the in-leaf mask, and where the window was read from.
    """
    start = jnp.asarray(start, jnp.int32)
    n = perm.shape[0]
    read_start = jnp.minimum(start, max(n - capacity, 0))
    rows = jax.lax.dynamic_slice(perm, (read_start,), (min(capacity, n),))
    if capacity > n:
        rows = jnp.pad(rows, (0, capacity - n))
    off = start - read_start
    pos = jnp.arange(capacity, dtype=jnp.int32)
    valid = (pos >= off) & (pos < off + count)
    return rows, valid, read_start


def gather_leaf_rows(perm: jax.Array, start, count, capacity: int):
    """Leaf row ids padded to ``capacity``; non-leaf positions clamped to
    row 0 and flagged invalid (for masked gathers)."""
    rows, valid, _ = leaf_window(perm, start, count, capacity)
    return jnp.where(valid, rows, 0), valid


def leaf_histogram(bins_full: jax.Array, perm: jax.Array, start, count,
                   grad: jax.Array, hess: jax.Array, capacity: int,
                   num_bins: int, method: Optional[str] = None) -> jax.Array:
    """Histogram of one leaf's rows (the reference's ConstructHistograms
    for the smaller leaf, serial_tree_learner.cpp:333): gather bin rows +
    ordered grad/hess by the leaf's index range, then histogram."""
    rows, valid = gather_leaf_rows(perm, start, count, capacity)
    b = bins_full[rows]
    zero = jnp.zeros((), grad.dtype)  # int levels must stay int
    g = jnp.where(valid, grad[rows], zero)
    h = jnp.where(valid, hess[rows], zero)
    return histogram(b, g, h, num_bins, method=method)
