"""On-device parity of the production Pallas kernels against their XLA
oracles, and Mosaic lower-and-compile of the kernels the dispatcher can
select off the main path.

Partition: both kernels (`partition_pallas` v1, `partition_pallas2` v2)
against `partition_ref`, in the static `cap=<int>` sweep and in the
dynamic-grid `cap=None` mode the fused grower runs. The result must be
EXACT (every plane, every lane, and the left count).

Histogram: `histogram_planar_pallas(cap=None)` against the scatter-add
oracle on the same window. Quantized-int accumulation must be exact;
the bf16 kernel is compared with the oracle fed the same bf16-rounded
weights, so only the f32 accumulation order differs (tolerance
`HIST_RTOL` of the bin's absolute mass). The oracle is
`histogram_scatter`'s contract computed on the HOST (`host_histogram`,
the sequential numpy form tests/test_ops.py pins `histogram_scatter`
to): on the chip XLA needs over a minute to compile each scatter-add
at these window sizes (my chip run, PR 21: 78.8 s and 69.1 s for one
f32 and one int32 scatter over 73,728 x 28 codes), which is what this
check would then mostly measure.

Traverse: `traverse_planes_pallas` (the per-tree tier's all-row leaf
assignment under row sampling: every split of a tree replayed on a lane
tile held in VMEM) against `traverse_planes_ref`, the XLA loop that
makes a pass over all lanes per split, at `expo700goss.train`'s
geometry: 3 code planes x 22M lanes, 254 splits on bundled columns. The
leaf ids must be EQUAL; both times are printed in ns a lane-split.

`python scripts/kernel_check.py` runs the checks on random states and
exits non-zero on any mismatch, or when JAX finds no TPU (Mosaic lowers
nowhere else). `chip_smoke.py` imports the check functions and runs them
on windows cut from its own trained planar state.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import multival as MV
from lightgbm_tpu.ops import plane
from lightgbm_tpu.ops import quantize as Q

# bf16 kernel vs the scatter oracle on bf16-rounded weights: per-bin
# |diff| <= HIST_RTOL * (sum of |weight| in the bin) + HIST_ATOL
HIST_RTOL = 1e-4
HIST_ATOL = 1e-4

PARTITION_KERNELS = {"pallas": plane.partition_pallas,
                     "pallas2": plane.partition_pallas2}


def require_tpu() -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"kernel_check needs a TPU: JAX initialised the "
            f"{jax.default_backend()} backend, where Mosaic cannot lower "
            "the kernels (the CPU tier runs them with interpret=True in "
            "tests/)")


def window_state(data, layout, rows: int):
    """(data_w, layout_w): the first lanes of a planar state as a
    self-contained state of `rows` rows with the same plane geometry.
    Valid when the source holds at least layout_w.num_lanes real rows
    (every lane cut is then a real, possibly permuted, row)."""
    Lw = plane.make_layout(
        layout.num_cols, layout.code_bits, rows,
        with_label=layout.label >= 0, with_score=layout.score >= 0,
        with_weight=layout.weight >= 0, tile=layout.tile,
        mv_planes=layout.mv_planes)
    assert Lw.num_planes == layout.num_planes
    assert Lw.num_lanes <= layout.num_rows, (Lw.num_lanes, layout.num_rows)
    return data[:, :Lw.num_lanes], Lw


def check_partition(data, layout, start, count, rscal, *, kernel: str,
                    dynamic: bool, tile=None, interpret: bool = False):
    """One kernel vs partition_ref on one window. Returns a result dict
    with `ok` true iff every plane and lane and the left count agree."""
    t0 = time.perf_counter()
    S = tile if tile is not None else layout.tile
    ref, nl_ref = plane.partition_ref(data, layout, start, count, rscal,
                                      cap=layout.num_lanes - layout.tile)
    cap = layout.num_lanes - S      # static sweep over the whole state
    # (the kernels alias input to output; without donation XLA copies,
    # so `data` survives the call)
    got, nl = PARTITION_KERNELS[kernel](
        data, layout, start, count, rscal,
        cap=None if dynamic else cap, tile=tile, interpret=interpret)
    equal = bool(jnp.all(ref == got))
    return {"check": f"partition/{kernel}/"
                     f"{'cap=None' if dynamic else f'cap={cap}'}",
            "start": int(start), "count": int(count),
            "nleft_ref": int(nl_ref), "nleft": int(nl),
            "ok": equal and int(nl_ref) == int(nl),
            "seconds": round(time.perf_counter() - t0, 1)}


def _window_rows(data, layout, start, count):
    """Host (codes [n, G], gh [n, 2] raw f32 lanes) of a state's window."""
    codes, gh = plane.window_rowmajor(data, layout, 0, cap=layout.num_lanes)
    sel = slice(int(start), int(start) + int(count))
    return np.asarray(codes)[sel], np.asarray(gh)[sel]


def host_histogram(codes, g, h, num_bins: int):
    """[F, B, 2] scatter-add of per-row (g, h) at (feature, code), in
    float64 (exact for integer weights)."""
    n, f = codes.shape
    flat = (np.arange(f)[None, :] * num_bins + codes).reshape(-1)
    return np.stack(
        [np.bincount(flat, np.broadcast_to(w[:, None], (n, f)).reshape(-1),
                     minlength=f * num_bins) for w in (g, h)],
        axis=-1).reshape(f, num_bins, 2)


def check_histogram(data, layout, start, count, num_bins: int, *,
                    dtype=jnp.bfloat16, rows_per_block=None,
                    interpret: bool = False):
    """histogram_planar_pallas(cap=None) vs histogram_scatter on the
    window [start, start+count) of a planar state (f32 grad/hess)."""
    t0 = time.perf_counter()
    codes, gh = _window_rows(data, layout, start, count)
    gh = np.asarray(jnp.asarray(gh).astype(dtype).astype(jnp.float32))
    ref = host_histogram(codes, gh[:, 0], gh[:, 1], num_bins)
    mass = host_histogram(codes, np.abs(gh[:, 0]), np.abs(gh[:, 1]),
                          num_bins)
    got = np.asarray(H.histogram_planar_pallas(
        data, start, count, num_bins=num_bins, num_cols=layout.num_cols,
        code_bits=layout.code_bits, grad_plane=layout.grad, cap=None,
        dtype=dtype, rows_per_block=rows_per_block, interpret=interpret))
    err = np.abs(got - ref)
    bound = HIST_RTOL * mass + HIST_ATOL
    return {"check": f"histogram/planar/{jnp.dtype(dtype).name}/cap=None",
            "start": int(start), "count": int(count),
            "max_err": float(err.max()),
            "max_err_over_bound": float((err / bound).max()),
            "ok": bool((err <= bound).all()),
            "seconds": round(time.perf_counter() - t0, 1)}


def check_histogram_quant(data, layout, start, count, num_bins: int, *,
                          seed: int = 0, rows_per_block=None,
                          interpret: bool = False):
    """Quantized-int mode: the grad plane is overwritten with packed
    (qg << 16 | qh) level words; the kernel's int32 sums must equal the
    integer scatter oracle exactly."""
    t0 = time.perf_counter()
    R = layout.num_lanes
    kg, kh = jax.random.split(jax.random.PRNGKey(seed))
    qg = jax.random.randint(kg, (R,), -31, 32, jnp.int32)
    qh = jax.random.randint(kh, (R,), 0, 64, jnp.int32)
    qdata = plane.set_gh_packed(data, layout,
                                plane.i32_as_f32(Q.pack_gh(qg, qh)))
    codes, _ = _window_rows(qdata, layout, start, count)
    sel = slice(int(start), int(start) + int(count))
    ref = host_histogram(codes, np.asarray(qg)[sel], np.asarray(qh)[sel],
                         num_bins)
    got = np.asarray(H.histogram_planar_pallas(
        qdata, start, count, num_bins=num_bins, num_cols=layout.num_cols,
        code_bits=layout.code_bits, grad_plane=layout.grad, cap=None,
        dtype=jnp.bfloat16, rows_per_block=rows_per_block, quant=True,
        interpret=interpret))
    return {"check": "histogram/planar/quant-int/cap=None",
            "start": int(start), "count": int(count),
            "ok": got.dtype == np.int32 and bool((got == ref).all()),
            "seconds": round(time.perf_counter() - t0, 1)}


def random_state(n, g, seed, *, bits=8, tile=2048):
    rng = np.random.RandomState(seed)
    hi = 250 if bits == 8 else 16
    codes = rng.randint(0, hi, size=(n, g)).astype(np.uint8)
    layout = plane.make_layout(g, bits, n, with_label=True, with_score=True,
                               tile=tile)
    cp = plane.build_codes_planes(jnp.asarray(codes), layout)
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(rng.rand(n).astype(np.float32))
    data = plane.build_data(layout, cp, grad, hess, label=grad, score=hess)
    return data, layout


def random_tree(rng, num_leaves: int, n_splits: int, features, max_bin: int,
                *, cat_features=(), chain: bool = False):
    """Tree arrays (the dict treelearner/fused.py grows, on the host) of
    a tree made by ``n_splits`` random splits in Tree::Split numbering:
    node k splits a leaf slot s, its left child keeps s and its right
    child is leaf k + 1. ``chain`` splits slot 0 every time (the longest
    chain of left children a tree of that size can have); splits on
    ``cat_features`` are categorical with bits in all eight words."""
    i32 = np.int32
    ta = {"n_leaves": i32(n_splits + 1),
          "split_feature": np.zeros(num_leaves - 1, i32),
          "threshold_bin": np.zeros(num_leaves - 1, i32),
          "default_left": np.zeros(num_leaves - 1, bool),
          "split_cat": np.zeros(num_leaves - 1, bool),
          "split_bits": np.zeros((num_leaves - 1, plane.CAT_WORDS), i32),
          "left_child": np.zeros(num_leaves - 1, i32),
          "right_child": np.zeros(num_leaves - 1, i32)}
    held_by = {0: None}            # leaf slot -> (node, side) that holds it
    for k in range(n_splits):
        s = 0 if chain else int(rng.randint(0, k + 1))
        f = int(rng.choice(features))
        ta["split_feature"][k] = f
        ta["threshold_bin"][k] = rng.randint(0, max_bin)
        ta["default_left"][k] = rng.rand() < 0.5
        if f in cat_features:
            ta["split_cat"][k] = True
            ta["split_bits"][k] = rng.randint(
                -2 ** 31, 2 ** 31, plane.CAT_WORDS, dtype=np.int64).astype(i32)
        if held_by[s] is not None:
            node, side = held_by[s]
            ta[side][node] = k
        ta["left_child"][k], ta["right_child"][k] = ~s, ~(k + 1)
        held_by[s], held_by[k + 1] = (k, "left_child"), (k, "right_child")
    return {key: jnp.asarray(v) for key, v in ta.items()}


def check_traverse(codes_planes, layout, ta, miss_bin, efb_dev=None, *,
                   interpret: bool = False, reps: int = 1):
    """traverse_planes_pallas vs traverse_planes_ref on one tree: `ok`
    iff every lane's leaf id agrees; host seconds of each (a blocked
    call, after one that compiled) as ns a lane-split."""
    t0 = time.perf_counter()
    ref_fn = jax.jit(lambda cp, ta: plane.traverse_planes_ref(
        cp, layout, ta, miss_bin, efb_dev))
    got_fn = jax.jit(lambda cp, ta: plane.traverse_planes_pallas(
        cp, plane.traverse_table(layout, ta, miss_bin, efb_dev),
        interpret=interpret))

    def timed(fn):
        out = jax.block_until_ready(fn(codes_planes, ta))
        t = time.perf_counter()
        for _ in range(reps):
            out = jax.block_until_ready(fn(codes_planes, ta))
        return out, (time.perf_counter() - t) / reps

    ref, ref_s = timed(ref_fn)
    got, got_s = timed(got_fn)
    work = max(codes_planes.shape[1] * (int(ta["n_leaves"]) - 1), 1)
    return {"check": f"traverse/{codes_planes.shape[0]}x"
                     f"{codes_planes.shape[1]}/{int(ta['n_leaves']) - 1} "
                     "splits",
            "leaves_seen": int(jnp.max(got)) + 1,
            "xla_ns_per_lane_split": ref_s / work * 1e9,
            "pallas_ns_per_lane_split": got_s / work * 1e9,
            "ok": bool(jnp.all(ref == got)),
            "seconds": round(time.perf_counter() - t0, 1)}


def bundled_tables(num_features: int, num_cols: int):
    """(group_of, offset_of, nslots_of, skip_of) of ``num_features``
    features spread evenly over ``num_cols`` bundle columns, three codes
    each from code 1 up, bin 0 skipped (a one-hot column's default):
    the shape of `expo700goss`'s bundles."""
    f = np.arange(num_features)
    per_col = -(-num_features // num_cols)
    dev = (f // per_col, 1 + (f % per_col) * 3, np.full(num_features, 3),
           np.zeros(num_features))
    return tuple(jnp.asarray(t, jnp.int32) for t in dev)


def traverse_at_cell_geometry(seed: int = 0):
    """`expo700goss.train`'s traverse: 22M rows in 3 planes of 8-bit
    codes (11 bundle columns of 700 features), 255 leaves."""
    features, cols, leaves = 700, 11, 255
    layout = plane.make_layout(cols, 8, 22_000_000)
    codes_planes = jax.random.bits(
        jax.random.PRNGKey(seed), (layout.code_planes, layout.num_lanes),
        jnp.uint32).view(jnp.int32)
    ta = random_tree(np.random.RandomState(seed), leaves, leaves - 1,
                     np.arange(features), 4)
    return check_traverse(codes_planes, layout, ta,
                          jnp.full(features, -1, jnp.int32),
                          bundled_tables(features, cols), reps=3)


# ---------------------------------------------------------------------------
# Mosaic lower-and-compile of the kernels the dispatcher can select off
# the HIGGS main path, from ShapeDtypeStructs at one real geometry each
# ---------------------------------------------------------------------------

def compile_only(name: str, fn, avals, donate=()):
    """Lower + compile through Mosaic; never executes. The compiler's
    message is kept verbatim on refusal."""
    t0 = time.perf_counter()
    try:
        jax.jit(fn, donate_argnums=donate).lower(*avals).compile()
        return {"kernel": name, "status": "compiled",
                "seconds": round(time.perf_counter() - t0, 1)}
    except Exception as exc:   # report every kernel, then fail the stage
        return {"kernel": name, "status": "refused",
                "seconds": round(time.perf_counter() - t0, 1),
                "error": f"{type(exc).__name__}: {exc}"}


def off_main_path_kernels():
    """[(name, fn, avals, donate_argnums)] at the wide-sparse (Allstate) geometry — 581
    bundle columns x 4-bit codes, 13.2M rows — plus the 63-bin HIGGS
    geometry and the row-major kernel of the host-loop learners."""
    aval = jax.ShapeDtypeStruct
    i32 = aval((), jnp.int32)
    rscal = aval((plane.ROUTE_SCALARS,), jnp.int32)
    rows = 13_184_290
    out = []

    # wide planar state: 581 x 4-bit = 73 code planes -> P = 80. v2's
    # staging exceeds the VMEM budget at this plane count, so the
    # dispatcher (fused.py) selects the single-scratch v1 kernel
    wide = plane.make_layout(581, 4, rows, with_label=True, with_score=True)
    assert plane.partition_vmem_bytes(wide, "pallas2") \
        > plane.PART_VMEM_BUDGET
    wdata = aval((wide.num_planes, wide.num_lanes), jnp.int32)
    out.append((
        f"partition_pallas v1 cap=None P={wide.num_planes} S={wide.tile}",
        lambda d, s, c, r: plane.partition_pallas(d, wide, s, c, r,
                                                  cap=None),
        (wdata, i32, i32, rscal), (0,)))
    out.append((
        "histogram_planar_pallas cap=None 581 cols x 4-bit (16 bins) bf16",
        lambda d, s, c: H.histogram_planar_pallas(
            d, s, c, num_bins=16, num_cols=581, code_bits=4,
            grad_plane=wide.grad, cap=None, dtype=jnp.bfloat16),
        (wdata, i32, i32)))

    # the same state with row-wise multi-value planes (K = 40 slots,
    # T = 4809 flat cells: 4228 one-hot columns in 581 bundles)
    mv_k, mv_t = 40, 4809
    mvl = plane.make_layout(581, 4, rows, with_label=True, with_score=True,
                            mv_planes=mv_k)
    while (mvl.tile > 512 and plane.partition_vmem_bytes(mvl, "pallas")
           > plane.PART_VMEM_BUDGET):       # fused.py's tile shrink
        mvl = plane.make_layout(581, 4, rows, with_label=True,
                                with_score=True, tile=mvl.tile // 2,
                                mv_planes=mv_k)
    mdata = aval((mvl.num_planes, mvl.num_lanes), jnp.int32)
    out.append((
        f"partition_pallas v1 cap=None P={mvl.num_planes} S={mvl.tile} "
        "(multival planes)",
        lambda d, s, c, r: plane.partition_pallas(d, mvl, s, c, r,
                                                  cap=None),
        (mdata, i32, i32, rscal), (0,)))
    out.append((
        f"histogram_multival_planar K={mv_k} T={mv_t} bf16",
        lambda d, s, c: MV.histogram_multival_planar(
            d, s, c, mv_start=mvl.mv_start, mv_planes=mvl.mv_planes,
            total_bins=mv_t, grad_plane=mvl.grad, dtype=jnp.bfloat16),
        (mdata, i32, i32)))
    cap = 1 << 20
    out.append((
        f"histogram_multival_pallas K={mv_k} T={mv_t} cap={cap} bf16",
        lambda c, gh: MV.histogram_multival_pallas(
            c, gh, total_bins=mv_t, dtype=jnp.bfloat16),
        (aval((mv_k, cap), jnp.int32), aval((8, cap), jnp.int32))))

    # HIGGS columns at the reference GPU learner's 63 bins
    h63 = plane.make_layout(28, 8, 10_485_760, with_label=True,
                            with_score=True)
    out.append((
        "histogram_planar_pallas cap=None 28 cols x 8-bit (63 bins) bf16",
        lambda d, s, c: H.histogram_planar_pallas(
            d, s, c, num_bins=63, num_cols=28, code_bits=8,
            grad_plane=h63.grad, cap=None, dtype=jnp.bfloat16),
        (aval((h63.num_planes, h63.num_lanes), jnp.int32), i32, i32)))

    # row-major kernel of the host-loop learners (serial.py, parallel.py)
    for dt in (jnp.bfloat16, jnp.float32):
        out.append((
            f"histogram_radix_pallas [{cap}, 28] 255 bins "
            f"{jnp.dtype(dt).name}",
            lambda b, g, h, dt=dt: H.histogram_radix_pallas(
                b, g, h, 255, dtype=dt),
            (aval((cap, 28), jnp.uint8), aval((cap,), jnp.float32),
             aval((cap,), jnp.float32))))
    return out


def main() -> int:
    require_tpu()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
          f"jax {jax.__version__}", flush=True)
    results = []
    for (n, start, count, seed) in [
        (100_000, 0, 100_000, 0),
        (100_000, 12345, 54321, 1),
        (100_000, 99_000, 1000, 2),
        (100_000, 7, 3, 3),
        (1_000_000, 333_333, 444_444, 5),
    ]:
        data, layout = random_state(n, 28, seed)
        routes = [plane.route_scalars(layout, seed % 28, 120, 1, 249)]
        if seed == 1:
            # categorical bitset route: left = bins whose bit is set
            bits = np.random.RandomState(seed).randint(
                0, 2 ** 31 - 1, plane.CAT_WORDS).astype(np.int32)
            routes.append(plane.route_scalars(
                layout, 3, 0, 0, -1, is_cat=1, cat_bitset=bits))
        for rscal in routes:
            for kernel in PARTITION_KERNELS:
                for dynamic in (False, True):
                    results.append(check_partition(
                        data, layout, start, count, rscal, kernel=kernel,
                        dynamic=dynamic))
        results.append(check_histogram(data, layout, start, count, 255))
        results.append(check_histogram_quant(data, layout, start, count,
                                             255, seed=seed))
    data4, layout4 = random_state(100_000, 28, 7, bits=4)
    for kernel in PARTITION_KERNELS:
        results.append(check_partition(
            data4, layout4, 1000, 90_000,
            plane.route_scalars(layout4, 5, 7, 1, 15), kernel=kernel,
            dynamic=True))
    results.append(check_histogram(data4, layout4, 1000, 90_000, 16))
    results.append(traverse_at_cell_geometry())
    for r in results:
        print(r, flush=True)
    compiled = [compile_only(*k) for k in off_main_path_kernels()]
    for r in compiled:
        print(r, flush=True)
    ok = all(r["ok"] for r in results) \
        and all(r["status"] == "compiled" for r in compiled)
    print("ALL OK" if ok else "MISMATCH OR REFUSAL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
