"""Partition-kernel cost decomposition on the real chip.

Host wall timings of single dispatches are not device time, so every
number here comes from the device-side profiler trace (the kernel's own
events on the chip's op line). Two tables per geometry (plane count P x
processing tile S, `PART_GEOM`, default the cells'
`16x8192,8x16384,512x512`; the window is `PART_ROWS` lanes, fewer where
P x lanes would pass 1 GiB):

1. the PRODUCTION kernels with `plane._compact_streams`, the one
   compaction primitive, swapped for a variant (`PLANS`). A variant
   that compacts at width S reaches the carry offset by ONE full-width
   dynamic roll (`_at_carry`), the alternative PR 32 timed and did not
   ship:
   - separate: one plan per stream — a prefix sum, a shift row with the
     `- b` update and a network each; what shipped before PR 28
   - ceiling:  `separate` without the second prefix sum and without
     both shift updates. WRONG output, timing only: what removing that
     bookkeeping outright would buy
   - rolled:   PR 28's stacked plan at width S, then the roll
   - rolledcol: the same with the roll done as one dynamic lane rotate
     and one blend per 128-lane column: the fastest PR 32 timed, found
     too late to ship (ROADMAP A3)
   - stacked:  the shipped helper — one prefix sum and one shift
     bookkeeping for all streams, stacked in sublanes, no subtract,
     the carry offset folded into the shifts behind a 128-lane lead
2. stripped kernels that add one cost component at a time around the
   SHIPPED helper (`COMPONENTS`, P <= 16): copy floor, routing, one
   network (K=1), the stacked pair (K=2), the carry (a dynamic offset
   in the network and the first column's select), and the production
   structure's scalar-prefetched index map and double-buffered manual
   DMA.

Run:  python scripts/part_micro.py   (writes chiprun_out/part_micro.json)
"""
import functools
import glob
import json
import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("PART_ROWS", 4 << 20))
GEOMETRIES = [tuple(int(v) for v in g.split("x")) for g in
              os.environ.get("PART_GEOM",
                             "16x8192,8x16384,512x512").split(",")]
REPEATS = 3


def rows_for(P):
    """Window lanes at plane count P: ROWS, or what keeps the state
    under 1 GiB."""
    return min(ROWS, (1 << 28) // P)


def device_ms(fn, x, match=""):
    """Device ms of the fastest of REPEATS traced calls of fn(x): per
    call, the summed durations of the op-line events whose instruction
    name contains `match` (all top-level ops when empty)."""
    import jax
    from jax.profiler import ProfileData
    jax.block_until_ready(fn(x))        # compile + drain before tracing
    tdir = tempfile.mkdtemp(prefix="part_micro_")
    try:
        with jax.profiler.trace(tdir):
            for _ in range(REPEATS):
                jax.block_until_ready(fn(x))
        path = sorted(glob.glob(os.path.join(
            tdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        events = []
        for pl_ in ProfileData.from_file(path).planes:
            if not pl_.name.startswith("/device:TPU:0"):
                continue
            for line in pl_.lines:
                if line.name == "XLA Ops":
                    events += [(e.name.split(" = ", 1)[0], e.duration_ns / 1e6)
                               for e in line.events]
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    durs = [d for n, d in events if match in n]
    if not durs:
        raise SystemExit(f"no device op named *{match}* among "
                         f"{sorted({n for n, _ in events})}")
    per_call, rest = divmod(len(durs), REPEATS)
    if rest:        # an event went missing: the mean over what is there
        return sum(durs) / REPEATS
    return min(sum(durs[i * per_call:(i + 1) * per_call])
               for i in range(REPEATS))


# ---------------------------------------------------------------------------
# compaction plans for the production kernels
# ---------------------------------------------------------------------------

def _at_carry(comps, carries):
    """[P, S] compacted tiles (kept lanes from lane 0) -> the shipped
    helper's [P, S + 128] with the kept lanes from each carry offset on:
    one full-width dynamic roll a stream."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.plane import LANE
    return [pltpu.roll(jnp.concatenate([comp, comp[:, :LANE]], axis=1), c, 1)
            for comp, c in zip(comps, carries)]


def _at_carry_cols(comps, carries):
    """`_at_carry` as one dynamic lane rotate and one blend per 128-lane
    column, without the full-width roll's log-stages."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.plane import LANE, _lane_iota
    out = []
    for comp, c in zip(comps, carries):
        rot = [pltpu.roll(comp[:, j:j + LANE], c, 1)
               for j in range(0, comp.shape[1], LANE)]
        cols = [rot[0]] + [jnp.where(_lane_iota(LANE) < c, lo, hi)
                           for lo, hi in zip(rot, rot[1:])] + [rot[-1]]
        out.append(jnp.concatenate(cols, axis=1))
    return out


def _plan_separate(x, keeps, carries, roll=None):
    """One plan per stream, as shipped before PR 28."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops import plane
    S = x.shape[1]
    comps = []
    for keep in keeps:
        ranks = plane._lane_prefix(keep, pltpu.roll)
        sh = jnp.where(keep == 1, plane._lane_iota(S) - (ranks - 1), 0)
        comp = x
        b = 1
        while b < S:
            moved = pltpu.roll(sh, S - b, 1)
            m1 = (moved & b) != 0
            comp = jnp.where(m1, pltpu.roll(comp, S - b, 1), comp)
            sh = jnp.where(m1, moved - b, sh)
            b *= 2
        comps.append(comp)
    return _at_carry(comps, carries), [jnp.sum(k) for k in keeps]


def _plan_ceiling(x, keeps, carries, roll=None):
    """`separate` less the second prefix sum and both shift updates:
    every stream ranks by the first keep row and tests the bits of its
    INITIAL shifts. Wrong lanes, right amount of data movement."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops import plane
    S = x.shape[1]
    ranks = plane._lane_prefix(keeps[0], pltpu.roll)
    comps = []
    for keep in keeps:
        sh = jnp.where(keep == 1, plane._lane_iota(S) - (ranks - 1), 0)
        comp = x
        b = 1
        while b < S:
            m1 = (pltpu.roll(sh, S - b, 1) & b) != 0
            comp = jnp.where(m1, pltpu.roll(comp, S - b, 1), comp)
            b *= 2
        comps.append(comp)
    return _at_carry(comps, carries), [jnp.sum(k) for k in keeps]


def _plan_rolled(x, keeps, carries, roll=None, place=_at_carry):
    """PR 28's stacked plan at width S, then one dynamic roll a stream
    to the carry offset (`place`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops import plane
    S = x.shape[1]
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, S), 0)
    keep8 = jnp.broadcast_to(keeps[-1], (8, S))
    for j in range(len(keeps) - 2, -1, -1):
        keep8 = jnp.where(sub == j, keeps[j], keep8)
    ranks = plane._lane_prefix(keep8, pltpu.roll)
    sh = jnp.where(keep8 == 1, plane._lane_iota(S) - (ranks - 1), 0)
    comps = [x] * len(keeps)
    b = 1
    while b < S:
        moved = pltpu.roll(sh, S - b, 1)
        take = moved & b
        for j in range(len(keeps)):
            comps[j] = jnp.where(
                jnp.broadcast_to(take[j:j + 1], x.shape) != 0,
                pltpu.roll(comps[j], S - b, 1), comps[j])
        sh = jnp.where(take != 0, moved, sh)
        b *= 2
    return place(comps, carries), [jnp.sum(k) for k in keeps]


def _plan_none(x, keeps, carries, roll=None):
    """No compaction at all (part_sides.py's `nonet`)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.plane import LANE
    wide = jnp.concatenate([x[:, :LANE], x], axis=1)
    return [wide] * len(keeps), [jnp.sum(k) for k in keeps]


PLANS = {"separate": _plan_separate, "ceiling": _plan_ceiling,
         "rolled": _plan_rolled,
         "rolledcol": functools.partial(_plan_rolled, place=_at_carry_cols),
         "stacked": None}


def production_ms(kernel, data, layout, count, rscal, S, plan):
    """Device ms of one production partition of [0, count) in the grow
    loop's mode (cap=None, per-branch tile S) under compaction `plan`
    (a PLANS value; None = the shipped helper)."""
    from lightgbm_tpu.ops import plane
    shipped = plane._compact_streams
    fn = getattr(plane, kernel)
    if plan is not None:
        plane._compact_streams = plan
    fn.clear_cache()
    try:
        return device_ms(
            lambda d: fn(d, layout, 0, count, rscal, cap=None, tile=S)[0],
            data, match="partition")
    finally:
        plane._compact_streams = shipped
        fn.clear_cache()


def layout_for(P, S, rows):
    """A layout of exactly P planes of 8-bit codes at tile S."""
    from lightgbm_tpu.ops import plane
    full = P > 8                  # label and score planes, as HIGGS has
    layout = plane.make_layout(4 * (P - (5 if full else 3)), 8, rows,
                               with_label=full, with_score=full, tile=S)
    assert layout.num_planes == P and layout.max_tile >= S, layout
    return layout


def random_state(P, S, rows, seed=0):
    """(layout, data, rscal): a [P, R] planar state of random words, a
    split of byte 1 of code plane 0 at 120 of 256 (~47 % left)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops import plane
    layout = layout_for(P, S, rows)
    rng = np.random.RandomState(seed)
    data = jnp.asarray(rng.randint(0, 1 << 31, size=(P, layout.num_lanes),
                                   dtype=np.int64).astype(np.int32))
    return layout, data, plane.route_scalars(layout, 1, 120, 1, 255)


# ---------------------------------------------------------------------------
# stripped kernels: one cost component at a time
# ---------------------------------------------------------------------------

COMPONENTS = ("copy", "routing", "network", "stacked2", "carry",
              "dynidx", "dma")


def component_kernel(mode, P, S, rows):
    """A stripped partition-like kernel over a [P, rows] window: reads
    [P, S] blocks, applies the cost components up to `mode`, writes
    back. `dynidx` / `dma` put one network under the production
    structure: a scalar-prefetched input index map, then a manual,
    double-buffered output DMA."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.plane import LANE, _compact_streams, _lane_iota

    nt = rows // S

    def compute(x):
        if mode == "copy":
            return x
        col = jnp.sum(jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (P, S), 0) == 0, x, 0),
            axis=0, keepdims=True)
        keep = (((col >> 8) & 0xFF) <= 120).astype(jnp.int32)
        if mode == "routing":
            return jnp.where(keep == 1, x, x + 1)
        if mode == "stacked2":
            (cl, cr), _ = _compact_streams(x, [keep, 1 - keep], [0, 0])
            return (cl + cr)[:, :S]
        if mode == "carry":
            # a dynamic offset in the network, the first column's select
            c = jnp.sum(keep) % LANE
            (comp,), _ = _compact_streams(x, [keep], [c])
            head = jnp.where(_lane_iota(LANE) < c, comp[:, S:],
                             comp[:, :LANE])
            return jnp.concatenate([head, comp[:, LANE:S]], axis=1)
        (comp,), _ = _compact_streams(x, [keep], [0])
        return comp[:, :S]

    def body(scal, x_ref, o_ref, stg0, stg1, sems):
        comp = compute(x_ref[...])
        if mode != "dma":
            o_ref[...] = comp
            return
        t = pl.program_id(0)
        slot = jax.lax.rem(t, 2)

        def out(stg, s, tt):
            return pltpu.make_async_copy(
                stg, o_ref.at[:, pl.ds(tt * S, S)], sems.at[s])

        @pl.when(slot == 0)
        def _():
            stg0[...] = comp
            @pl.when(t > 0)
            def _():
                out(stg1, 1, t - 1).wait()
            out(stg0, 0, t).start()

        @pl.when(slot == 1)
        def _():
            stg1[...] = comp
            out(stg0, 0, t - 1).wait()
            out(stg1, 1, t).start()

        @pl.when((t == nt - 1) & (slot == 0))
        def _():
            out(stg0, 0, t).wait()

        @pl.when((t == nt - 1) & (slot == 1))
        def _():
            out(stg1, 1, t).wait()

    dyn = mode in ("dynidx", "dma")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt,),
        in_specs=[pl.BlockSpec(
            (P, S), (lambda t, scal: (0, scal[0] + jnp.minimum(t, scal[1])))
            if dyn else (lambda t, scal: (0, t)))],
        out_specs=(pl.BlockSpec(memory_space=pltpu.HBM) if mode == "dma"
                   else pl.BlockSpec((P, S), lambda t, scal: (0, t))),
        scratch_shapes=[
            pltpu.VMEM((P, S), jnp.int32),
            pltpu.VMEM((P, S), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    f = pl.pallas_call(
        body, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, rows), jnp.int32))
    scal = jnp.asarray([0, nt - 1], jnp.int32)
    return jax.jit(lambda x: f(scal, x))


def main():
    import jax
    from lightgbm_tpu.ops import plane
    if jax.default_backend() != "tpu":
        raise SystemExit("part_micro times kernels on the chip; JAX "
                         f"initialised the {jax.default_backend()} backend")
    out = {"device": jax.devices()[0].device_kind, "geometries": {}}
    for P, S in GEOMETRIES:
        rows = rows_for(P)
        layout, data, rscal = random_state(P, S, rows)
        res = {"rows": rows, "production": {}, "components": {}}
        print(f"window {rows} lanes x {P} planes, tile {S}", flush=True)
        for kernel, method in (("partition_pallas2", "pallas2"),
                               ("partition_pallas", "pallas")):
            if plane.partition_vmem_bytes_at(P, S, method) \
                    > plane.PART_VMEM_BUDGET:
                continue        # the grower would not pick it here either
            for name, plan in PLANS.items():
                try:
                    ms = production_ms(kernel, data, layout, rows, rscal, S,
                                       plan)
                except jax.errors.JaxRuntimeError as exc:
                    # an ablation may need more scoped VMEM than Mosaic
                    # grants where the shipped body fits: say so, go on
                    if plan is None:
                        raise
                    print(f"  {kernel:18s} {name:9s}: refused "
                          f"({str(exc).splitlines()[0][:60]} ...)", flush=True)
                    continue
                res["production"][f"{kernel}.{name}"] = ms * 1e6 / rows
                print(f"  {kernel:18s} {name:9s}: {ms:8.3f} ms = "
                      f"{ms * 1e6 / rows:.4f} ns/lane", flush=True)
        x = data[:, :rows]
        for mode in COMPONENTS if P <= 16 else ():
            ms = device_ms(component_kernel(mode, P, S, rows), x)
            res["components"][mode] = ms * 1e6 / rows
            print(f"  {mode:9s}: {ms:8.3f} ms = {ms * 1e6 / rows:.4f} ns/lane",
                  flush=True)
        out["geometries"][f"{P}x{S}"] = res
        del data, x
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/part_micro.json", "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
