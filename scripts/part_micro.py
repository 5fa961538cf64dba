"""Partition-kernel cost decomposition on the real chip.

Host wall timings of single dispatches are not device time, so every
number here comes from the device-side profiler trace. Measures, at a
HIGGS-scale window:

1. the production v1/v2 partition kernels (ns/lane),
2. ablated kernel variants that isolate the cost components:
   - copy-only (DMA floor: stream the window through VMEM untouched)
   - +routing (the split-column decode + go_left compute)
   - +compaction network (the log2(S) roll+select rounds)
   - +carry rolls (the three full-width dynamic rolls per step)

Run:  python scripts/part_micro.py
"""
import functools
import glob
import gzip
import json
import os
import sys
from collections import defaultdict

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("PART_ROWS", 4 << 20))
P = 16
S = int(os.environ.get("PART_TILE", 4096))


def device_ms(fn, x):
    """Total device-lane ms for one call of fn, from the profiler.
    The traced call uses a different argument value than the warm-up."""
    import jax
    jax.block_until_ready(fn(x))  # warm/compile + drain before tracing
    tdir = "/tmp/part_micro_trace"
    os.system(f"rm -rf {tdir}")
    with jax.profiler.trace(tdir):
        out = fn(x + 1)
        jax.block_until_ready(out)
    files = glob.glob(f"{tdir}/**/*.trace.json.gz", recursive=True)
    with gzip.open(files[0], "rt") as fh:
        trace = json.load(fh)
    events = trace.get("traceEvents", [])
    pid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
    device_pids = {p for p, n in pid_names.items()
                   if "TPU" in n or "/device" in n.lower()}
    agg = defaultdict(float)
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in device_pids:
            agg[e.get("name", "?")] += e.get("dur", 0) / 1e3
    return agg


def kernel_variant(mode: str):
    """A stripped partition-like kernel: reads [P, S] blocks, applies
    the chosen cost component, writes back. Grid = one pass over the
    window."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nt = ROWS // S

    def body(x_ref, o_ref):
        x = x_ref[...]
        if mode == "copy":
            o_ref[...] = x
            return
        # routing: split-column decode + threshold compare
        col = jnp.sum(jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (P, S), 0) == 3, x, 0),
            axis=0, keepdims=True)
        keep = ((col >> 8) & 0xFF) <= 120
        if mode == "routing":
            o_ref[...] = jnp.where(keep, x, x + 1)
            return
        # compaction network: log2(S) roll+select rounds (the v1/v2
        # inner loop shape, static shifts, data-dependent selects)
        ranks = keep.astype(jnp.int32)
        b = 1
        while b < S:
            ranks = ranks + jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (1, S), 1) >= b,
                pltpu.roll(ranks, b, 1), 0)
            b *= 2
        sh = jnp.where(keep, jax.lax.broadcasted_iota(
            jnp.int32, (1, S), 1) - (ranks - 1), 0)
        comp = x
        shv = sh
        b = 1
        while b < S:
            moved = pltpu.roll(shv, S - b, 1)
            m1 = (moved & b) != 0
            comp = jnp.where(m1, pltpu.roll(comp, S - b, 1), comp)
            shv = jnp.where(m1, moved - b, shv)
            b *= 2
        if mode == "network":
            o_ref[...] = comp
            return
        # + the three full-width dynamic rolls of the carry machinery
        c = jnp.sum(keep.astype(jnp.int32)) % 128
        comp = pltpu.roll(comp, jax.lax.rem(128 - c, 128), 1)
        comp = pltpu.roll(comp, c, 1)
        comp = pltpu.roll(comp, jax.lax.rem(S - c, S), 1)
        o_ref[...] = comp

    f = pl.pallas_call(
        body,
        grid=(nt,),
        in_specs=[pl.BlockSpec((P, S), lambda i: (0, i))],
        out_specs=pl.BlockSpec((P, S), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((P, ROWS), jnp.int32),
    )
    return jax.jit(f)


def kernel_structural(mode: str):
    """Variants that mimic the PRODUCTION kernel's structure one
    element at a time: dynamic (scalar-prefetched) input index maps,
    manual-DMA output with double buffering, and the 2-stream v2 shape.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nt = ROWS // S

    def body(scal, x_ref, o_ref, stg0, stg1, sems):
        t = pl.program_id(0)
        x = x_ref[...]
        col = jnp.sum(jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (P, S), 0) == 3, x, 0),
            axis=0, keepdims=True)
        keep = ((col >> 8) & 0xFF) <= 120
        ranks = keep.astype(jnp.int32)
        b = 1
        while b < S:
            ranks = ranks + jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (1, S), 1) >= b,
                pltpu.roll(ranks, b, 1), 0)
            b *= 2
        sh = jnp.where(keep, jax.lax.broadcasted_iota(
            jnp.int32, (1, S), 1) - (ranks - 1), 0)
        comp = x
        shv = sh
        b = 1
        while b < S:
            moved = pltpu.roll(shv, S - b, 1)
            m1 = (moved & b) != 0
            comp = jnp.where(m1, pltpu.roll(comp, S - b, 1), comp)
            shv = jnp.where(m1, moved - b, shv)
            b *= 2
        if mode == "dynidx":
            o_ref[...] = comp
            return
        # manual-DMA double-buffered output, production-style
        slot = jax.lax.rem(t, 2)

        @pl.when(slot == 0)
        def _():
            stg0[...] = comp
            @pl.when(t > 0)
            def _():
                pltpu.make_async_copy(
                    stg1, o_ref.at[:, pl.ds((t - 1) * S, S)],
                    sems.at[1]).wait()
            pltpu.make_async_copy(
                stg0, o_ref.at[:, pl.ds(t * S, S)], sems.at[0]).start()

        @pl.when(slot == 1)
        def _():
            stg1[...] = comp
            pltpu.make_async_copy(
                stg0, o_ref.at[:, pl.ds((t - 1) * S, S)], sems.at[0]).wait()
            pltpu.make_async_copy(
                stg1, o_ref.at[:, pl.ds(t * S, S)], sems.at[1]).start()

        @pl.when((t == nt - 1) & (slot == 0))
        def _():
            pltpu.make_async_copy(
                stg0, o_ref.at[:, pl.ds(t * S, S)], sems.at[0]).wait()

        @pl.when((t == nt - 1) & (slot == 1))
        def _():
            pltpu.make_async_copy(
                stg1, o_ref.at[:, pl.ds(t * S, S)], sems.at[1]).wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt,),
        in_specs=[pl.BlockSpec(
            (P, S), lambda t, scal: (0, scal[0] + jnp.minimum(t, scal[1])))],
        out_specs=(pl.BlockSpec(memory_space=pltpu.ANY)
                   if mode == "dma" else
                   pl.BlockSpec((P, S), lambda t, scal: (0, t))),
        scratch_shapes=[
            pltpu.VMEM((P, S), jnp.int32),
            pltpu.VMEM((P, S), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    f = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, ROWS), jnp.int32),
    )
    scal = jnp.asarray([0, nt - 1], jnp.int32)
    return jax.jit(lambda x: f(scal, x))


def main():
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import plane

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, 1 << 30, size=(P, ROWS)), jnp.int32)

    print(f"window: {ROWS} lanes x {P} planes, tile {S}")
    for mode in ("copy", "routing", "network", "carry"):
        fn = kernel_variant(mode)
        agg = device_ms(fn, x)
        total = sum(v for k, v in agg.items() if "pallas" in k.lower()
                    or "custom" in k.lower() or "fusion" in k.lower())
        # fall back to the total if names don't match
        total = total or sum(agg.values())
        print(f"  {mode:8s}: {total:8.2f} ms = "
              f"{total * 1e6 / ROWS:.3f} ns/lane")
    for mode in ("dynidx", "dma"):
        fn = kernel_structural(mode)
        agg = device_ms(fn, x)
        total = sum(v for k, v in agg.items() if "pallas" in k.lower()
                    or "custom" in k.lower() or "fusion" in k.lower())
        total = total or sum(agg.values())
        print(f"  {mode:8s}: {total:8.2f} ms = "
              f"{total * 1e6 / ROWS:.3f} ns/lane")

    # the production kernels at the same shape
    codes = rng.randint(0, 250, size=(ROWS, 8)).astype(np.uint8)
    layout = plane.make_layout(8, 8, ROWS, with_label=True, with_score=True,
                               tile=S)
    cp = plane.build_codes_planes(jnp.asarray(codes), layout)
    grad = jnp.asarray(rng.randn(ROWS), jnp.float32)
    data = plane.build_data(layout, cp, grad, grad, label=grad, score=grad)
    rscal = plane.route_scalars(layout, 3, 120, 1, 249)
    cap = (ROWS // S - 1) * S
    for name, meth in (("v1", "pallas"), ("v2", "pallas2")):
        fn = functools.partial(plane.partition_window, layout=layout,
                               start=0, count=cap, rscal=rscal, cap=cap,
                               method=meth)
        agg = device_ms(lambda d: fn(d)[0], data)
        total = sum(v for k, v in agg.items()
                    if "partition" in k.lower() or "custom" in k.lower())
        total = total or sum(agg.values())
        print(f"  prod {name}: {total:8.2f} ms = "
              f"{total * 1e6 / cap:.3f} ns/lane "
              f"(P={layout.num_planes})")


if __name__ == "__main__":
    main()
