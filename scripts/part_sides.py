"""Production-v2 partition ablation IN CONTEXT: times the real kernel
against itself with individual stages stubbed out, at a real window on
the chip (the component sums of part_micro.py add up to less).

Stages stubbed, cumulatively:
  full      — production _partition_kernel2
  noalign   — side 1 (realign/writeback) body skipped
  nocarry   — + the carry write (`plane._emit_stream`) stubbed to a
              plain staged copy: no select of the old carry into the
              first column, no read of the next one
  nonet     — + the compaction (`plane._compact_streams`) replaced by
              pass-through: what is left is routing, the staging copies
              and the DMA chains

Run: python scripts/part_sides.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import part_micro

P = int(os.environ.get("PART_PLANES", 16))
S = int(os.environ.get("PART_TILE", 8192))


def main():
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops import plane

    if jax.default_backend() != "tpu":
        raise SystemExit("part_sides times kernels on the chip; JAX "
                         f"initialised the {jax.default_backend()} backend")
    rows = part_micro.rows_for(P)
    layout, data, rscal = part_micro.random_state(P, S, rows)
    print(f"window {rows} lanes, P={P}, tile {S}")
    whole = plane._partition_kernel2
    emit = plane._emit_stream

    def stream_side_only(*refs, **kw):
        @pl.when(pl.program_id(0) == 0)
        def _():
            whole(*refs, **kw)

    def staged_copy_only(comp, k, smem, cursor, carry, slot, asteps, stgs,
                         cbuf, sems, win_ref):
        """`_emit_stream` less the carry: the chunk staged as it comes
        and DMA'd; the cursor and carry length advance as they do."""
        written = pl.multiple_of(smem[cursor], plane.LANE)
        total = smem[carry] + k
        adv = (total // plane.LANE) * plane.LANE
        for s in (0, 1):
            @pl.when(slot == s)
            def _(s=s):
                stgs[s][...] = comp
                @pl.when(asteps > 0)
                def _():
                    pltpu.make_async_copy(
                        stgs[1 - s], win_ref.at[:, pl.ds(0, comp.shape[1])],
                        sems.at[1 - s]).wait()
                pltpu.make_async_copy(
                    stgs[s], win_ref.at[:, pl.ds(written, comp.shape[1])],
                    sems.at[s]).start()
        smem[cursor] = written + adv
        smem[carry] = total - adv

    for label, kernel, carry_write, plan in (
            ("full", whole, emit, None),
            ("noalign", stream_side_only, emit, None),
            ("nocarry", stream_side_only, staged_copy_only, None),
            ("nonet", stream_side_only, staged_copy_only,
             part_micro._plan_none)):
        plane._partition_kernel2 = kernel
        plane._emit_stream = carry_write
        try:
            ms = part_micro.production_ms("partition_pallas2", data, layout,
                                          rows, rscal, S, plan)
        finally:
            plane._partition_kernel2 = whole
            plane._emit_stream = emit
        print(f"  {label:8s}: {ms:8.3f} ms = {ms * 1e6 / rows:.4f} ns/lane",
              flush=True)


if __name__ == "__main__":
    main()
