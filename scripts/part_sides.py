"""Production-v2 partition ablation IN CONTEXT: times the real kernel
against itself with individual stages stubbed out, at a real window on
the chip (the component sums of part_micro.py add up to less).

Stages stubbed, cumulatively:
  full      — production _partition_kernel2
  noalign   — side 1 (realign/writeback) body skipped
  nonet     — + the compaction (`plane._compact_streams`) replaced by
              pass-through: what is left is routing, the carry rolls,
              the staging copies and the DMA chains

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
    from lightgbm_tpu.ops import plane

    if jax.default_backend() != "tpu":
        raise SystemExit("part_sides times kernels on the chip; JAX "
                         f"initialised the {jax.default_backend()} backend")
    rows = part_micro.ROWS
    layout, data, rscal = part_micro.random_state(P, S, rows)
    print(f"window {rows} lanes, P={P}, tile {S}")
    whole = plane._partition_kernel2

    def stream_side_only(*refs, **kw):
        @pl.when(pl.program_id(0) == 0)
        def _():
            whole(*refs, **kw)

    for label, kernel, plan in (
            ("full", whole, None),
            ("noalign", stream_side_only, None),
            ("nonet", stream_side_only, part_micro._plan_none)):
        plane._partition_kernel2 = kernel
        try:
            ms = part_micro.production_ms("partition_pallas2", data, layout,
                                          rows, rscal, S, plan)
        finally:
            plane._partition_kernel2 = whole
        print(f"  {label:8s}: {ms:8.3f} ms = {ms * 1e6 / rows:.4f} ns/lane",
              flush=True)


if __name__ == "__main__":
    main()
