"""The ranking gradient program's parts on the chip, at a cell's size:
the whole of `LambdarankNDCG.get_gradients`, the window gathers in, the
gather back, each bucket's walk (sort, pair blocks, sort back, chunk by
chunk), the scatter the unsampled grow program made a tree
before PR 34,
and the way in by one index a slot instead of one a query (PERF.md
section 6, PR 34).

Run: python scripts/rank_micro.py [k]     (k x 2,270,296 rows, default 3)
"""
import os
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def timed(name, fn, *args, reps=5):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / reps * 1e3
    print(f"{name}: {ms:.2f} ms", flush=True)
    return ms


def main():
    import jax
    import jax.numpy as jnp
    from benchmarks.generators import ltr_like
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective.rank import LambdarankNDCG

    k = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    rows, queries = k * 2_270_296, k * 18_919
    rng = np.random.default_rng(1)
    group = ltr_like.query_sizes(rng, queries, rows)
    label = np.searchsorted(np.cumsum(ltr_like.SHARES)[:-1],
                            rng.random(rows)).astype(np.float32)
    print(f"device {jax.devices()[0].device_kind}; {rows} rows, {queries} "
          f"queries, largest {group.max()}", flush=True)
    obj = LambdarankNDCG(Config.from_params({"objective": "lambdarank"}))
    t0 = time.perf_counter()
    obj.init(SimpleNamespace(
        label=label, weights=None,
        query_boundaries=np.concatenate([[0], np.cumsum(group)])), rows)
    print(f"init {time.perf_counter() - t0:.2f} s; plan {obj.rank_plan()}; "
          f"{obj._layout['slots']} slots", flush=True)
    score = jnp.asarray(rng.normal(size=rows).astype(np.float32))

    t0 = time.perf_counter()
    jax.block_until_ready(obj.get_gradients(score))
    print(f"first call {time.perf_counter() - t0:.2f} s", flush=True)
    timed("get_gradients", obj.get_gradients, score)

    lay, inv = obj._layout_dev, obj._inv_dev
    buckets = list(obj._slabs(obj._label_slots))
    padded = obj._end_padded(score)

    def windows(padded):
        return [obj._windows(padded, obj._per_query(b, lay["start"]),
                             obj._per_query(b, lay["count"]), b["m"])[0]
                for b, _ in buckets]
    slabs = jax.jit(windows)(padded)
    timed("windows in, a bucket at a time (one index a query)",
          jax.jit(windows), padded)
    timed("way back: one gather of [2, slots] by row_slot",
          jax.jit(lambda a: obj._to_rows(lay, a, a)), slabs)
    for (b, (lab,)), s in zip(buckets, slabs):
        chunks = b["padded"] // b["chunk"]
        valid = jnp.arange(b["m"])[None, :] \
            < obj._per_query(b, lay["count"])[:, None]
        xs = tuple(x.reshape((chunks, b["chunk"]) + x.shape[1:])
                   for x in (s, valid, lab, obj._per_query(b, inv)))
        timed(f"  bucket M={b['m']}: {len(b['queries'])} queries, {chunks} "
              f"chunks of {b['chunk']}: sort, pairs, sort back",
              jax.jit(lambda xs: jax.lax.map(
                  lambda x: obj._chunk_lambdas(*x), xs)), xs, reps=3)

    # the unsampled grow program's way from lanes back to rows (ISSUE 34,
    # item 3): the scatter it made a tree, at this many rows
    perm = jnp.asarray(rng.permutation(rows).astype(np.int32))
    leaf = jnp.asarray(rng.integers(0, 255, rows).astype(np.int32))
    timed("leaf_of_row by zeros(n).at[rowids].set(pos_leaf, unique)",
          jax.jit(lambda r, v: jnp.zeros(rows, jnp.int32).at[r].set(
              v, unique_indices=True)), perm, leaf, reps=3)

    # the way in by one index a SLOT instead, for the record
    slot_src = np.zeros(obj._layout["slots"], np.int32)
    slot_src[obj._layout["row_slot"]] = np.arange(rows, dtype=np.int32)
    timed("way in by score[slot_src], one index a slot",
          jax.jit(lambda s, i: s[i]), score, jnp.asarray(slot_src))


if __name__ == "__main__":
    main()
