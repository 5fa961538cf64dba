"""Times the replay kernels on whatever device it finds: the one-tree
leaf-id kernel (`plane.traverse_planes_pallas`, what every tree of the
per-tree tier runs) at the code-plane counts of the cells that run it,
and, where the package has it, the forest replay
(`plane.replay_forest_pallas`, DART's dropped trees) at HIGGS's 7 code
planes for several tree counts. Random 255-leaf trees over random codes;
device time by the host clock over blocked calls, after one that
compiled. Prints one JSON line a measurement.

    python3 scripts/dart_micro.py [reps] [planes,...]
    PYTHONPATH=<other checkout> python3 scripts/dart_micro.py   # its kernels
"""
import json
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import plane

LEAVES = 255
# (code planes, lanes): expo700goss's 3 planes at 22M rows, 8, msltr137's
# 35 at 6.8M, 40; HIGGS's 7 at 21M
LEAF_ID_SHAPES = ((3, 22_020_096), (8, 22_020_096), (35, 6_815_744),
                  (40, 6_815_744), (7, 20_971_520))
FOREST_TREES = (1, 10, 20, 50)


def random_tree(rng, cols: int):
    """A full 255-leaf tree of numerical splits in Tree::Split numbering
    (node k splits a random slot; the left child keeps it)."""
    L = LEAVES
    ta = {"n_leaves": np.int32(L),
          "split_feature": rng.integers(0, cols, L - 1).astype(np.int32),
          "threshold_bin": rng.integers(0, 255, L - 1).astype(np.int32),
          "default_left": rng.random(L - 1) < 0.5,
          "split_cat": np.zeros(L - 1, bool),
          "split_bits": np.zeros((L - 1, plane.CAT_WORDS), np.int32),
          "left_child": np.zeros(L - 1, np.int32),
          "right_child": np.zeros(L - 1, np.int32),
          "leaf_value": rng.normal(size=L).astype(np.float32),
          "internal_value": rng.normal(size=L - 1).astype(np.float32)}
    held = {0: None}
    for k in range(L - 1):
        s = int(rng.integers(0, k + 1))
        if held[s] is not None:
            node, side = held[s]
            ta[side][node] = k
        ta["left_child"][k], ta["right_child"][k] = ~s, ~(k + 1)
        held[s], held[k + 1] = (k, "left_child"), (k, "right_child")
    return {k: jnp.asarray(v) for k, v in ta.items()}


def timed(fn, *args, reps: int):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps


def main(reps: int = 10, planes=None) -> None:
    rng = np.random.default_rng(7)
    for C, R in LEAF_ID_SHAPES:
        if planes is not None and C not in planes:
            continue
        layout = plane.make_layout(4 * C, 8, R)
        codes = jax.random.randint(jax.random.PRNGKey(C), (C, R), -2 ** 31,
                                   2 ** 31 - 1, jnp.int32)
        miss = jnp.full(4 * C, -1, jnp.int32)
        table = plane.traverse_table(layout, random_tree(rng, 4 * C), miss)
        s = timed(plane.traverse_planes_pallas, codes, table, reps=reps)
        print(json.dumps({"kernel": "traverse_planes_pallas", "planes": C,
                          "lanes": R, "ms": s * 1e3,
                          "ns_per_lane_split": s * 1e9 / (R * (LEAVES - 1))}),
              flush=True)
        del codes
    if not hasattr(plane, "replay_forest_pallas") or planes is not None:
        return
    C, R = 7, 20_971_520
    layout = plane.make_layout(4 * C, 8, R)
    codes = jax.random.randint(jax.random.PRNGKey(0), (C, R), -2 ** 31,
                               2 ** 31 - 1, jnp.int32)
    miss = jnp.full(4 * C, -1, jnp.int32)
    W, Wv = plane.replay_widths(LEAVES)
    trees = [random_tree(rng, 4 * C) for _ in range(max(FOREST_TREES))]
    routes = jnp.stack([jnp.pad(plane.traverse_table(layout, t, miss),
                                (0, W - 1 - (LEAVES - 1) * plane.TRAVERSE_REC))
                        for t in trees])
    values = jnp.stack([jnp.pad(plane.replay_values(t),
                                (0, Wv - 1 - 2 * (LEAVES - 1)))
                        for t in trees])
    kmax = 50
    for k in FOREST_TREES:
        sel = jnp.asarray([k] + list(range(k)) + [k - 1] * (kmax - k),
                          jnp.int32)
        vals = jnp.concatenate([values[:k],
                                jnp.zeros((kmax - k, Wv), jnp.float32)])
        s = timed(plane.replay_forest_pallas, codes, routes, vals, sel,
                  reps=reps)
        print(json.dumps({"kernel": "replay_forest_pallas", "planes": C,
                          "lanes": R, "trees": k, "kmax": kmax,
                          "ms": s * 1e3, "ns_per_lane_split":
                          s * 1e9 / (R * (LEAVES - 1) * k)}), flush=True)
    # the forest against the one-tree kernel on tree 0 and its values
    leaf = plane.traverse_planes_pallas(codes, routes[0])
    want = trees[0]["leaf_value"][leaf]
    sel = jnp.asarray([1] + [0] * kmax, jnp.int32)
    got = plane.replay_forest_pallas(
        codes, routes, jnp.concatenate([values[:1], jnp.zeros(
            (kmax - 1, Wv), jnp.float32)]), sel)
    print(json.dumps({"check": "forest k=1 against the leaf ids' values",
                      "equal": bool(jnp.all(got == want))}), flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 10,
         [int(c) for c in sys.argv[2].split(",")] if len(sys.argv) > 2
         else None)
