"""Per-split ICI collective payload accounting from compiled HLO.

The reference documents its dominant communication volumes in code
(data_parallel_tree_learner.cpp:169 ReduceScatter+Allgather of the full
histogram; voting_parallel_tree_learner.cpp:320,343 reduce only the
top-2k selected features' buffers). This script makes the TPU build's
equivalents QUANTITATIVE: it lowers the actual sharded histogram
programs of the data-parallel and voting-parallel learners (and the
fused data-parallel while-program) on an 8-device mesh at a Criteo-like
width, parses every `all-reduce` op out of the lowered HLO, and prints
bytes-per-split next to the histogram-size lower bound.

Run:  python scripts/ici_traffic.py        (re-execs itself on a forced
                                            8-device CPU mesh)
Writes the table into docs/PERF_NOTES.md by hand — the output is the
evidence, the doc records it.
"""
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
N_DEV = int(os.environ.get("ICI_DEVICES", 8))
COLS = int(os.environ.get("ICI_COLS", 1000))     # Criteo-like width
ROWS = int(os.environ.get("ICI_ROWS", 16384))
BINS = 255


def _reexec():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={N_DEV}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["ICI_BODY"] = "1"
    res = subprocess.run([sys.executable, os.path.abspath(__file__)],
                         env=env)
    sys.exit(res.returncode)


_DTYPE_BYTES = {"f32": 4, "i32": 4, "ui32": 4, "f16": 2, "bf16": 2,
                "i1": 1, "ui8": 1, "i8": 1, "f64": 8, "i64": 8}


def allreduce_bytes(mlir_text: str):
    """[(shape_str, bytes)] for every stablehlo.all_reduce result type
    in the lowered MLIR (one entry per op; each while-body op runs once
    per split)."""
    out = []
    wpos = mlir_text.find("stablehlo.while")
    for m in re.finditer(
            r'"?stablehlo\.all_reduce"?.*?\}\)\s*:\s*\(([^)]*)\)',
            mlir_text, re.DOTALL):
        shapes = re.findall(
            r"tensor<(?:([0-9]+(?:x[0-9]+)*)x)?([a-z]+[0-9]+)>",
            m.group(1))
        total = 0
        desc = []
        for dims, dt in shapes:
            n = 1
            for d in dims.split("x"):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES.get(dt, 4)
            desc.append(f"{dims or 'scalar'}x{dt}")
        where = ("prologue" if 0 <= wpos and m.start() < wpos
                 else "loop body")
        out.append((", ".join(desc) + f"  [{where}]", total))
    return out


def main_body():
    import numpy as np
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, REPO)
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective.functions import create_objective
    from lightgbm_tpu.treelearner.parallel import (
        DataParallelTreeGrower, VotingParallelTreeGrower,
        FusedDataParallelGrower)

    rng = np.random.RandomState(0)
    X = rng.rand(ROWS, COLS)
    y = (X[:, 0] > 0.5).astype(np.float64)
    base = {"objective": "binary", "num_machines": N_DEV, "verbose": -1,
            "max_bin": BINS, "num_leaves": 31, "min_data_in_leaf": 20}

    def lower_hist(learner_cls, params):
        cfg = Config.from_params(params)
        ds = BinnedDataset.from_matrix(X, cfg, label=y)
        lrn = learner_cls(ds, cfg)
        cap = 4096
        fn = lrn._hist_fn_sharded(cap)
        d = lrn.num_shards
        rps = lrn.rows_per_shard
        sds = jax.ShapeDtypeStruct
        args = (sds((d, rps, ds.bins.shape[1]), ds.bins.dtype),
                sds((d, rps), jnp.int32),
                sds((d,), jnp.int32), sds((d,), jnp.int32),
                sds((d, rps), jnp.float32), sds((d, rps), jnp.float32))
        txt = fn.lower(*args).as_text()
        return allreduce_bytes(txt), ds, cfg

    print(f"shape: {ROWS} rows x {COLS} cols, {BINS} bins, "
          f"{N_DEV} shards")
    lower = BINS * COLS * 2 * 4
    print(f"histogram-size lower bound (one [F,B,2] f32 reduction): "
          f"{lower:,} bytes/split")

    rows = []
    ar, ds, cfg = lower_hist(DataParallelTreeGrower,
                             dict(base, tree_learner="data"))
    total = sum(b for _, b in ar)
    rows.append(("data_parallel (host-loop)", ar, total))

    ar, _, _ = lower_hist(VotingParallelTreeGrower,
                          dict(base, tree_learner="voting", top_k=20))
    total = sum(b for _, b in ar)
    rows.append(("voting_parallel (top_k=20)", ar, total))

    # fused data-parallel: collectives of ONE while-iteration (= one
    # split) inside the persistent whole-iteration program
    cfg = Config.from_params(dict(base, tree_learner="data"))
    obj = create_objective(cfg)
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    obj.init(ds.metadata, ds.num_data)
    gr = FusedDataParallelGrower(ds, cfg, obj)
    # lower the sharded whole-iteration program on abstract shapes
    # (mirrors FusedDataParallelGrower.train_iter_persistent's jit)
    import functools
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(data_l, nvalid_l, mask_, shr, b):
        return gr._train_iter(data_l, mask_, shr, b,
                              n_valid=nvalid_l[0])

    f = functools.partial(
        shard_map, mesh=gr.mesh, check_vma=False,
        in_specs=(P(None, "data"), P("data"), P(), P(), P()),
        out_specs=(P(None, "data"), P()))(body)
    sds = jax.ShapeDtypeStruct
    Ly = gr.layout
    mask = gr.feature_masks_for_tree()
    lowered = jax.jit(f).lower(
        sds((Ly.num_planes, gr.num_shards * Ly.num_lanes), jnp.int32),
        sds((gr.num_shards,), jnp.int32),
        sds(mask.shape, mask.dtype),
        sds((), jnp.float32), sds((), jnp.float32))
    ar = allreduce_bytes(lowered.as_text())
    # ops inside the while body run once per split; the lowered text
    # contains each op once
    total = sum(b for _, b in ar)
    rows.append(("fused data_parallel (per while step)", ar, total))

    print()
    for name, ar, total in rows:
        print(f"{name}: {total:,} bytes/split "
              f"({total / lower:.2f}x lower bound)")
        for shape, b in ar:
            print(f"    {b:>12,}  {shape}")


if __name__ == "__main__":
    if os.environ.get("ICI_BODY"):
        main_body()
    else:
        _reexec()
