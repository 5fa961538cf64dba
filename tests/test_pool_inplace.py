"""The loop-carried histogram pool is written in place on the TPU.

Each split step writes two `[F, B, 2]` rows of the `[L, F, B, 2]` pool
(`lgbm.pool` in treelearner/fused.py). If a write's fusion still reads the
pre-write pool, XLA:TPU copies the whole carry before the first write and back
after the second: two copies of L*F*B*8 bytes on each of the L-1 steps, a third
of `epsilon63.train`'s device time before PR 26. XLA:CPU keeps such copies
either way, so only the TPU's compiler can hold this: the programs are compiled
here for a described v5e (no chip, nothing runs) and their text is searched.

Every compile of this file happens inside a test, in this process: the TPU's
library is loaded by the one worker that is given the file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu.compile import reset_manager
from lightgbm_tpu.ops import histogram as H

# the reduced wide shape: the parent's body copies f32[31,256,63,2] twice here
ROWS, COLS, LEAVES, MAX_BIN = 4096, 256, 31, 63
CASES = {
    # case -> (parameters, pool dtype in the compiled text)
    "persistent_f32": ({}, "f32"),
    "persistent_quantized_i32": ({"use_quantized_grad": True}, "s32"),
    "per_tree": ({"objective": "multiclass", "num_class": 3}, "f32"),
    "data_parallel": ({"tree_learner": "data", "tpu_mesh_shape": [4]}, "f32"),
    # row sampling: the bag compacted out of the resident planes, every row's
    # leaf by replaying the splits over them (FusedSerialGrower._grow_tree)
    "per_tree_sampled": ({"bagging_fraction": 0.5, "bagging_freq": 1}, "f32"),
    "data_parallel_sampled": ({"tree_learner": "data", "tpu_mesh_shape": [4],
                               "bagging_fraction": 0.5, "bagging_freq": 1},
                              "f32"),
}


def _describe(name, **kwargs):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name=name, **kwargs)
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"no {name} topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip():
    return _describe("v5e:1x1", chip_config_name="default",
                     chips_per_host_bounds=(1, 1, 1), num_slices=1).devices


@pytest.fixture(scope="module")
def four_chips():
    return _describe("v5e:2x2").devices


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Growers dispatch as on a TPU (the Pallas histogram and partition
    kernels); what is compiled for the described chip stays out of the
    persistent cache, which could not hand it back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(H, "_use_tpu", lambda: True)
    monkeypatch.setenv("LGBM_TPU_WARMUP", "0")
    reset_manager()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    reset_manager()


def _grower(extra, shape=(ROWS, COLS, LEAVES, MAX_BIN)):
    rows, cols, leaves, max_bin = shape
    rng = np.random.RandomState(1)
    X = rng.randn(rows, cols).astype(np.float32)
    if extra.get("objective") == "multiclass":
        y = rng.randint(0, extra["num_class"], rows).astype(np.float64)
    else:
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    params = dict({"objective": "binary", "num_leaves": leaves,
                   "max_bin": max_bin, "min_data_in_leaf": 1, "verbose": -1},
                  **extra)
    g = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))._gbdt._fused
    assert g._hist_method == "radix_pallas_bf16" and g._use_hist_pool
    assert g._part_method.startswith("pallas")
    g._interpret = False    # Mosaic, not the interpreter
    return g


def _on(avals, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        avals)


def _lowered(case, g, request):
    """The program of `case`, lowered on the avals its warm-up would use."""
    aval = jax.ShapeDtypeStruct
    if case == "data_parallel_sampled":
        g.mesh = mesh = Mesh(np.asarray(request.getfixturevalue("four_chips")),
                             ("data",))
        Ly, D, sr = g.layout, g.num_shards, g.shard_rows

        def on(*spec):
            return NamedSharding(mesh, P(*spec))

        rows = aval((D, sr), jnp.float32, sharding=on("data", None))
        return g._grow_mc_jit_build().jit_fn().lower(
            aval((Ly.code_planes, D * Ly.num_lanes), jnp.int32,
                 sharding=on(None, "data")),
            aval((D, sr), jnp.bool_, sharding=on("data", None)),
            aval((D,), jnp.int32, sharding=on("data")), rows, rows,
            aval((g.num_features,), jnp.bool_, sharding=on()))
    if case == "data_parallel":
        mesh = Mesh(np.asarray(request.getfixturevalue("four_chips")),
                    ("data",))

        def body(data_l, nvalid_l, mask, shrinkage, bias):
            return g._train_iter(data_l, mask, shrinkage, bias,
                                 n_valid=nvalid_l[0])

        f = shard_map(body, mesh=mesh, check_vma=False,
                      in_specs=(P(None, "data"), P("data"), P(), P(), P()),
                      out_specs=(P(None, "data"), P()))
        aval, Ly, D = jax.ShapeDtypeStruct, g.layout, g.num_shards

        def on(*spec):
            return NamedSharding(mesh, P(*spec))

        return jax.jit(f, donate_argnums=0).lower(
            aval((Ly.num_planes, D * Ly.num_lanes), jnp.int32,
                 sharding=on(None, "data")),
            aval((D,), jnp.int32, sharding=on("data")),
            aval((g.num_features,), jnp.bool_, sharding=on()),
            aval((), jnp.float32, sharding=on()),
            aval((), jnp.float32, sharding=on()))
    chip = SingleDeviceSharding(request.getfixturevalue("one_chip")[0])
    if case == "per_tree_sampled":
        Ly, n = g.layout, g.actual_rows
        tables = jax.tree_util.tree_map(lambda a: aval(a.shape, a.dtype),
                                        g._tables())
        args = (tables, aval((Ly.code_planes, Ly.num_lanes), jnp.int32),
                aval((n,), jnp.float32), aval((n,), jnp.float32),
                aval((n,), jnp.bool_), aval((), jnp.int32),
                aval((g.num_features,), jnp.bool_), None)
        return jax.jit(g._entry_grow_tree,
                       static_argnames=("compute_score_update",)).lower(
            *_on(args, chip), compute_score_update=True)
    if case == "per_tree":
        (args, statics), = g._grow_entry.specs
        return jax.jit(g._entry_grow_tree,
                       static_argnames=tuple(statics)).lower(
            *_on(args, chip), **statics)
    (args, _), = g._iter_entry.specs
    return jax.jit(g._entry_train_iter, donate_argnums=1).lower(
        *_on(args, chip))


@pytest.mark.parametrize("case", sorted(CASES))
def test_no_pool_shaped_copy_in_the_v5e_program(case, as_on_tpu, request):
    extra, dtype = CASES[case]
    if case.startswith("data_parallel") and len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices to build the grower")
    g = _grower(extra)
    text = _lowered(case, g, request).compile().as_text()
    pool = re.escape(f"{dtype}[{LEAVES},{COLS},{MAX_BIN},2]")
    ops = re.findall(rf"(%\S+) = {pool}\S* ([\w-]+)\(", text)
    kinds = {kind for _, kind in ops}
    # the search sees the pool: its two row writes are there, fused
    assert "dynamic-update-slice" in kinds and "fusion" in kinds, kinds
    assert "tpu_custom_call" in text, "the Pallas kernels are not in it"
    copies = [name for name, kind in ops if kind == "copy"]
    assert not copies, (
        f"{copies}: the whole pool is copied on every split step; a pool "
        f"write reads the pre-write pool again (fused.py, `lgbm.pool`)")


# ------------------------------------------------- the traverse's one kernel

TRAVERSE_KERNEL = 'kernel_name = "traverse_planes_pallas"'


def _scoped_names(text):
    return [nm.split("/") for nm in re.findall(r'loc\("([^"]*)"', text)]


@pytest.mark.parametrize("case", ["per_tree_sampled",
                                  "data_parallel_sampled"])
def test_sampled_grow_program_traverses_in_one_kernel(case, as_on_tpu,
                                                      request, monkeypatch):
    """Under `lgbm.row_traverse` the sampled grow program holds ONE
    Pallas call, `traverse_planes_pallas`, and no loop: the XLA traverse
    it replaced is a `while` over the splits whose carry is the [R] leaf
    ids (and the search below finds that loop where it still runs)."""
    if case.startswith("data_parallel") and len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices to build the grower")
    g = _grower(CASES[case][0])
    assert g.row_traverse_method == "pallas"
    text = _lowered(case, g, request).as_text(debug_info=True)
    assert text.count(TRAVERSE_KERNEL) == 1
    assert text.count("call @traverse_planes_pallas(") == 1
    line, = [ln for ln in text.splitlines() if "call @traverse_planes_pallas("
             in ln]
    call_loc = re.search(r"loc\((#loc\d+)\)\s*$", line).group(1)
    where = re.search(rf'^{call_loc} = loc\("([^"]*)"', text, re.M).group(1)
    assert "lgbm.row_traverse" in where.split("/"), where
    loops = [nm for nm in _scoped_names(text)
             if "lgbm.row_traverse" in nm and "while" in nm]
    assert not loops, loops[:3]

    monkeypatch.setattr(type(g), "row_traverse_method", "xla")
    reset_manager()
    ref = _lowered(case, _grower(CASES[case][0]),
                   request).as_text(debug_info=True)
    assert TRAVERSE_KERNEL not in ref
    assert any("lgbm.row_traverse" in nm and "while" in nm
               for nm in _scoped_names(ref))


@pytest.mark.parametrize("shape, extra", [
    # the two dense cells' rehearsal shapes (benchmarks/configs)
    ((20000, 28, 255, 255), {"min_data_in_leaf": 20}),
    ((6000, 200, 255, 63), {"min_sum_hessian_in_leaf": 100})],
    ids=["higgs255", "epsilon63"])
def test_persistent_program_holds_no_traverse_kernel(shape, extra, as_on_tpu,
                                                     request):
    """The persistent tier assigns leaves by its partition: its iteration
    program has the three kernels it had and not the traverse's."""
    g = _grower(extra, shape)
    text = _lowered("persistent_f32", g, request).as_text()
    assert text.count("tpu_custom_call") == 3
    assert "traverse_planes_pallas" not in text


# ------------------------------------------------- the bag's one kernel pass

@pytest.mark.parametrize("case", ["per_tree_sampled",
                                  "data_parallel_sampled"])
def test_sampled_grow_program_compacts_the_bag_in_one_kernel_pass(
        case, as_on_tpu, request):
    """Under `lgbm.bag_gather` the sampled grow program calls the
    partition kernel ONCE, the kernel the tree's splits run under
    `lgbm.partition`, and nothing there sorts, gathers or
    scatters: the bag reaches lane order by the kernel's own stable
    compaction of a flag, not by a permutation."""
    if case.startswith("data_parallel") and len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices to build the grower")
    g = _grower(CASES[case][0])
    text = _lowered(case, g, request).as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def scope_path(ref):
        """Every name the location holds, its call sites' included."""
        return [part for nm in re.findall(r'"([^"]*)"', locs[ref])
                for part in nm.split("/")] + [
            part for inner in re.findall(r"#loc\d+", locs[ref])
            for part in scope_path(inner)]

    kernel = re.compile(rf"call @partition_{g._part_method}(_\d+)?")
    sites = {"lgbm.bag_gather": [], "lgbm.partition": []}
    under_bag = []
    for ln in text.splitlines():
        ref = re.search(r"loc\((#loc\d+)\)\s*$", ln)
        op = re.search(r'(?:= |^\s+)"?(?:func\.)?'
                       r'((?:stablehlo|chlo)\.[a-z_]+|call @\w+)', ln)
        if not ref or not op or ref.group(1) not in locs:
            continue
        path = scope_path(ref.group(1))
        for scope, calls in sites.items():
            if scope in path and kernel.fullmatch(op.group(1)):
                calls.append(ln)
        if "lgbm.bag_gather" in path:
            under_bag.append(op.group(1))
    assert len(sites["lgbm.bag_gather"]) == 1, sites
    assert len(sites["lgbm.partition"]) >= 1, sites
    assert len(under_bag) > 5, under_bag
    assert not [op for op in under_bag
                if "sort" in op or "gather" in op or "scatter" in op], \
        sorted(set(under_bag))
