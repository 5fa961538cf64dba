"""meshlint: the device-side rule packs (collective-axis,
kernel-contract, dtype-flow).

Same three layers as test_tpulint.py: fixture tests seeding one
violation per check (plus the annotated/structured negative twin), the
package-wide zero-findings gate per pack, and a slow runtime
cross-check that the static mesh-axis inventory accounts for the mesh
`build_mesh` actually constructs on the 8-device CPU dryrun.

Everything except the slow check is pure `ast` — no jax import, no
jit — so this file adds ~seconds to tier-1, not minutes.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from lightgbm_tpu.analysis import collective_axis, dtype_flow, kernel_contract
from lightgbm_tpu.analysis import runtime_check
from lightgbm_tpu.analysis.core import Package
from lightgbm_tpu.analysis.mesh_inventory import (axis_inventory,
                                                  mapped_bodies)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REPO_PKG = None


def repo_pkg():
    global _REPO_PKG
    if _REPO_PKG is None:
        _REPO_PKG = Package.load(REPO_ROOT)
    return _REPO_PKG


def make_pkg(tmp_path, files):
    """Synthetic package: {relpath under lightgbm_tpu/: source}."""
    for rel, src in files.items():
        p = tmp_path / "lightgbm_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Package.load(str(tmp_path))


def codes(findings):
    return {f.code for f in findings}


# ------------------------------------------------------- mesh inventory

def test_axis_inventory_literals_and_dynamic(tmp_path):
    pkg = make_pkg(tmp_path, {"mesh.py": """\
        import numpy as np
        from jax.sharding import Mesh

        def one_axis(devices):
            return Mesh(devices, ("data",))

        def multi(devices, shape):
            axes = tuple(f"axis{i}" for i in range(len(shape))) + ("data",)
            return Mesh(devices.reshape(shape), axes)
        """})
    inv = axis_inventory(pkg)
    assert "data" in inv.axes
    assert inv.dynamic
    assert inv.permits("data") and inv.permits("axis3")
    assert not inv.permits("dat")
    assert len(inv.meshes) == 2


def test_mapped_bodies_all_spellings(tmp_path):
    pkg = make_pkg(tmp_path, {"maps.py": """\
        import functools
        import jax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        @functools.partial(shard_map, mesh=None, in_specs=P("data"),
                           out_specs=P())
        def deco_body(x):
            return x

        def call_form(mesh, x):
            def body(b):
                return b
            return shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P())(x)

        def partial_form(mesh, x):
            def body2(b):
                return b
            fn = functools.partial(shard_map, mesh=mesh,
                                   in_specs=P("data"), out_specs=P())(body2)
            return fn(x)

        def pmapped(x):
            def body3(b):
                return b
            return jax.pmap(body3, axis_name="data")(x)
        """})
    roots = mapped_bodies(pkg)
    names = {q.split("::")[1] for q in roots}
    assert names == {"deco_body", "call_form.body", "partial_form.body2",
                     "pmapped.body3"}


# ------------------------------------------------------ collective-axis

_COLLECTIVE_COMMON = """\
    import functools
    import jax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    def build(devices):
        return Mesh(devices, ("data",))
"""


def test_collective_axis_catches_typo_and_unmapped(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": _COLLECTIVE_COMMON + """\

    def mapped_body(x):
        return jax.lax.psum(x, "dat")      # typo: no mesh defines "dat"

    def entry(mesh, x):
        return shard_map(mapped_body, mesh=mesh, in_specs=P("data"),
                         out_specs=P())(x)

    def never_mapped(x):
        return jax.lax.psum(x, "data")
        """})
    found = collective_axis.check(pkg)
    assert "axis-unknown:dat" in codes(found)
    assert "unmapped-collective" in codes(found)
    # the typo site IS mapped: only never_mapped trips the unmapped check
    unmapped = [f for f in found if f.code == "unmapped-collective"]
    assert all(f.func.endswith("never_mapped") for f in unmapped)


def test_collective_axis_negatives_and_pragma(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": _COLLECTIVE_COMMON + """\

    def helper(x):
        # bound transitively: entry's body calls helper
        return jax.lax.psum(x, "data")

    def mapped_body(x):
        return helper(jax.lax.all_gather(x, "data"))

    def entry(mesh, x):
        return shard_map(mapped_body, mesh=mesh, in_specs=P("data"),
                         out_specs=P())(x)

    def external_harness(x):
        return jax.lax.psum(x, "data")  # tpulint: mesh-ok(called under an external pjit harness)

    def guarded(self, x):
        self.psum_axis = None
        if self.psum_axis is None:
            return x
        return jax.lax.psum(x, self.psum_axis)
        """})
    assert collective_axis.check(pkg) == []


def test_collective_axis_attribute_axis_resolution(tmp_path):
    # self.<attr> axes resolve through package-wide constant
    # assignments; a non-None resolved value in an unmapped method is
    # a finding (the fused/parallel psum_axis pattern)
    pkg = make_pkg(tmp_path, {"mod.py": _COLLECTIVE_COMMON + """\

    class G:
        def __init__(self):
            self.psum_axis = "data"

        def reduce(self, x):
            return jax.lax.psum(x, self.psum_axis)
        """})
    found = collective_axis.check(pkg)
    assert codes(found) == {"unmapped-collective"}


def test_collective_axis_quantize_contract(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": _COLLECTIVE_COMMON + """\
    from .ops.quantize import pack_gh, pairs_to_packed_hist, \\
        packed_hist_to_pairs

    def bad_unpack_first(mesh, h):
        def body(b):
            return jax.lax.psum(packed_hist_to_pairs(b), "data")
        return shard_map(body, mesh=mesh, in_specs=P("data"),
                         out_specs=P())(h)

    def bad_pack_after(mesh, h):
        def body(b):
            return pairs_to_packed_hist(jax.lax.psum(b, "data"))
        return shard_map(body, mesh=mesh, in_specs=P("data"),
                         out_specs=P())(h)

    def good(mesh, h):
        def body(b):
            return packed_hist_to_pairs(
                jax.lax.psum(pairs_to_packed_hist(b), "data"))
        return shard_map(body, mesh=mesh, in_specs=P("data"),
                         out_specs=P())(h)
        """, "ops/quantize.py": """\
    def pack_gh(qg, qh):
        return qg

    def pairs_to_packed_hist(h):
        return h

    def packed_hist_to_pairs(p):
        return p
        """})
    found = collective_axis.check(pkg)
    by_code = codes(found)
    assert "psum-of-unpacked" in by_code
    assert "pack-after-psum" in by_code
    # the contract-conforming composition in good() stays quiet
    assert all(not f.func.endswith("good.body") for f in found)


# ------------------------------------------------------- kernel-contract

_PALLAS_COMMON = """\
    import functools
    import jax
    import jax.numpy as jnp
"""


def test_kernel_contract_tiling(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": _PALLAS_COMMON + """\

    def kernel(x_ref, out_ref):
        out_ref[...] = x_ref[...]

    def run(x):
        from jax.experimental import pallas as pl
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((5, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((20, 128), jnp.float32),
        )(x)
        """})
    found = kernel_contract.check(pkg)
    assert "tile-lane:100" in codes(found)
    assert "tile-sublane:5" in codes(found)


def test_kernel_contract_divisibility_and_out_dtype(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": _PALLAS_COMMON + """\

    def kernel(x_ref, out_ref):
        out_ref[...] = x_ref[...].astype(jnp.bfloat16)

    def run(x):
        from jax.experimental import pallas as pl
        return pl.pallas_call(
            kernel,
            grid=(3,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((16, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((40, 128), jnp.float32),
        )(x)
        """})
    found = kernel_contract.check(pkg)
    assert "block-divisibility:0" in codes(found)      # 40 % 16 != 0
    assert "out-dtype:bfloat16-vs-float32" in codes(found)


def test_kernel_contract_tiling_negatives(tmp_path):
    # variable dims are trusted; aligned literals stay quiet; pragma
    # silences a deliberate sub-tile block
    pkg = make_pkg(tmp_path, {"mod.py": _PALLAS_COMMON + """\

    def kernel(x_ref, s_ref, out_ref):
        out_ref[...] = x_ref[...].astype(jnp.float32)

    def run(x, s, rows):
        from jax.experimental import pallas as pl
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[
                pl.BlockSpec((rows, 128), lambda i: (i, 0)),
                pl.BlockSpec((rows, 1), lambda i: (i, 0)),  # tpulint: tile-ok(per-row scalar column rides one padded lane)
            ],
            out_specs=pl.BlockSpec((8, 256), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((32, 256), jnp.float32),
        )(x, s)
        """})
    assert kernel_contract.check(pkg) == []


def test_kernel_contract_memspace_and_bitcast(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": _PALLAS_COMMON + """\

    def space():
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.ANY

    def widths(x):
        return jax.lax.bitcast_convert_type(x.astype(jnp.uint16),
                                            jnp.uint8)
        """})
    found = kernel_contract.check(pkg)
    assert "memspace:ANY" in codes(found)
    assert "bitcast-width:uint16->uint8" in codes(found)


def test_kernel_contract_memspace_bitcast_negatives(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": _PALLAS_COMMON + """\

    def smem_is_fine():
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.SMEM

    def same_width(x):
        y = x.astype(jnp.float32)
        return jax.lax.bitcast_convert_type(y, jnp.int32)

    def annotated(x):
        # tpulint: tile-ok(deliberate plane split for the packed layout)
        return jax.lax.bitcast_convert_type(x.astype(jnp.uint16),
                                            jnp.uint8)

    def hbm_is_the_name():
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.HBM
        """})
    assert kernel_contract.check(pkg) == []


# ---------------------------------------------------------- dtype-flow

def test_dtype_flow_narrow_sum_and_packed(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": """\
        import jax.numpy as jnp
        from .ops.quantize import pairs_to_packed_hist, unpack_gh

        def narrow(x):
            q = x.astype(jnp.int16)
            return jnp.sum(q)

        def narrow_method(w):
            qg, qh = unpack_gh(w)
            return qg.sum()

        def packed_bad(h):
            w = pairs_to_packed_hist(h)
            return w.astype(jnp.float32)
        """, "ops/quantize.py": """\
        def pairs_to_packed_hist(h):
            return h

        def unpack_gh(w):
            return w, w
        """})
    found = dtype_flow.check(pkg)
    assert "narrow-sum:int16" in codes(found)
    assert "packed-as-float" in codes(found)
    assert len([f for f in found if f.code == "narrow-sum:int16"]) == 2


def test_dtype_flow_subtract_and_accum(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": """\
        import jax.numpy as jnp

        def dequant_bad(parent, sib):
            pi = parent.astype(jnp.int32)
            si = sib.astype(jnp.int32)
            p = pi.astype(jnp.float32)
            s = si.astype(jnp.float32)
            return p - s

        def accum_bad(idx, v):
            acc = jnp.zeros((8,), dtype=jnp.int16)
            w = v.astype(jnp.int32)
            return acc.at[idx].add(w)
        """})
    found = dtype_flow.check(pkg)
    assert "dequant-before-subtract" in codes(found)
    assert "accum-downcast:int16<-int32" in codes(found)


def test_dtype_flow_negatives_and_pragma(tmp_path):
    pkg = make_pkg(tmp_path, {"mod.py": """\
        import jax.numpy as jnp

        def widened(x):
            q = x.astype(jnp.int16)
            return jnp.sum(q, dtype=jnp.int32)

        def subtract_in_int(parent, sib):
            pi = parent.astype(jnp.int32)
            si = sib.astype(jnp.int32)
            return (pi - si).astype(jnp.float32)

        def wide_accum(idx, v):
            acc = jnp.zeros((8,), dtype=jnp.int32)
            return acc.at[idx].add(v.astype(jnp.int32))

        def annotated(x):
            q = x.astype(jnp.int16)
            return jnp.sum(q)  # tpulint: dtype-ok(histogram is <256 rows; 16-bit sum cannot overflow)
        """})
    assert dtype_flow.check(pkg) == []


# -------------------------------------------------------- package gates

def test_package_clean_collective_axis():
    found = collective_axis.check(repo_pkg())
    assert found == [], "\n".join(map(str, found))


def test_package_clean_kernel_contract():
    found = kernel_contract.check(repo_pkg())
    assert found == [], "\n".join(map(str, found))


def test_package_clean_dtype_flow():
    found = dtype_flow.check(repo_pkg())
    assert found == [], "\n".join(map(str, found))


def test_repo_inventory_and_roots_nonempty():
    """The world model the packs check against must be non-trivial on
    the real repo: the "data" axis and the shard_map bodies of the
    parallel learners must be visible statically."""
    pkg = repo_pkg()
    inv = axis_inventory(pkg)
    assert "data" in inv.axes
    assert inv.dynamic          # build_mesh's f"axis{i}" multi-dim form
    roots = mapped_bodies(pkg)
    rels = {q.split("::")[0] for q in roots}
    assert any(r.endswith("treelearner/parallel.py") for r in rels)
    assert any(r.endswith("io/distributed.py") for r in rels)


# ----------------------------------------------------------- CLI + obs

def test_cli_rules_subset_json():
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis", "--json",
         "--rules", "collective-axis,kernel-contract,dtype-flow"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] and payload["new"] == []
    assert payload["by_rule"] == {}


@pytest.mark.slow
def test_run_publishes_meshlint_gauges():
    """Slow-marked: pack-generic gauge publication stays tier-1 via
    test_lifelint::test_run_publishes_lifelint_gauges (two-pack run);
    the meshlint rules themselves are tier-1 via the fixture tests."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.analysis import run
    reg = obs.MetricsRegistry()
    obs.activate(reg)
    try:
        run(REPO_ROOT, pkg=repo_pkg())
        assert reg.gauges.get("lint.mesh_findings") == 0.0
        assert reg.gauges.get("lint.tile_findings") == 0.0
        assert reg.gauges.get("lint.dtype_findings") == 0.0
    finally:
        obs.activate(None)


# ------------------------------------------------- runtime cross-check

@pytest.mark.slow
def test_mesh_inventory_matches_runtime_mesh():
    """The static axis inventory must account for every axis of the
    mesh build_mesh actually constructs on the 8-device CPU dryrun —
    default config and an explicit multi-dim tpu_mesh_shape."""
    from lightgbm_tpu.config import Config

    report = runtime_check.mesh_axis_check(pkg=repo_pkg())
    assert report["unaccounted"] == [], report
    assert report["runtime_axes"] == ["data"]

    multi = runtime_check.mesh_axis_check(
        Config(tpu_mesh_shape=[2, 4]), pkg=repo_pkg())
    assert multi["unaccounted"] == [], multi
    assert "data" in multi["runtime_axes"]
