"""Lane rotates of one partition-kernel tile, counted without a chip.

The partition kernels' time follows the count of sub-column lane rotates
(`llo.vrot.lane`, ~3.0 ns each on a v5e; PERF.md section 6, PRs 28 and
32) in Mosaic's final LLO, where the body is one unrolled tile. This
compiles each kernel for a DESCRIBED v5e (nothing runs, no chip is
needed) with Mosaic's dump turned on and counts them, with the other
vector ops beside them.

Run:  JAX_PLATFORMS=cpu python scripts/part_rotates.py [PxS ...]
      (default: the cells' 16x8192 8x16384 512x512)
"""
import collections
import glob
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import part_micro

DEFAULT = ("16x8192", "8x16384", "512x512")


def main(argv):
    # libtpu reads its flags once, when it is loaded: before jax is
    dump = tempfile.mkdtemp(prefix="part_rotates_")
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "")
        + f" --xla_mosaic_dump_to={dump}")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.ops import plane

    # a compile for a described device is written to jax's persistent
    # cache but cannot be read back, and a hit would dump nothing
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1", chip_config_name="default",
        chips_per_host_bounds=(1, 1, 1), num_slices=1).devices[0])
    for geom in argv or DEFAULT:
        P, S = (int(v) for v in geom.split("x"))
        layout = part_micro.layout_for(P, S, 1 << 20)
        args = (jax.ShapeDtypeStruct((P, layout.num_lanes), jnp.int32,
                                     sharding=chip),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
                jax.ShapeDtypeStruct((plane.ROUTE_SCALARS,), jnp.int32,
                                     sharding=chip))
        for method, fn in (("pallas2", plane.partition_pallas2),
                           ("pallas", plane.partition_pallas)):
            if plane.partition_vmem_bytes_at(P, S, method) \
                    > plane.PART_VMEM_BUDGET:
                continue        # the grower would not pick it here either
            before = set(glob.glob(os.path.join(dump, "*")))
            jax.jit(lambda d, s, n, r: fn(d, layout, s, n, r, cap=None,
                                          tile=S)).lower(*args).compile()
            (llo,) = [f for f in set(glob.glob(os.path.join(dump, "*")))
                      - before if f.endswith("post-finalize-llo.txt")]
            with open(llo) as fh:
                ops = collections.Counter(
                    re.findall(r"\bllo\.(v[a-z_0-9.]+)", fh.read()))
            print(f"{fn.__name__} P={P} S={S}: "
                  f"{ops['vrot.lane']} lane rotates a tile, "
                  f"{sum(ops.values())} vector ops "
                  f"({ops['vselect']} selects, {ops['vector_load']} loads, "
                  f"{ops['vector_store']} stores)", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
