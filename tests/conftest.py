"""Test configuration: run everything on a virtual 8-device CPU mesh
(JAX_PLATFORMS=cpu + xla_force_host_platform_device_count), the same
environment the multi-chip dry run uses.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# No background preload of the AOT store: a preload thread loads EVERY
# executable any worker has stored, each worker then holds all of them
# for life, and their memory mappings take a worker to the kernel's
# per-process limit (vm.max_map_count; ROADMAP C12). An entry still
# loads its own executable from the store at its first call.
os.environ.setdefault("LGBM_TPU_AOT_PRELOAD", "0")

import jax  # noqa: E402

from lightgbm_tpu.compile import ensure_compile_cache  # noqa: E402

# The one compile cache (JAX_COMPILATION_CACHE_DIR, else the checkout's
# .jax_cache): the suite pays hundreds of small per-config compiles, and
# repeat runs skip them. min_compile_time 0 caches even sub-second
# programs: a cache lookup is orders of magnitude cheaper than any
# compile.
ensure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(42)

