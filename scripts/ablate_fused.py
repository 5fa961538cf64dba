import time, numpy as np, jax, jax.numpy as jnp
import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.treelearner.fused import FusedSerialGrower

N, F = 1_000_000, 28
rng = np.random.RandomState(0)
X = rng.randn(N, F).astype(np.float32)
y = (X[:,0] > 0).astype(np.float32)
cfg = Config.from_params({"objective":"binary","num_leaves":255,"max_bin":255,"verbose":-1})
ds = BinnedDataset.from_matrix(X, cfg, label=y)
grad = jnp.asarray(rng.randn(N).astype(np.float32))
hess = jnp.asarray(np.ones(N, dtype=np.float32))

def time_grow(tag, grower):
    t0=time.time()
    ta, lo = grower.grow_device(grad, hess)
    jax.block_until_ready(lo)
    compile_t = time.time()-t0
    t0=time.time()
    for _ in range(3):
        ta, lo = grower.grow_device(grad, hess)
    jax.block_until_ready(lo)
    print(f"{tag}: compile {compile_t:.1f}s, steady {(time.time()-t0)/3*1e3:.0f} ms/tree", flush=True)

g = FusedSerialGrower(ds, cfg)
time_grow("full", g)

g2 = FusedSerialGrower(ds, cfg)
def fake_partition(perm, start, count, feature, thr, dl, miss_bin, grad_dummy=None):
    return perm, count // 2
g2._partition_full = fake_partition
time_grow("no_partition", g2)

g3 = FusedSerialGrower(ds, cfg)
g3._partition_full = fake_partition
B = g3.max_num_bin
def fake_hist(perm, start, count, grad, hess):
    return jnp.ones((g3.num_features, B, 2), jnp.float32)
g3._leaf_hist_switch = fake_hist
time_grow("no_partition_no_hist", g3)

g4 = FusedSerialGrower(ds, cfg)
g4._leaf_hist_switch = fake_hist
time_grow("no_hist_only", g4)
