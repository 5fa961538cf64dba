"""What the compile manager's dispatch point costs a call, in ns: a
plain-jit entry around a Python no-op (the manager's own cost and nothing
else), the same around a trivial jitted program, a shared (AOT) entry of
that program, and the bare jit for scale. Run it on two checkouts
(`PYTHONPATH=<checkout> python scripts/exe_table_micro.py`) to read what
a change to `compile/manager.py` adds: it uses only `jit_entry`,
`shared_entry` and `get_manager`. Runs on whatever device it finds."""
import sys
import time

import jax
import jax.numpy as jnp

from lightgbm_tpu.compile import get_manager

N = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000


def ns_a_call(fn, *args):
    for _ in range(1000):
        out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(N):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / N * 1e9)
    return best


def main():
    mgr = get_manager()
    x = jnp.zeros((8,), jnp.float32)
    add = jax.jit(lambda v: v + 1.0)  # tpulint: jit-ok(a micro benchmark's probe)
    rows = {
        "noop": ns_a_call(lambda: None),
        "jit_entry(noop)": ns_a_call(mgr.jit_entry("micro/noop",
                                                   lambda: None)),
        "bare jit": ns_a_call(add, x),
        "jit_entry(jit)": ns_a_call(mgr.jit_entry("micro/add", add), x),
        "shared_entry(jit)": ns_a_call(mgr.shared_entry(
            "micro/add_aot", ("micro", 1),
            lambda: jax.jit(lambda v: v + 1.0)), x),  # tpulint: jit-ok(inside a shared_entry builder)
    }
    table = hasattr(mgr, "snapshot_entries")
    print(f"exe_table_micro: device={jax.devices()[0].device_kind} "
          f"calls={N} table={'yes' if table else 'no'} " + " ".join(
              f"[{k}]={v:.0f}ns" for k, v in rows.items()), flush=True)


if __name__ == "__main__":
    main()
