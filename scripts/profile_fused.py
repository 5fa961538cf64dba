"""Profile the fused training iteration on the real device and print
the top HLO ops by device time (parses the jax.profiler trace JSON,
no tensorboard needed). Uses the same shapes as bench.py so the
persistent compile cache is shared."""
import glob
import gzip
import json
import os
import sys
import time
from collections import defaultdict

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("BENCH_ROWS", 1 << 20))
LEAVES = int(os.environ.get("BENCH_LEAVES", 255))


def main():
    import jax
    import lightgbm_tpu as lgb
    from chip_smoke import make_higgs_like

    X, y = make_higgs_like(ROWS, 28)
    params = {"objective": "binary", "num_leaves": LEAVES, "max_bin": 255,
              "learning_rate": 0.1, "verbose": -1, "min_data_in_leaf": 20}
    t0 = time.time()
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=1,
                    verbose_eval=False, keep_training_booster=True)
    jax.block_until_ready(bst._gbdt.device_score_state())
    print(f"first iter (compile+run): {time.time() - t0:.1f}s")

    t0 = time.time()
    bst.update()
    jax.block_until_ready(bst._gbdt.device_score_state())
    print(f"steady iter: {time.time() - t0:.3f}s")

    tdir = "/tmp/fused_trace"
    os.system(f"rm -rf {tdir}")
    with jax.profiler.trace(tdir):
        for _ in range(2):
            bst.update()
        jax.block_until_ready(bst._gbdt.device_score_state())

    files = glob.glob(f"{tdir}/**/*.trace.json.gz", recursive=True)
    if not files:
        print("no trace written; files:",
              glob.glob(f"{tdir}/**/*", recursive=True))
        return
    with gzip.open(files[0], "rt") as fh:
        trace = json.load(fh)
    events = trace.get("traceEvents", [])
    # find device-side lanes (TPU core threads); host python lanes excluded
    pid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
    device_pids = {p for p, n in pid_names.items()
                   if "TPU" in n or "/device" in n.lower()}
    agg = defaultdict(float)
    cnt = defaultdict(int)
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        name = e.get("name", "?")
        dur = e.get("dur", 0) / 1e3  # ms
        agg[name] += dur
        cnt[name] += 1
        total += dur
    print(f"\ndevice lanes: {[pid_names[p] for p in device_pids]}")
    print(f"total device time in trace: {total:.1f} ms (2 iterations)")
    for name, dur in sorted(agg.items(), key=lambda kv: -kv[1])[:25]:
        print(f"{dur:10.2f} ms  x{cnt[name]:<6d} {name[:90]}")


if __name__ == "__main__":
    main()
