"""python3 benchmarks/tools/measure.py --out DIR [--seconds S] CELL:SEEDS[:TRACE] ...

The runs a benchmark PR makes to set its bounds: each run a process of
its own (`benchmarks/run.py`), one after the other, never touching JAX
here. `higgs255.train:1,2,3` runs three seeds untraced;
`higgs255.train:4:1` runs seed 4 with --trace 1. Every run's output goes to
DIR/<cell>.seed<k>.trace<t>.log, its result line (with the end-to-end
values a traced run prints on an earlier line) to DIR/runs.jsonl, and a
summary of medians and spreads (the distance between the quartiles over
the median, as the driver takes it) to standard output.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
EARLIER = "end_to_end (not this line's metrics): "


def run_once(cell: str, seed: int, trace: int, seconds: int, out: str,
             timeout: int) -> dict:
    log = os.path.join(out, f"{cell}.seed{seed}.trace{trace}.log")
    cmd = [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    with open(log, "w") as fh:
        try:
            rc = subprocess.run(cmd, cwd=ROOT, stdout=fh, timeout=timeout,
                                stderr=subprocess.STDOUT).returncode
        except subprocess.TimeoutExpired:
            rc = 124
    rec = {"cell": cell, "seed": seed, "trace": trace, "rc": rc,
           "wall_s": time.time() - t0}
    with open(log) as fh:
        lines = fh.read().splitlines()
    if rc == 0 and lines:
        rec["result"] = json.loads(lines[-1])
        rec["end_to_end"] = rec["result"]["metrics"]
        for line in lines:
            if line.startswith(EARLIER):
                rec["end_to_end"] = json.loads(line[len(EARLIER):])
    return rec


def spread(values: list) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--timeout", type=int, default=1200,
                    help="seconds a run may take (a first run compiles)")
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        seconds = args.seconds or json.load(fh)["run_seconds"]
    recs = []
    for spec in args.runs:
        cell, seeds, *trace = spec.split(":")
        for seed in seeds.split(","):
            rec = run_once(cell, int(seed), int(trace[0]) if trace else 0,
                           seconds, args.out, args.timeout)
            recs.append(rec)
            with open(os.path.join(args.out, "runs.jsonl"), "a") as fh:
                fh.write(json.dumps(rec) + "\n")
            ok = rec.get("result", {}).get("correct")
            print(f"{cell} seed={seed} trace={rec['trace']} rc={rec['rc']} "
                  f"correct={ok} wall={rec['wall_s']:.0f}s "
                  + " ".join(f"{k}={v['value']:.6g}" for k, v in
                             rec.get("end_to_end", {}).items()), flush=True)
    for cell in dict.fromkeys(r["cell"] for r in recs):
        mine = [r for r in recs if r["cell"] == cell and "end_to_end" in r]
        for name in dict.fromkeys(k for r in mine for k in r["end_to_end"]):
            vals = [r["end_to_end"][name]["value"] for r in mine
                    if name in r["end_to_end"]]
            print(f"summary {cell} {name}: n={len(vals)} "
                  f"median={statistics.median(vals):.6g} "
                  f"spread={spread(vals):.4%} "
                  f"(first run, which may have compiled, included) "
                  f"values={[round(v, 5) for v in vals]}", flush=True)
    return 0 if all(r["rc"] == 0 for r in recs) else 1


if __name__ == "__main__":
    sys.exit(main())
