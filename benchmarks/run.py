"""python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of BENCHMARK.json, one run, on the machine it is
started on. Refuses to run off a TPU or with another number of chips than
the cell asks for (exit code 2, no result line). The last line of standard
output is the result: `correct`, `attempted`, `failed`, `metrics`,
`device`, and `breakdown` when traced; what the run saw on its way
(execution plan, stage seconds, compile counts, each check) is on the
lines before it.
"""
import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    from benchmarks.harness import device, loader, runner
    try:
        return runner.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except (loader.UnknownName, device.WrongDevice) as exc:
        print(f"benchmarks/run.py: {exc}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
