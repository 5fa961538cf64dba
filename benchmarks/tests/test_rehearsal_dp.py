"""The four-chip cell end to end on the CPU with four forced host devices,
in a process of its own (benchmarks/tests/conftest.py forces none, and
test_rehearsal.py rehearses every cell in one one-device process, where
`tree_learner=data` is the serial learner): here the learner is the
sharded one and the mode's sharding checks hold. What a CPU run prints is
never a device number."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import loader

CELL = "higgs255dp4.train"
SCRIPT = """
import json, sys
from benchmarks.harness import clock, loader, runner
bench = loader.load_benchmark()
entry = loader.find_cell(bench, {cell!r})
overrides = {{
    "config": loader.load_config(bench, entry["config"])["rehearsal"],
    "traffic": loader.load_traffic(entry["traffic"])["rehearsal"]}}
sys.exit(runner.run_cell({cell!r}, 2147483659, 1.0, bool({trace}),
                         t_start=clock.now(), require_tpu=False,
                         overrides=overrides))
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_sharded_over_four_forced_devices(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=loader.ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(cell=CELL, trace=trace)],
        cwd=loader.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    lines = done.stdout.splitlines()
    assert done.returncode == 0, done.stderr[-2000:]
    checks = [ln for ln in lines if ln.startswith("check ")]
    line = json.loads(lines[-1])
    assert line["correct"] is True, "\n".join(checks)
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0}
    plan, = [ln for ln in checks if ln.startswith("check dp_plan:")]
    assert "learner FusedDataParallelGrower" in plan \
        and "device_count 4" in plan and "packed on: host" in plan
    held = {ln.split(":")[0] for ln in checks}
    assert {"check dp_sharding", "check dp_memory_balance",
            "check tree0_leaf_counts"} <= held
    assert ("check dp_blocking_syncs" in held) == bool(trace)
    if trace:
        # the data-parallel dispatch runs under the serial one's span
        assert line["metrics"]["jit_call_ms_per_iter"]["value"] > 0.0
        assert line["metrics"]["blocking_syncs_per_iter"]["value"] == 0.0
        # no device trace on this backend: the collectives' readers say
        # nothing rather than 0
        assert not {"allreduce_device_share", "allreduce_roofline",
                    "shard_imbalance_share"} & set(line["metrics"])
