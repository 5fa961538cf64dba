"""Every cell of BENCHMARK.json end to end on the CPU at a tiny size: the
same runner, modes, generators, readers and result line as on the chip.
The sizes come from the "rehearsal" keys of the cell's configuration and
traffic files, so a cell a later PR adds is rehearsed without a change
here. (The measuring command itself refuses a backend that is no TPU:
test_loader.py.) What a CPU run prints is never a device number."""
import json

import pytest

from benchmarks.harness import clock, loader, runner

BENCH = loader.load_benchmark()


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_cell_runs_end_to_end(cell, trace, capsys):
    entry = loader.find_cell(BENCH, cell)
    overrides = {
        "config": loader.load_config(BENCH, entry["config"])["rehearsal"],
        "traffic": loader.load_traffic(entry["traffic"])["rehearsal"]}
    rc = runner.run_cell(cell, 11, 1.0, bool(trace), t_start=clock.now(),
                         require_tpu=False, overrides=overrides)
    lines = capsys.readouterr().out.splitlines()
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True, "\n".join(
        ln for ln in lines if ln.startswith("check "))
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m, _ in
                loader.metrics_of(BENCH, section, cell)}
    assert line["metrics"], "a cell reports at least one metric"
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name] and got["value"] == got["value"]
    # no device trace and no memory statistic on this backend: the readers
    # that need one leave their metric out
    host_side = {m["name"] for m, _ in loader.metrics_of(BENCH, section, cell)
                 if m["source"] != "device_trace"} - {"peak_hbm_gib"}
    assert set(line["metrics"]) == host_side
