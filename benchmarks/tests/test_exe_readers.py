"""The five readers of the program's executable table
(`harness/exe_table.py`) on the CPU rehearsal of every cell: a finite
number >= 0 each, the split of `host_dispatch_ms_per_iter` exact, both
printed lines there; and None, with nothing printed, on a program that
has no table (the parent of the PR that brought it). The four-chip cell
is the one-device process's here, as in test_rehearsal.py."""
import json
import math
import types

import pytest

from benchmarks.harness import clock, exe_table, loader, runner

BENCH = loader.load_benchmark()
NEW = ("exe_call_ms_per_iter", "exe_call_blocked_ms_per_iter",
       "host_python_ms_per_iter", "first_call_xla_s", "first_call_lower_s")


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_the_readers_on_the_rehearsal_of_the_cell(cell, capsys):
    entry = loader.find_cell(BENCH, cell)
    overrides = {
        "config": loader.load_config(BENCH, entry["config"])["rehearsal"],
        "traffic": loader.load_traffic(entry["traffic"])["rehearsal"]}
    rc = runner.run_cell(cell, 2147483693, 1.0, True, t_start=clock.now(),
                         require_tpu=False, overrides=overrides)
    lines = capsys.readouterr().out.splitlines()
    assert rc == 0
    got = {k: v["value"] for k, v in json.loads(lines[-1])["metrics"].items()}
    for name in NEW:
        assert math.isfinite(got[name]) and got[name] >= 0.0, (name, got)
    assert got["exe_call_ms_per_iter"] > 0.0
    assert got["exe_call_blocked_ms_per_iter"] <= got["exe_call_ms_per_iter"]
    assert got["exe_call_ms_per_iter"] + got["host_python_ms_per_iter"] \
        == pytest.approx(got["host_dispatch_ms_per_iter"], rel=0.01)
    if "jit_call_ms_per_iter" in got:
        # the profiler's clock around the wrapper, the manager's inside it
        assert got["exe_call_ms_per_iter"] \
            <= got["jit_call_ms_per_iter"] * 1.05 + 0.05
    builds, = [ln for ln in lines if ln.startswith("program builds: ")]
    assert exe_table.UNREGISTERED in builds
    traffic = dict(loader.load_traffic(entry["traffic"]),
                   **overrides["traffic"])
    calls = [ln for ln in lines if ln.startswith("program calls (")]
    assert len(calls) == 2 and calls[0].startswith("program calls (window, ")
    assert calls[1].startswith(
        f"program calls (traced, {int(traffic['traced_iters'])} update()s")


def test_a_program_without_the_table_reads_none(monkeypatch, capsys):
    import lightgbm_tpu.compile as compile_
    monkeypatch.setattr(compile_, "get_manager",
                        lambda: types.SimpleNamespace(snapshot=dict))
    ev = types.SimpleNamespace(spans=clock.Spans())
    with ev.spans.span("update"):
        pass
    for name in NEW:
        assert loader.load_module("layer_metrics", name).read(ev) is None
    assert capsys.readouterr().out == ""


def test_calls_between_two_clock_readings():
    zero = {"a": (1, 1.0, 0.5)}
    marks = [(1.0, 0.25, zero),
             (2.0, 0.5, {"a": (2, 3.0, 1.0), "b": (1, 0.5, 0.5)}),
             (3.0, 1.0, {"a": (3, 6.0, 1.5), "b": (2, 1.0, 1.0)})]
    assert exe_table._between(marks, 1.5, 3.5) == (
        2, {"a": (2, 5.0, 1.0), "b": (2, 1.0, 1.0)}, 2.0, 0.75)
    assert exe_table._between(marks, 2.5, 3.0) == (
        1, {"a": (1, 3.0, 0.5), "b": (1, 0.5, 0.5)}, 1.0, 0.5)
    assert exe_table._between(marks, 0.5, 3.0) is None     # no mark before
    assert exe_table._between(marks, 3.0, 4.0) is None     # no update inside
