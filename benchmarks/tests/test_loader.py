"""The loader finds files by the names in BENCHMARK.json and refuses what
it does not hold."""
import pytest

from benchmarks.harness import loader
from benchmarks import run


def test_every_name_in_benchmark_json_resolves():
    bench = loader.load_benchmark()
    for cell in bench["workloads"]:
        config = loader.load_config(bench, cell["config"])
        traffic = loader.load_traffic(cell["traffic"])
        loader.load_module("modes", traffic["mode"])
        loader.load_module("generators", config["generator"]["name"])
        for section in ("end_to_end", "per_layer"):
            readers = loader.metrics_of(bench, section, cell["name"])
            assert readers, (cell["name"], section)
            assert all(hasattr(mod, "read") for _, mod in readers)
        moved = {m["name"] for m, _ in
                 loader.metrics_of(bench, "end_to_end", cell["name"])}
        assert "setup_s" in moved and len(moved) >= 2
        for m, _ in loader.metrics_of(bench, "per_layer", cell["name"]):
            assert m["moves"] in moved, (cell["name"], m["name"])


def test_config_files_say_what_benchmark_json_says():
    bench = loader.load_benchmark()
    for entry in bench["configs"]:
        config = loader.load_config(bench, entry["name"])
        assert config["name"] == entry["name"]
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]


@pytest.mark.parametrize("call", [
    lambda: loader.find_cell(loader.load_benchmark(), "no.such.cell"),
    lambda: loader.load_config(loader.load_benchmark(), "no-such-config"),
    lambda: loader.load_traffic("no_such_mix"),
    lambda: loader.load_traffic("../configs/higgs255"),
    lambda: loader.load_module("modes", "no_such_mode"),
    lambda: loader.load_module("layer_metrics", "../harness/work"),
    lambda: loader.load_module("../tests", "conftest"),
])
def test_unknown_names_are_refused(call):
    with pytest.raises(loader.UnknownName):
        call()


def test_the_measuring_command_refuses_another_backend(capsys):
    cell = loader.load_benchmark()["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == "" and "tpu" in out.err
    assert run.main(["--workload", "no.such.cell", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2


def test_benchmark_json_keeps_the_contracts_limits():
    import os
    import re
    bench = loader.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(loader.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    name = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
    names = [e["name"] for section in ("configs", "workloads", "end_to_end",
                                       "per_layer") for e in bench[section]]
    assert all(name.match(n) for n in names)
    for section in ("configs", "workloads"):
        assert all(len(e["why"]) <= 200 for e in bench[section])
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    assert {c["config"] for c in cells} == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    assert all(f.startswith(tuple(p + "/" for p in bench["paths"]))
               for f in files)
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
