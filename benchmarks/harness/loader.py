"""Finds everything a cell is made of by the names in BENCHMARK.json.

The harness knows no cell, configuration, traffic mix, generator, mode or
metric by name. A later PR adds one by adding its file here and an entry
in BENCHMARK.json:

    configs/<config>.json          sizes, params, generator, bands
    traffic/<traffic>.json         parameters of a mix; "mode" names its driver
    generators/<generator>.py      make(...): inputs from a seed
    modes/<mode>.py                setup / window / traced / check
    end_to_end/<metric>.py         read(ev): one end-to-end metric
    layer_metrics/<metric>.py      read(ev): one per-layer metric
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
_NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
_SECTIONS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


class UnknownName(LookupError):
    """A name that BENCHMARK.json or a benchmark directory does not hold."""


def _checked(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise UnknownName(f"not a benchmark name: {name!r}")
    return name


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise UnknownName(f"no workload {name!r} in BENCHMARK.json; it has "
                      f"{[c['name'] for c in bench['workloads']]}")


def load_config(bench: dict, name: str) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            return _read_json(os.path.join(ROOT, entry["file"]))
    raise UnknownName(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    path = os.path.join(BENCH_DIR, "traffic", _checked(name) + ".json")
    if not os.path.exists(path):
        raise UnknownName(f"no traffic mix {name!r} ({path})")
    return _read_json(path)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module of its own."""
    path = os.path.join(BENCH_DIR, _checked(kind), _checked(name) + ".py")
    if not os.path.exists(path):
        raise UnknownName(f"no {kind} named {name!r} ({path})")
    modname = f"benchmarks_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(modname, path)
        sys.modules[modname] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[modname])
    return sys.modules[modname]


def metrics_of(bench: dict, section: str, cell: str) -> list:
    """[(entry, reader module)] of the section's metrics that this cell
    reports: every one without a "workloads" list, and those that list it."""
    return [(m, load_module(_SECTIONS[section], m["name"]))
            for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]
