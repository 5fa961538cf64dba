"""The result line: the last line of standard output, one JSON object."""
from __future__ import annotations

import json


def metric_values(readers: list, ev) -> dict:
    """{name: {"value", "unit"}} of what each reader found; a reader that
    finds nothing to read returns None and its metric is left out."""
    out = {}
    for entry, reader in readers:
        value = reader.read(ev)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
