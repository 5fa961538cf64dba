"""What the run ran on: the device check, the table of peaks, peak memory."""
from __future__ import annotations

import json
import os


class WrongDevice(RuntimeError):
    """JAX found another platform or another number of chips than the
    cell asks for. The measuring command prints no result then."""


def describe() -> dict:
    import jax
    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def require(chips: int, platform: str = "tpu") -> dict:
    found = describe()
    if found["platform"] != platform or found["count"] != chips:
        raise WrongDevice(
            f"the cell needs {chips} {platform} chip(s); JAX found "
            f"{found['count']} x {found['platform']} ({found['kind']})")
    return found


def peaks_for(kind: str) -> dict:
    """The row of peaks.json for this device kind; an unknown kind is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as fh:
        table = json.load(fh)
    if kind not in table:
        raise WrongDevice(f"no peaks for device kind {kind!r} in peaks.json "
                          f"(it has {sorted(table)})")
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip; 0 where the backend keeps no
    such statistic (the CPU backend of a rehearsal)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())
