"""Own device time inside and outside the program's collectives, chip by
chip: the ops whose scope path holds `lgbm.allreduce` (every `psum`,
`pmax` and `all_gather` of the data-parallel grower sits under it), read
through harness/scopes.py. A host consumer's score sync carries
`lgbm.score_sync` as well and is left out, so the iteration's collectives
are not mixed with a check's. None off the chip, and for a program or a
trace without the scope (one chip, or the parent of the PR that added a
reader)."""
from __future__ import annotations

from statistics import fmean

from . import scopes

SCOPE = "lgbm.allreduce"
NOT_THE_ITERATION = "lgbm.score_sync"


def in_allreduce(segs: list) -> bool:
    return SCOPE in segs and NOT_THE_ITERATION not in segs


def per_chip(ev):
    """[(allreduce ns, other ns, busy ns)] for each chip of the traced
    sub-window; None where no op carries the scope."""
    if ev.trace is None:
        return None
    found = scopes.for_evidence(ev)
    out, any_in = [], False
    for dev in ev.trace.devices:
        inside = sum(ns for name, ns in dev.self_ns.items()
                     if in_allreduce(scopes.segments(found.get(name))))
        any_in = any_in or inside > 0
        out.append((inside, sum(dev.self_ns.values()) - inside,
                    dev.busy_ns))
    return out if any_in else None


def allreduce_seconds(ev):
    """Mean over the chips of the own seconds under the scope."""
    chips = per_chip(ev)
    return None if chips is None else fmean(a for a, _, _ in chips) / 1e9


def allreduce_share(ev):
    """Those seconds over the busy seconds, means over the chips, in %."""
    chips = per_chip(ev)
    if chips is None:
        return None
    return 100.0 * fmean(a for a, _, _ in chips) / fmean(
        b for _, _, b in chips)


def imbalance_share(ev):
    """The slowest chip's own time OUTSIDE the collectives less the mean
    chip's, over the mean busy time, in %: what the others spend inside
    the collectives waiting for it."""
    chips = per_chip(ev)
    if chips is None:
        return None
    other = [o for _, o, _ in chips]
    return 100.0 * (max(other) - fmean(other)) / fmean(
        b for _, _, b in chips)
