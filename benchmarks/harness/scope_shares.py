"""Own device time of the ops under one `lgbm.*` scope, wherever the scope
sits in an op's path. harness/scopes.py buckets an op by the grower's loop
first; the per-tree tier has scopes outside that loop that hold loops of
their own (`lgbm.row_traverse` replays the splits in one), so its readers
ask by scope alone."""
from __future__ import annotations

from . import scopes

_SAID = set()


def _by_scope(ev):
    """({outermost lgbm.* scope or "unscoped": ns}, busy ns) summed over
    the devices; None off the chip or for a trace without scopes."""
    if ev.trace is None:
        return None
    found = scopes.for_evidence(ev)
    if not any(scopes.SCOPE_PREFIX in (s or "") for s in found.values()):
        return None
    total, busy = {}, 0.0
    for dev in ev.trace.devices:
        busy += dev.busy_ns
        for name, ns in dev.self_ns.items():
            own = scopes.stages(scopes.segments(found.get(name)))
            key = own[0] if own else "unscoped"
            total[key] = total.get(key, 0.0) + ns
    if id(ev) not in _SAID:
        _SAID.add(id(ev))
        print("device time by outermost scope, % of busy: " + ", ".join(
            f"{k} {100.0 * v / busy:.2f}" for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])), flush=True)
    return total, busy


def seconds(ev, scope: str):
    """Own seconds of the ops whose outermost program scope is `scope`,
    mean over the devices; None where no op carries it."""
    got = _by_scope(ev)
    if got is None or scope not in got[0]:
        return None
    return got[0][scope] / 1e9 / len(ev.trace.devices)


def share(ev, scope: str):
    """Those ops' own time over the busy time, in %."""
    got = _by_scope(ev)
    if got is None or scope not in got[0]:
        return None
    return 100.0 * got[0][scope] / got[1]
