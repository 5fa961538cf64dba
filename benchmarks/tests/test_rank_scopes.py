"""The ranking cell's scope readers on ONE iteration of `msltr137.train`
recorded on the chip (recorded_v5e_rank_scopes.json, cut by
tools/cut_scopes_rank.py from the builder's traced run, PR 34): the
gradient program, the unsampled grow program and the score add, each op
with its own nanoseconds and its `tf_op`."""
import json
import os
import types

import pytest

from benchmarks.harness import loader, scope_shares, scopes, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "msltr137.train"

with open(os.path.join(HERE, "recorded_v5e_rank_scopes.json")) as _fh:
    REC = json.load(_fh)[CELL]


def _read(metric: str, ev):
    return loader.load_module("layer_metrics", metric).read(ev)


@pytest.fixture
def ev(monkeypatch):
    """An Evidence whose one device spent the recorded iteration."""
    monkeypatch.setattr(scopes, "for_evidence", lambda ev: REC["scopes"])
    monkeypatch.setattr(scope_shares, "_SAID", set())
    dev = xplane.DeviceTrace("/device:TPU:0", REC["busy_ns"],
                             dict(REC["self_ns"]), [])
    return types.SimpleNamespace(
        cell={"name": CELL}, traced={"units": {"iters": 1}},
        config={"shape": {"rows": 6_810_888}},
        peaks={"hbm_bytes_per_s": 819e9},
        artifacts={"rank_pairs": 399_067_557, "rank_queries": 56_757},
        trace=xplane.TraceSummary(0.0, 1.0, [dev], []))


def _under(scope: str) -> float:
    """Own ns of the recorded ops whose outermost lgbm.* segment is it."""
    total = 0.0
    for op, ns in REC["self_ns"].items():
        own = scopes.stages(scopes.segments(REC["scopes"][op]))
        if own and own[0] == scope:
            total += ns
    return total


def test_the_gradient_scope_holds_its_nested_scopes(ev):
    """Sorts, pair blocks and the way back are booked to the one
    outermost scope; nothing of the gradient program is left outside."""
    grad = _under("lgbm.rank_grad")
    nested = sum(ns for op, ns in REC["self_ns"].items()
                 if any(s in scopes.segments(REC["scopes"][op]) for s in
                        ("lgbm.rank_sort", "lgbm.rank_pairs",
                         "lgbm.rank_to_rows")))
    assert 0 < nested <= grad
    assert nested > 0.95 * grad
    assert _read("rank_grad_device_share", ev) == pytest.approx(
        100.0 * grad / REC["busy_ns"])
    assert 5.0 < _read("rank_grad_device_share", ev) < 80.0


def test_per_pair_and_roofline_read_the_same_seconds(ev):
    spent = _under("lgbm.rank_grad") / 1e9
    assert _read("rank_grad_ns_per_pair", ev) == pytest.approx(
        1e9 * spent / 399_067_557)
    roofline = _read("rank_grad_roofline", ev)
    least = (16 * 6_810_888 + 8 * 56_757) / 819e9
    assert roofline == pytest.approx(100.0 * least / spent)
    assert 0.0 < roofline < 5.0         # the pair work is compute


def test_the_unsampled_grow_programs_scopes(ev):
    assert _read("build_state_device_share", ev) == pytest.approx(
        100.0 * _under("lgbm.build_state") / REC["busy_ns"])
    assert _read("row_traverse_device_share", ev) == pytest.approx(
        100.0 * _under("lgbm.row_traverse") / REC["busy_ns"])
    assert _read("score_update_device_share", ev) > 0.0
    # every op's time is booked once, and little of it to no scope
    by = {}
    for op, ns in REC["self_ns"].items():
        own = scopes.stages(scopes.segments(REC["scopes"][op]))
        by[own[0] if own else "unscoped"] = \
            by.get(own[0] if own else "unscoped", 0.0) + ns
    assert sum(by.values()) == pytest.approx(REC["busy_ns"], rel=1e-3)
    assert by.get("unscoped", 0.0) < 0.05 * REC["busy_ns"]


def test_a_program_without_the_scopes_reports_nothing(ev, monkeypatch):
    """The parent of PR 34 (no `lgbm.rank_grad`, no `lgbm.build_state`),
    a run without a trace, or one without the pair count: the metric is
    left out, and nothing raises."""
    bare = {op: (None if s is None else s.replace("lgbm.rank_grad/", "")
                 .replace("lgbm.build_state/", ""))
            for op, s in REC["scopes"].items()}
    monkeypatch.setattr(scopes, "for_evidence", lambda ev: bare)
    for metric in ("rank_grad_device_share", "rank_grad_ns_per_pair",
                   "rank_grad_roofline", "build_state_device_share"):
        assert _read(metric, ev) is None
    monkeypatch.setattr(scopes, "for_evidence", lambda ev: REC["scopes"])
    ev.artifacts = {}
    assert _read("rank_grad_ns_per_pair", ev) is None
    assert _read("rank_grad_roofline", ev) is None
    ev.trace = None
    assert _read("rank_grad_device_share", ev) is None
