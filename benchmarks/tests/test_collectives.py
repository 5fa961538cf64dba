"""The collectives' readers (harness/collectives.py and the three layer
metrics over it): on four chips worked by hand, on a program or a trace
without the scope (None, not 0), and on one iteration of the four-chip
cell recorded on the chip (recorded_v5e_dp4_scopes.json, cut by
tools/cut_scopes_dp.py)."""
import json
import os
import types

import pytest

from benchmarks.harness import collectives, loader, scopes, work_dp, xplane
from benchmarks.reference import gbdt_numpy as ref

HERE = os.path.dirname(os.path.abspath(__file__))
HIST = "jit(_entry_train_iter)/while/body/lgbm.hist/lgbm.allreduce/psum:"
COUNT = "jit(_entry_train_iter)/while/body/lgbm.allreduce/psum:"
SYNC = "jit(_sync_scores)/lgbm.score_sync/lgbm.allreduce/all_gather:"
PART = "jit(_entry_train_iter)/while/body/lgbm.partition/pallas_call:"
PEAKS = {"ici_bits_per_s": 1600e9}


def _tree(splits: int):
    return types.SimpleNamespace(internal_count=[0] * splits)


def _evidence(monkeypatch, chips: list, tf_op: dict, trees=(254,)):
    """An Evidence of one traced tree per entry of `trees` whose chips
    spent `chips` ([{op: ns}]) under the scopes `tf_op`."""
    monkeypatch.setattr(scopes, "for_evidence", lambda ev: tf_op)
    devices = [xplane.DeviceTrace(f"/device:TPU:{i}",
                                  float(sum(c.values())), dict(c), [])
               for i, c in enumerate(chips)]
    return types.SimpleNamespace(
        cell={"name": "x.cell"}, peaks=PEAKS,
        config={"shape": {"cols": 28}, "params": {"max_bin": 255}},
        trace=xplane.TraceSummary(0.0, 1.0, devices, []),
        artifacts={"traced_trees": (0, len(trees)),
                   "trees": [_tree(s) for s in trees]})


def _read(metric: str, ev):
    return loader.load_module("layer_metrics", metric).read(ev)


TF_OP = {"all-reduce.1": HIST, "all-reduce.2": COUNT, "all-gather.9": SYNC,
         "partition_pallas2.12": PART, "fusion.3": None}


def test_the_three_readers_on_four_chips_worked_by_hand(monkeypatch):
    # chip 2 is the slowest outside the collectives (its partition takes
    # 120), so the other three wait for it inside them; the score sync's
    # all-gather is a check's, not the iteration's
    chips = [{"partition_pallas2.12": 80e6, "all-reduce.1": 36e6,
              "all-reduce.2": 4e6, "fusion.3": 0.0},
             {"partition_pallas2.12": 100e6, "all-reduce.1": 18e6,
              "all-reduce.2": 2e6},
             {"partition_pallas2.12": 120e6, "all-reduce.1": 0.0,
              "all-gather.9": 0.0},
             {"partition_pallas2.12": 100e6, "all-reduce.1": 18e6,
              "all-reduce.2": 2e6}]
    ev = _evidence(monkeypatch, chips, TF_OP)
    assert collectives.per_chip(ev) == [
        (40e6, 80e6, 120e6), (20e6, 100e6, 120e6), (0.0, 120e6, 120e6),
        (20e6, 100e6, 120e6)]
    assert _read("allreduce_device_share", ev) == pytest.approx(
        100.0 * 20 / 120)
    assert _read("shard_imbalance_share", ev) == pytest.approx(
        100.0 * (120 - 100) / 120)
    # 255 histograms and counts of 57,348 B, 1.5 times over a chip's
    # links, at 200 GB/s, in the mean chip's 0.02 s
    moved = 255 * 57_348 * 1.5
    assert work_dp.allreduce_bytes([254], 28, 255, 4) == moved
    assert _read("allreduce_roofline", ev) == pytest.approx(
        100.0 * moved / 200e9 / 0.02)


def test_the_score_syncs_collective_is_not_the_iterations(monkeypatch):
    ev = _evidence(monkeypatch, [{"all-gather.9": 5e6, "fusion.3": 5e6}] * 4,
                   TF_OP)
    assert collectives.in_allreduce(scopes.segments(HIST))
    assert not collectives.in_allreduce(scopes.segments(SYNC))
    assert collectives.per_chip(ev) is None


@pytest.mark.parametrize("tf_op", [
    {"partition_pallas2.12": PART, "fusion.3": None},   # one chip: no psum
    {"partition_pallas2.12": None, "fusion.3": None},   # no scopes at all
], ids=["no_allreduce", "no_scopes"])
def test_without_the_scope_the_readers_say_nothing(monkeypatch, tf_op):
    ev = _evidence(monkeypatch,
                   [{"partition_pallas2.12": 9e6, "fusion.3": 1e6}], tf_op)
    for metric in ("allreduce_device_share", "allreduce_roofline",
                   "shard_imbalance_share"):
        assert _read(metric, ev) is None
    ev.trace = None                     # the CPU rehearsal
    assert _read("allreduce_device_share", ev) is None


def test_roofline_needs_the_traced_trees(monkeypatch):
    ev = _evidence(monkeypatch, [{"all-reduce.1": 1e6}] * 4, TF_OP)
    del ev.artifacts["traced_trees"]    # a run whose check did not get far
    assert _read("allreduce_roofline", ev) is None
    assert _read("allreduce_device_share", ev) == pytest.approx(100.0)


# ------------------------------------------------- recorded on the chip

with open(os.path.join(HERE, "recorded_v5e_dp4_scopes.json")) as _fh:
    RECORDED = json.load(_fh)["higgs255dp4.train"]


def _recorded_evidence(monkeypatch):
    """One iteration of the cell on four chips (41.9M x 28 rows, a full
    255-leaf tree), as the readers see a traced sub-window."""
    ev = _evidence(monkeypatch, [c["self_ns"] for c in RECORDED["chips"]],
                   RECORDED["scopes"])
    for dev, chip in zip(ev.trace.devices, RECORDED["chips"]):
        dev.busy_ns = chip["busy_ns"]
    return ev


def test_recorded_collectives_are_the_psums_and_all_of_them():
    assert len(RECORDED["chips"]) == 4
    under = {op for op, s in RECORDED["scopes"].items()
             if collectives.in_allreduce(scopes.segments(s))}
    named = {op for op in RECORDED["scopes"]
             if op.split(".")[0] in ("psum", "pmax", "all-reduce",
                                     "all-gather", "all-reduce-start",
                                     "all-reduce-done")}
    # every collective op carries the scope, and nothing else does
    assert named and named == under, (named, under)
    # the root's histogram and count, then the loop's: the count on its
    # own at loop level, the histogram inside lgbm.hist
    paths = sorted(tuple(scopes.stages(scopes.segments(
        RECORDED["scopes"][op]))) for op in under)
    assert paths == [("lgbm.allreduce",), ("lgbm.hist", "lgbm.allreduce"),
                     ("lgbm.root_hist", "lgbm.allreduce"),
                     ("lgbm.root_hist", "lgbm.allreduce")]


def test_recorded_iteration_through_the_three_readers(monkeypatch):
    ev = _recorded_evidence(monkeypatch)
    chips = collectives.per_chip(ev)
    for (inside, other, busy), rec in zip(chips, RECORDED["chips"]):
        # own times add up to the busy time: no op counted twice
        assert inside + other == pytest.approx(busy, rel=1e-6)
        assert busy == rec["busy_ns"]
    share = _read("allreduce_device_share", ev)
    wait = _read("shard_imbalance_share", ev)
    roof = _read("allreduce_roofline", ev)
    mean_in = sum(a for a, _, _ in chips) / 4
    mean_busy = sum(c["busy_ns"] for c in RECORDED["chips"]) / 4
    assert share == pytest.approx(100.0 * mean_in / mean_busy, rel=1e-9)
    # i.i.d. rows in contiguous shards: the chips are a tenth of a
    # millisecond apart, and the allreduces under 2 % of the iteration
    assert 1.0 < share < 2.5 and 0.0 <= wait < 0.2
    # 510 small allreduces a tree are latency: a few percent of 200 GB/s
    assert roof == pytest.approx(
        100.0 * 255 * 57_348 * 1.5 / 200e9 / (mean_in / 1e9), rel=1e-6)
    assert 1.0 < roof < 10.0


def test_recorded_partition_roofline_spreads_the_rows_over_the_chips(
        monkeypatch):
    """The inherited reader on four chips: bytes of the whole table's
    splits over the number of chips, against the mean chip's kernel time;
    under 100 % for any tree the cell can grow."""
    ev = _recorded_evidence(monkeypatch)
    ev.work = loader.load_module("harness", "work")
    ev.peaks = dict(PEAKS, hbm_bytes_per_s=819e9)
    # the most a 255-leaf tree can move: every level splits every row
    ev.artifacts["trees"] = [types.SimpleNamespace(
        internal_count=[41_943_040] * 8)]
    got = _read("partition_roofline", ev)
    assert got is not None and 5.0 < got < 100.0
