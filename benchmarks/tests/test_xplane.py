"""The reduction from trace events to busy time, idle gaps and own time,
on events worked by hand and on a small trace recorded on the chip."""
import json
import os

import pytest

from benchmarks.harness import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def by_hand():
    """One device, nanoseconds. A `while` of 100 spans two body ops and
    10 of its own time between them; an all-reduce follows after a gap."""
    ops = [("while.1", 0.0, 100.0), ("fusion.1", 10.0, 20.0),
           ("custom-call.2", 30.0, 40.0), ("all-reduce.3", 120.0, 30.0)]
    spans = [("traced", 0.0, 200.0), ("update", 90.0, 35.0),
             ("sync", 140.0, 60.0)]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_busy_is_the_union_and_own_time_leaves_children_out():
    s = xplane.reduce(by_hand())
    assert s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx(130e-9)        # not 100+20+40+30
    assert s.idle_share() == pytest.approx(70 / 200)
    own = s.devices[0].self_ns
    assert own == {"while.1": 40.0, "fusion.1": 20.0, "custom-call.2": 40.0,
                   "all-reduce.3": 30.0}
    assert sum(own.values()) == pytest.approx(s.devices[0].busy_ns)
    assert s.op_seconds("all-reduce") == [pytest.approx(30e-9)]
    assert s.op_share("all-reduce") == pytest.approx(30 / 130)
    assert s.top_ops(2) == [["while.1", pytest.approx(40e-9)],
                            ["custom-call.2", pytest.approx(40e-9)]]


def test_gaps_are_named_by_the_span_that_covers_them():
    s = xplane.reduce(by_hand())
    assert s.devices[0].gaps == [(100.0, 120.0), (150.0, 200.0)]
    assert s.top_gaps(5) == [["sync", pytest.approx(50e-9)],
                             ["update", pytest.approx(20e-9)]]


def test_window_clips_and_devices_average():
    raw = by_hand()
    raw["spans"][0] = ("traced", 50.0, 100.0)       # window [50, 150)
    raw["devices"]["/device:TPU:1"] = [("fusion.9", 60.0, 10.0)]
    s = xplane.reduce(raw)
    assert [d.busy_ns for d in s.devices] == [80.0, 10.0]
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle_share() == pytest.approx(0.9)     # the most idle chip
    assert s.devices[0].self_ns["while.1"] == pytest.approx(50.0 - 20.0)


def test_no_device_op_means_no_summary():
    assert xplane.reduce({"devices": {}, "spans": []}) is None
    assert xplane.reduce({"devices": {"/device:TPU:0": []},
                          "spans": by_hand()["spans"]}) is None


def test_without_the_window_span_the_ops_extent_is_the_window():
    raw = by_hand()
    raw["spans"] = []
    s = xplane.reduce(raw)
    assert (s.t0_ns, s.t1_ns) == (0.0, 150.0)
    assert s.top_gaps(5) == [[xplane.NO_SPAN, pytest.approx(20e-9)]]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_v5e_higgs_iter.json")) as fh:
        raw = json.load(fh)
    return raw, xplane.reduce(raw)


def test_recorded_trace_gives_the_known_sums(recorded):
    """5.3 ms of the chip's op line around the root partition of one
    iteration (the file says where it is from)."""
    _, s = recorded
    d = s.devices[0]
    assert s.window_s == pytest.approx(5.3e-3)
    assert d.busy_ns == 5295983.0
    assert s.idle_share() == pytest.approx(4017.0 / 5.3e6)
    assert len(d.gaps) == 104 and sum(e - b for b, e in d.gaps) == 4017.0
    assert d.self_ns["partition_pallas2.12"] == 4986882.0
    assert d.self_ns["histogram_planar_pallas.13"] == 259255.0
    # the loop's own time is what its body leaves, not its 416 ms extent
    assert d.self_ns["while.133"] == 6377.0
    assert s.top_ops(2) == [["partition_pallas2.12", 4986882.0 / 1e9],
                            ["histogram_planar_pallas.13", 259255.0 / 1e9]]
    assert s.top_gaps(1) == [["sync", pytest.approx(1318e-9)]]


def test_recorded_trace_agrees_with_counting_nanoseconds(recorded):
    """The same busy time by brute force: mark every nanosecond an event
    covers."""
    import numpy as np
    raw, s = recorded
    t0, t1 = int(s.t0_ns), int(s.t1_ns)
    covered = np.zeros(t1 - t0, bool)
    for _, start, dur in raw["devices"]["/device:TPU:0"]:
        covered[max(int(start) - t0, 0):max(int(start + dur) - t0, 0)] = True
    d = s.devices[0]
    assert int(covered.sum()) == d.busy_ns
    assert sum(d.self_ns.values()) == pytest.approx(d.busy_ns)


def test_op_name_is_the_hlo_instruction():
    long = ("%partition_pallas2.12 = (s32[16,21004288]{1,0:T(8,128)}, "
            "s32[1,1]{1,0:T(1,128)}) custom-call(s32[]{:T(128)S(6)} %add.1202)")
    assert xplane.op_name(long) == "partition_pallas2.12"
    assert xplane.op_name("jit__entry_train_iter(145)") == \
        "jit__entry_train_iter(145)"


def test_kernel_readers_on_the_recorded_trace(recorded):
    """The three kernel readers against the recorded trace and a one-tree
    model: shares by the kernels' names, and the partition's roofline from
    the rows the tree's splits had to move."""
    import types

    from benchmarks.harness import loader, work
    from benchmarks.reference import gbdt_numpy as ref
    _, s = recorded
    tree = ref.Tree(*[None] * 6, internal_count=[20_971_520, 9_000_000],
                    split_gain=None)
    ev = types.SimpleNamespace(
        trace=s, work=work, artifacts={"traced_trees": (0, 1),
                                       "trees": [tree]},
        config={"params": {"max_bin": 255}, "shape": {"cols": 28}},
        peaks={"hbm_bytes_per_s": 819e9})
    read = lambda name: loader.load_module("layer_metrics", name).read(ev)
    assert read("partition_device_share") == pytest.approx(
        100 * 4986882 / 5295983)
    assert read("hist_device_share") == pytest.approx(100 * 259255 / 5295983)
    moved = 2 * 4 * 16 * (20_971_520 + 9_000_000)
    assert read("partition_roofline") == pytest.approx(
        100 * moved / 819e9 / 4986882e-9)
    ev.trace = None
    assert read("partition_roofline") is None
    assert read("hist_device_share") is None
