"""harness/scope_events.py and the DART readers over it, on a small XSpace
encoded here: one instruction name that two programs give two scopes is
booked to each event's own scope, where harness/scopes.py keeps one; a
loop's own time is what its children leave; an op outside the traced
window is left out. The kernel readers read the replay kernel by name."""
import types

import pytest

from benchmarks.harness import loader, runner, scope_events, scopes, xplane


def varint(v: int) -> bytes:
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def map_entry(num: int, key: int, message: bytes) -> bytes:
    return field(num, field(1, key) + field(2, message))


def event_metadata(eid: int, name: str, scope: str = "") -> bytes:
    stat = field(5, field(1, 2) + field(5, scope)) if scope else b""
    return map_entry(4, eid, field(1, eid) + field(2, name) + stat)


def line(name: str, t0_ns: int, *events) -> bytes:
    """events: (metadata id, offset ns, duration ns)."""
    return field(3, field(2, name) + field(3, t0_ns) + b"".join(
        field(4, field(1, m) + field(2, off * 1000) + field(3, dur * 1000))
        for m, off, dur in events))


ADD = "%select_reduce_fusion = f32[8] fusion()"
DEVICE = (field(2, "/device:TPU:0") + map_entry(5, 2, field(1, 2)
                                                 + field(2, "tf_op"))
          + event_metadata(1, ADD, "jit(add)/lgbm.score_update/reduce_sum:")
          + event_metadata(2, ADD, "jit(put)/lgbm.bookkeeping/gather:")
          + event_metadata(3, "%replay_forest_pallas.1 = f32[8] custom-call()",
                           "jit(r)/lgbm.dart_replay/pallas_call:")
          + event_metadata(4, "%while.1 = () while()", "jit(g)/while:")
          + line(xplane.OP_LINE, 1000, (1, 0, 3000), (4, 10_000, 10_000),
                 (3, 12_000, 4000), (2, 30_000, 1000), (1, 100_000, 5000)))
HOST = (field(2, "/host:CPU") + event_metadata(1, "bench:traced")
        + line("python", 1000, (1, 0, 50_000)))
SPACE = field(1, DEVICE) + field(1, HOST)


def test_each_event_is_booked_to_its_own_programs_scope(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(SPACE)
    raw = scope_events.load(str(path))
    assert raw["window"] == (1000.0, 51_000.0)
    total, busy, apart = scope_events.by_scope(raw)
    assert total == {"lgbm.score_update": 3000.0, "unscoped": 6000.0,
                     "lgbm.dart_replay": 4000.0, "lgbm.bookkeeping": 1000.0}
    assert busy == 14_000.0
    assert apart == ["select_reduce_fusion"]
    # the join by name gives the shared name one scope for both programs
    got = scopes.op_scopes(str(path))["/device:TPU:0"]
    assert got["select_reduce_fusion"].count("lgbm.") == 1


def test_without_a_window_span_the_ops_extent_is_the_window(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, DEVICE))
    total, busy, _ = scope_events.by_scope(scope_events.load(str(path)))
    assert total["lgbm.score_update"] == 8000.0 and busy == 19_000.0


def _evidence(tmp_path, monkeypatch, space: bytes, self_ns: dict):
    monkeypatch.setattr(runner, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(scope_events, "_CACHE", {})
    where = tmp_path / "c.dart" / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(space)
    dev = xplane.DeviceTrace("/device:TPU:0", float(sum(self_ns.values())),
                             dict(self_ns), [])
    return types.SimpleNamespace(
        cell={"name": "c.dart"}, traced={"units": {"iters": 2}},
        trace=xplane.TraceSummary(0.0, 1.0, [dev], []),
        artifacts={"traced_drops": [(0, 2), (), (1,)],
                   "tree_splits": [254, 10, 3]},
        config={"shape": {"rows": 1000, "cols": 28},
                "params": {"max_bin": 255}},
        peaks={"hbm_bytes_per_s": 8.0e11})


def _read(metric: str, ev):
    return loader.load_module("layer_metrics", metric).read(ev)


def test_dart_readers(tmp_path, monkeypatch, capsys):
    ev = _evidence(tmp_path, monkeypatch, SPACE,
                   {"replay_forest_pallas.1": 2.0e6, "while.1": 6.0e6,
                    "select_reduce_fusion": 4.0e6})
    assert _read("dart_replay_device_share", ev) == pytest.approx(
        100.0 * 4 / 14)
    out = capsys.readouterr().out
    assert out.count("booked by event") == 1
    assert "1 instruction names ran under two scopes" in out
    # 2 ms over 1000 rows x (254 + 3 + 10) splits
    assert _read("dart_replay_ns_per_lane_split", ev) == pytest.approx(
        2.0e6 / (1000 * 267))
    # two dropping rounds of 7 planes x 4 B + 4 B a row, at 800 GB/s
    assert _read("dart_replay_roofline", ev) == pytest.approx(
        100.0 * 2 * 1000 * 32 / 8.0e11 / 2.0e-3)
    ev.trace.devices[0].self_ns.pop("replay_forest_pallas.1")
    assert _read("dart_replay_ns_per_lane_split", ev) is None
    assert _read("dart_replay_roofline", ev) is None
    ev.trace = None                     # the CPU rehearsal
    for metric in ("dart_replay_device_share", "dart_replay_ns_per_lane_split",
                   "dart_replay_roofline"):
        assert _read(metric, ev) is None


def test_a_program_without_the_scope_reports_no_share(tmp_path, monkeypatch):
    bare = field(1, field(2, "/device:TPU:0") + event_metadata(1, ADD)
                 + line(xplane.OP_LINE, 0, (1, 0, 10)))
    ev = _evidence(tmp_path, monkeypatch, bare, {"select_reduce_fusion": 10})
    assert _read("dart_replay_device_share", ev) is None
