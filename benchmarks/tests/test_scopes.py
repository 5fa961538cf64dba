"""The readers of what the program says about itself (harness/scopes.py):
the wire-format walk against a small XSpace encoded here, the buckets and
the three shares against one iteration per cell recorded on the chip
(recorded_v5e_scopes.json, cut by tools/cut_scopes.py), and what a
program without scopes, spans or a stage table gets: None."""
import json
import os
import types

import pytest

from benchmarks.harness import loader, runner, scopes, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------- a protobuf encoder

def varint(v: int) -> bytes:
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def field(num: int, value) -> bytes:
    """A varint field for an int, a length-delimited one for bytes/str."""
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def map_entry(num: int, key: int, message: bytes) -> bytes:
    return field(num, field(1, key) + field(2, message))


def stat_metadata(sid: int, name: str) -> bytes:
    return map_entry(5, sid, field(1, sid) + field(2, name))


def event_metadata(eid: int, name: str, *stats: bytes) -> bytes:
    return map_entry(4, eid, field(1, eid) + field(2, name)
                     + b"".join(field(5, s) for s in stats))


HIST = "jit(f)/while/body/lgbm.hist/jit(histogram_planar_pallas)/pallas_call:"
PART = "jit(f)/while/body/lgbm.partition/pallas_call:"


def small_xspace() -> bytes:
    """Two planes. On the device plane: a scope as a str_value, one as a
    ref_value into stat_metadata, an op with other stats only, an op with
    none, and one instruction name that two programs use differently.
    A fixed64 and a fixed32 field lie in the way."""
    device = (field(1, 7) + field(2, "/device:TPU:0")
              + stat_metadata(1, "flops") + stat_metadata(2, "tf_op")
              + stat_metadata(9, HIST)
              + event_metadata(
                  1, "%partition_pallas2.12 = (s32[16,128]{1,0}) custom-call()",
                  field(1, 1) + field(3, 77), field(1, 2) + field(5, PART))
              + event_metadata(
                  2, "%histogram_planar_pallas.14 = f32[2] custom-call()",
                  field(1, 2) + field(7, 9))
              + event_metadata(3, "%fusion.80 = f32[] fusion()",
                               field(1, 1) + field(3, 5))
              + event_metadata(4, "%while.133 = () while()")
              + event_metadata(5, "%copy.1 = f32[] copy()",
                               field(1, 2) + field(5, "jit(f)/while:"))
              + event_metadata(6, "%copy.1 = f32[] copy()",
                               field(1, 2) + field(5, "jit(g)/lgbm.grad/mul:"))
              + varint(99 << 3 | 1) + b"\0" * 8 + varint(98 << 3 | 5) + b"\0" * 4)
    host = (field(2, "/host:CPU") + stat_metadata(1, "tf_op")
            + event_metadata(1, "lgbm:update"))
    return field(1, device) + field(1, host) + field(3, "an error string")


def test_walk_reads_both_stat_forms_two_planes_and_a_collision(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(small_xspace())
    said = []
    got = scopes.op_scopes(str(path), say=said.append)
    assert got == {
        "/device:TPU:0": {"partition_pallas2.12": PART,
                          "histogram_planar_pallas.14": HIST,
                          "fusion.80": None, "while.133": None,
                          "copy.1": "jit(f)/while:"},
        "/host:CPU": {"lgbm:update": None}}
    assert len(said) == 1 and "copy.1" in said[0] \
        and "/device:TPU:0" in said[0]


def test_walk_refuses_what_it_does_not_know():
    with pytest.raises(ValueError, match="wire type 3"):
        list(scopes.fields(varint(1 << 3 | 3)))


def test_varints_of_any_length():
    for v in (0, 1, 127, 128, 300, 2**31 + 5, 2**63 - 1):
        assert list(scopes.fields(field(4, v))) == [(4, 0, v)]


# ---------------------------------------------- predicates and buckets

@pytest.mark.parametrize("name,scope,bucket,flags", [
    ("partition_pallas2.12", PART, "lgbm.partition", ""),
    ("histogram_planar_pallas.14", HIST, "lgbm.hist", ""),
    ("histogram_planar_pallas.13", "jit(f)/lgbm.root_hist/pallas_call:",
     "lgbm.root_hist", ""),
    ("fusion.168", "jit(f)/while/body/lgbm.split_scan/vmap(gather):",
     "lgbm.split_scan/loop", "s"),
    ("fusion.9", "jit(f)/lgbm.split_scan/reduce_max:",
     "lgbm.split_scan/root", "s"),
    ("copy.57", "jit(f)/while:", "loop_overhead", "l"),
    ("while.133", None, "loop_overhead", "l"),
    ("fusion.65", "jit(f)/while/body/lgbm.bookkeeping/scatter:",
     "loop_overhead", "l"),
    ("fusion.72", "jit(f)/while/body/lgbm.pool/scatter:", "loop_overhead",
     "l"),
    ("all-reduce.3", "jit(f)/while/body/lgbm.hist/lgbm.allreduce/psum:",
     "lgbm.hist", ""),
    ("all-reduce.4", "jit(f)/while/body/lgbm.allreduce/psum:",
     "loop_overhead", "l"),
    ("fusion.1", "jit(f)/lgbm.grad/mul:", "lgbm.grad", ""),
    ("fusion.2", "jit(f)/lgbm.renew/while/body/add:", "loop_overhead", "l"),
    ("fusion.3", "jit(f)/lgbm.score_update/add:", "lgbm.score_update", ""),
    ("reshape.2864", "reduce_window_sum:", "unscoped", "u"),
    ("copy.28", None, "unscoped", "u"),
    # a prefix of a scope's name is not the scope
    ("fusion.4", "jit(f)/while/body/lgbm.histogram_x/add:", "loop_overhead",
     "l"),
])
def test_every_op_has_one_bucket_and_the_predicates_agree(name, scope,
                                                          bucket, flags):
    segs = scopes.segments(scope)
    assert scopes.bucket(name, segs) == bucket
    assert scopes.is_split_scan(name, segs) == ("s" in flags)
    assert scopes.is_loop_overhead(name, segs) == ("l" in flags)
    assert scopes.is_unscoped(name, segs) == ("u" in flags)


# -------------------------------------------------- through the readers

def _evidence(tmp_path, monkeypatch, cell, xspace: bytes, self_ns: dict):
    """An Evidence whose trace directory holds `xspace` and whose one
    device spent `self_ns`."""
    monkeypatch.setattr(runner, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(scopes, "_CACHE", {})
    where = tmp_path / cell / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(xspace)
    dev = xplane.DeviceTrace("/device:TPU:0", float(sum(self_ns.values())),
                             dict(self_ns), [])
    return types.SimpleNamespace(
        cell={"name": cell}, traced={"units": {"iters": 1}},
        trace=xplane.TraceSummary(0.0, 1.0, [dev], []))


def _read(metric: str, ev):
    return loader.load_module("layer_metrics", metric).read(ev)


def test_share_readers_on_the_small_trace(tmp_path, monkeypatch, capsys):
    ev = _evidence(tmp_path, monkeypatch, "a.cell", small_xspace(),
                   {"partition_pallas2.12": 50.0, "while.133": 5.0,
                    "histogram_planar_pallas.14": 30.0, "copy.1": 10.0,
                    "fusion.80": 5.0})
    assert _read("loop_overhead_device_share", ev) == pytest.approx(15.0)
    assert _read("unscoped_device_share", ev) == pytest.approx(5.0)
    assert _read("split_scan_device_share", ev) == 0.0
    out = capsys.readouterr().out.splitlines()
    # read once, said once: the collision, then the time by scope
    assert [ln.split(":")[0] for ln in out] == [
        "scopes", "device time by scope, % of busy"]
    assert "lgbm.partition 50.00, lgbm.hist 30.00, loop_overhead 15.00, " \
        "unscoped 5.00" in out[1]
    ev.trace = None                     # the CPU rehearsal
    assert _read("loop_overhead_device_share", ev) is None


def test_a_program_without_scopes_reports_no_share(tmp_path, monkeypatch,
                                                   capsys):
    """The parent of the PR that added the scopes, or an executable that
    a compile cache kept from it: the metric is left out, not 0 or 100."""
    bare = field(1, field(2, "/device:TPU:0") + stat_metadata(2, "tf_op")
                 + event_metadata(1, "%fusion.1 = f32[] fusion()",
                                  field(1, 2) + field(5, "jit(f)/mul:"))
                 + event_metadata(2, "%while.133 = () while()"))
    ev = _evidence(tmp_path, monkeypatch, "b.cell", bare,
                   {"fusion.1": 5.0, "while.133": 5.0})
    for metric in ("split_scan_device_share", "loop_overhead_device_share",
                   "unscoped_device_share"):
        assert _read(metric, ev) is None
    assert capsys.readouterr().out.count("no lgbm.* scope") == 1


def test_stage_readers_read_the_programs_table(monkeypatch, capsys):
    from lightgbm_tpu import obs
    monkeypatch.setattr(scopes, "_CACHE", {})
    monkeypatch.setattr(obs, "stage_seconds", lambda: {
        "construct/find_bins": (58.0, 1), "construct/bin_rows": (34.8, 1)})
    assert _read("find_bins_s", None) == 58.0
    assert _read("bin_rows_s", None) == 34.8
    assert _read("pack_codes_s", None) is None      # never entered
    assert capsys.readouterr().out.count("program set-up stages:") == 1
    monkeypatch.delattr(obs, "stage_seconds")       # a program without it
    assert _read("find_bins_s", None) is None


def test_span_reader_without_a_trace_or_spans(tmp_path, monkeypatch):
    ev = _evidence(tmp_path, monkeypatch, "c.cell", small_xspace(), {})
    # the small trace's host plane has no line and no lgbm: event
    assert _read("jit_call_ms_per_iter", ev) is None
    monkeypatch.setattr(runner, "TRACE_DIR", str(tmp_path / "nowhere"))
    assert _read("jit_call_ms_per_iter", ev) is None
    ev.traced = None                    # a run without --trace
    assert _read("jit_call_ms_per_iter", ev) is None


def test_span_reader_on_a_real_profiler_session(tmp_path, monkeypatch):
    """Two `lgbm:` spans inside the benchmark's window and one outside
    it, through the profiler of this backend."""
    import jax
    from benchmarks.harness.clock import SPAN_PREFIX
    monkeypatch.setattr(runner, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(scopes, "_CACHE", {})
    note = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(str(tmp_path / "d.cell"))
    try:
        with note("lgbm:fused/train_iter"):
            pass
        with note(SPAN_PREFIX + xplane.WINDOW_SPAN):
            for _ in range(2):
                with note("lgbm:update"), note("lgbm:fused/train_iter"):
                    sum(range(20_000))
    finally:
        jax.profiler.stop_trace()
    events = scopes.span_events(xplane.find_xplane(str(tmp_path / "d.cell")))
    assert {k: len(v) for k, v in events.items()} == {
        "update": 2, "fused/train_iter": 2}
    ev = types.SimpleNamespace(cell={"name": "d.cell"},
                               traced={"units": {"iters": 2}})
    per_iter = _read("jit_call_ms_per_iter", ev)
    assert per_iter == pytest.approx(
        sum(d for _, d in events["fused/train_iter"]) / 2e6)
    assert 0.0 < per_iter <= sum(d for _, d in events["update"]) / 2e6


# ------------------------------------------------- recorded on the chip

with open(os.path.join(HERE, "recorded_v5e_scopes.json")) as _fh:
    RECORDED = json.load(_fh)

BUCKETS = {"lgbm.partition", "lgbm.hist", "lgbm.split_scan/loop",
           "lgbm.split_scan/root", "loop_overhead", "unscoped",
           # outside the loop, an op's outermost scope is its bucket
           "lgbm.root_hist", "lgbm.grad", "lgbm.renew", "lgbm.score_update",
           "lgbm.bookkeeping"}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_iteration_buckets_count_every_op_once(cell):
    rec = RECORDED[cell]
    by, seen = {}, 0
    for op, ns in rec["self_ns"].items():
        b = scopes.bucket(op, scopes.segments(rec["scopes"][op]))
        by[b] = by.get(b, 0.0) + ns
        seen += 1
    assert seen == len(rec["self_ns"]) and set(by) <= BUCKETS, set(by)
    assert 100.0 * sum(by.values()) / rec["busy_ns"] == pytest.approx(
        100.0, abs=0.1)
    # the scope and the kernel's name point at the same time
    by_name = sum(ns for op, ns in rec["self_ns"].items()
                  if op.startswith("partition_pallas"))
    assert 100.0 * abs(by["lgbm.partition"] - by_name) / rec["busy_ns"] < 0.5
    assert 100.0 * by.get("unscoped", 0.0) / rec["busy_ns"] < 1.0


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_iteration_predicates_are_unions_of_buckets(cell):
    rec = RECORDED[cell]

    def total(keep):
        return sum(ns for op, ns in rec["self_ns"].items()
                   if keep(op, scopes.segments(rec["scopes"][op])))

    def of(*buckets):
        return total(lambda op, segs: scopes.bucket(op, segs) in buckets)
    assert total(scopes.is_split_scan) == of("lgbm.split_scan/loop",
                                             "lgbm.split_scan/root")
    assert total(scopes.is_loop_overhead) == of("loop_overhead")
    assert total(scopes.is_unscoped) == of("unscoped")


def test_recorded_epsilon_pool_copies_are_loop_level():
    """The two whole-pool copies of every step carry the `while` op's own
    scope and no stage: XLA's loop-carry copies, owned by no line of the
    body, inside loop_overhead."""
    rec = RECORDED["epsilon63.train"]
    copies = sorted(((ns, op) for op, ns in rec["self_ns"].items()
                     if op.startswith("copy.")), reverse=True)[:2]
    for ns, op in copies:
        segs = scopes.segments(rec["scopes"][op])
        assert 100.0 * ns / rec["busy_ns"] > 10.0
        assert "while" in segs and not scopes.stages(segs)
        assert scopes.is_loop_overhead(op, segs)
