"""The trace reduction on a hand-built ``.xplane.pb`` with known answers.

The file is written here with the protobuf wire format of the profiler's
XSpace: one TPU plane with two program executions on its "XLA Modules"
line and leaf ops (plus a ``while`` container) on its "XLA Ops" line, their
``tf_op`` path in the event *metadata* as on the chip; one host plane with
two chunk-boundary annotations and a dispatch span.  Every number the
per-layer readers use is then known exactly.  ``record_trace.py`` records
the same layout on a chip.
"""
import math

import pytest
from _paths import ROOT

from chipbench import entries, metrics, xplane
from chipbench.trace import CHUNK_SPAN, Summary


def _varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(data)) + data


def _plane(name: str, lines: list, stats: dict[str, str]) -> bytes:
    """lines: [(line name, [(event name, start ns, end ns, stats)])]."""
    stat_ids = {k: i + 1 for i, k in enumerate(stats)}
    meta, out = {}, b""
    for lname, events in lines:
        evs = b""
        for ename, start, end, est in events:
            key = (ename, tuple(sorted(est.items())))
            if key not in meta:
                meta[key] = len(meta) + 1
            evs += _field(4, _field(1, meta[key]) + _field(2, start * 1000) + _field(3, (end - start) * 1000))
        out += _field(3, _field(2, lname) + _field(3, 0) + evs)
    for (ename, est), mid in meta.items():
        body = _field(1, mid) + _field(2, ename)
        for k, v in est:
            body += _field(5, _field(1, stat_ids[k]) + _field(5, v))
        out += _field(4, _field(1, mid) + _field(2, body))
    for k, i in stat_ids.items():
        out += _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, k)))
    return _field(2, name) + out


def _op(name, start, end, scope, category="loop fusion"):
    return (f"%{name} = f32[8] {name}()", start, end, {"tf_op": scope, "hlo_category": category})


LOCAL = "jit(chunk_inner)/while/body/dfl_local/vmap()/dot_general:"
MIX = "jit(chunk_inner)/while/body/dfl_mix/scatter-add:"
EVAL = "jit(chunk_inner)/while/body/cond/dfl_eval/reduce:"


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    device = _plane("/device:TPU:0", [
        ("XLA Modules", [("jit_chunk_inner(1)", 1000, 2000, {}), ("jit_chunk_inner(1)", 2500, 4000, {})]),
        ("XLA Ops", [
            _op("while.1", 1000, 2000, "jit(chunk_inner)/while", "while"),
            _op("fusion.1", 1000, 1400, LOCAL), _op("fusion.2", 1400, 1900, MIX),
            _op("fusion.3", 1900, 2000, EVAL),
            _op("fusion.1", 2500, 3100, LOCAL), _op("fusion.2", 3100, 4000, MIX),
        ]),
    ], {"tf_op": "", "hlo_category": ""})
    host = _plane("/host:CPU", [
        ("main", [(CHUNK_SPAN, 1200, 1210, {}), (CHUNK_SPAN, 2100, 2110, {}),
                  ("PjitFunction(chunk_inner)", 2150, 2450, {}), (CHUNK_SPAN, 4100, 4110, {})]),
    ], {})
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    return str(path)


@pytest.fixture(scope="module")
def summary(trace_file):
    return Summary.from_file(trace_file, 1)


def test_reader_decodes_events_and_metadata(trace_file):
    planes = {p.name: p for p in xplane.read(trace_file)}
    ops = next(ln for ln in planes["/device:TPU:0"].lines if ln.name == "XLA Ops").events
    assert len(ops) == 6
    assert ops[1].start_ns == 1000 and ops[1].end_ns == 1400
    assert ops[1].meta_stats["tf_op"] == LOCAL


def test_window_busy_and_gaps(summary):
    assert summary.marks == [1200, 2100, 4100]
    assert (summary.lo, summary.hi) == (1200, 4000)
    # programs run over [1200, 2000] and [2500, 4000] of the window
    assert summary.busy_s == pytest.approx(2300e-9)
    assert summary.window_s == pytest.approx(2800e-9)
    assert summary.gaps(0) == [(2000, 2500)]
    # the mark after the last chunk lies past the window's end
    assert summary.boundary_gaps_s() == pytest.approx([0.0, 500e-9])


def test_scopes_count_leaf_ops_in_the_window(summary):
    assert summary.scope_s("dfl_local") == pytest.approx((200 + 600) * 1e-9)
    assert summary.scope_s("dfl_mix") == pytest.approx((500 + 900) * 1e-9)
    assert summary.scope_s("dfl_eval") == pytest.approx(100e-9)
    assert summary.scope_s("halo_exchange") is None


def test_breakdown_labels_gaps_by_host_span(summary):
    b = summary.breakdown()
    assert b["device_ops"][0] == ["%fusion.2", pytest.approx(1400e-9)]
    assert b["idle_gaps"] == [[f"{CHUNK_SPAN} / PjitFunction(chunk_inner)", pytest.approx(500e-9)]]


def test_readers(summary):
    import json

    cfg = json.loads((ROOT / "chipbench" / "configs" / "paper_mlp.json").read_text())
    tr = {"graph": {"family": "ring", "n": 16}, "local_batches": 8, "batch_size": 16}
    win = entries.Window(rounds=2, node_rounds=32, wall_s=1.0, t0=0.0, t1=0.0, calls=1,
                         notes={"eval_rounds": 1})
    timed = entries.Window(rounds=4, node_rounds=64, wall_s=2.0, t0=0.0, t1=0.0, calls=1)
    ctx = metrics.Context(cfg, tr, win, timed, summary, peak_bytes=2**31,
                          device_kind="TPU v5 lite", chips=1)
    names = ["boundary_gap_ms", "local_ms_per_round", "mix_ms_per_round", "mix_roofline",
             "eval_ms_per_eval", "device_idle", "peak_hbm_gib", "train_mfu"]
    got = metrics.read_all([{"name": n} for n in names], ctx)
    assert set(got) == set(names)
    assert got["boundary_gap_ms"] == pytest.approx(250e-6)
    assert got["local_ms_per_round"] == pytest.approx(400e-6)
    assert got["mix_ms_per_round"] == pytest.approx(700e-6)
    assert got["eval_ms_per_eval"] == pytest.approx(100e-6)
    assert got["device_idle"] == pytest.approx(100 * 500 / 2800)
    assert got["peak_hbm_gib"] == 2.0
    # the untraced window: 64 node-rounds × 3 × 1,133,056 × 128 FLOP in 2 s
    # on one 197 TFLOP/s chip
    assert got["train_mfu"] == pytest.approx(100 * 64 * 3 * 1_133_056 * 128 / 2 / 197e12)
    # least mix time of a 16-node ring at d = 567,434 over the measured 700 ns
    least = max(2 * 16 * 567_434 * 4 / 819e9, 2 * (32 + 16) * 567_434 / 197e12)
    assert got["mix_roofline"] == pytest.approx(100 * least / 700e-9)
    assert all(math.isfinite(v) for v in got.values())


def test_readers_with_nothing_to_read_leave_their_metric_out():
    """A trace with no op under a layer's scope yields no number for it,
    never 0."""
    import json

    cfg = json.loads((ROOT / "chipbench" / "configs" / "paper_mlp.json").read_text())
    tr = {"graph": {"family": "ring", "n": 16}, "local_batches": 8, "batch_size": 16}
    empty = Summary(ops=[[]], modules=[[(0, 10)]], host=[], marks=[0], lo=0, hi=10)
    win = entries.Window(rounds=2, node_rounds=32, wall_s=1.0, t0=0.0, t1=0.0, calls=1,
                         notes={"eval_rounds": 1})
    ctx = metrics.Context(cfg, tr, win, win, empty, peak_bytes=2**31,
                          device_kind="TPU v5 lite", chips=1)
    names = ["local_ms_per_round", "mix_ms_per_round", "mix_roofline", "eval_ms_per_eval"]
    assert metrics.read_all([{"name": n} for n in names], ctx) == {}



@pytest.fixture(scope="module")
def chip_summary(tmp_path_factory):
    """The reduction of a trace recorded on a TPU v5e by ``record_trace.py``:
    16 paper MLPs on a ring with failing links, chunks of 3 rounds, traced
    from the first chunk's callback to the end of the call."""
    import gzip

    data = gzip.decompress((ROOT / "tests" / "chipbench" / "data" / "chip_trace.xplane.pb.gz").read_bytes())
    path = tmp_path_factory.mktemp("chip") / "t.xplane.pb"
    path.write_bytes(data)
    return Summary.from_file(str(path), 1)


def test_chip_recorded_trace_reduces(chip_summary):
    s = chip_summary
    assert len(s.marks) == 3  # one annotation per chunk callback
    assert 0 < s.busy_s <= s.window_s
    local, mix, ev = (s.scope_s(k) for k in ("dfl_local", "dfl_mix", "dfl_eval"))
    assert local > 0 and mix > 0 and ev > 0
    assert local + mix + ev <= s.busy_s
    assert s.scope_s("halo_exchange") is None  # one device, no halo
    # the marks after the first fall between programs: each finds its gap
    assert len(s.boundary_gaps_s()) == 2 and all(g >= 0 for g in s.boundary_gaps_s())
    b = s.breakdown()
    assert b["device_ops"] and all(t > 0 for _, t in b["device_ops"])
    assert b["idle_gaps"] and any(CHUNK_SPAN in label for label, _ in b["idle_gaps"])
