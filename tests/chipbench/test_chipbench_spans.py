"""The readers of the program's spans, scopes and counters
(``chipbench/spans.py`` and the five metrics that use it).

First on a hand-built ``Summary`` whose every number is known: idle gaps
cut by host spans, scopes that nest, ops that straddle the window; then on
a trace recorded on a TPU v5e by ``record_trace.py`` with the program's
spans and scopes in it.
"""
import collections
import gzip
import json

import pytest
from _paths import ROOT

from chipbench import entries, metrics, spans
from chipbench.trace import HostEvent, Op, Summary

NEW = ["unscoped_ms_per_round", "bookkeeping_ms_per_round", "fetch_idle_ms",
       "dispatch_idle_ms", "traces_per_call"]
BODY = "jit(chunk_inner)/while/body/"


def _ctx(summary, rounds=2, evals=1):
    cfg = json.loads((ROOT / "chipbench" / "configs" / "paper_mlp.json").read_text())
    tr = {"graph": {"family": "ring", "n": 16}, "local_batches": 8, "batch_size": 16}
    win = entries.Window(rounds=rounds, node_rounds=16 * rounds, wall_s=1.0, t0=0.0, t1=0.0,
                         calls=1, notes={"eval_rounds": evals})
    return metrics.Context(cfg, tr, win, win, summary, peak_bytes=2**31,
                           device_kind="TPU v5 lite", chips=1)


@pytest.fixture
def summary():
    """Window [1000, 5000]: programs over [0, 2000], [2600, 4000] and
    [4300, 5200], so idle [2000, 2600] and [4000, 4300]; marks at 1000, 2200
    and 4100 (three boundaries) and one past the end."""
    ops = [
        Op(500, 1500, "%fusion.0", BODY + "dfl_local/dot_general:", "convolution"),  # 500 in
        Op(1500, 1800, "%fusion.1", BODY + "dfl_mix/scatter-add:", "loop fusion"),
        Op(1800, 1900, "%fusion.2", BODY + "dfl_mix/halo_exchange/all-to-all:", "collective"),
        Op(1900, 2000, "%copy.1", "", "copy"),
        Op(2600, 2700, "%fusion.3", BODY + "dfl_batch/gather:", "loop fusion"),
        Op(2700, 2750, "%fusion.4", BODY + "dfl_round/threefry2x32:", "loop fusion"),
        Op(2750, 2800, "%fusion.5", BODY + "dfl_wire/reduce_sum:", "loop fusion"),
        Op(2800, 3000, "%fusion.6", BODY + "dfl_reinit/vmap()/broadcast_in_dim:", "loop fusion"),
        # one op under two bookkeeping scopes counts once
        Op(3000, 3100, "%fusion.7", BODY + "cond/dfl_sigma/dfl_batch/reduce:", "loop fusion"),
        Op(3100, 3400, "%fusion.8", BODY + "cond/dfl_eval/jit(eval_fn)/dot:", "convolution"),
        Op(3400, 3460, "%dynamic-update-slice.1", BODY + "dynamic_update_slice", "data formatting"),
        Op(4300, 5200, "%fusion.9", BODY + "dfl_local/dot_general:", "convolution"),  # 700 in
    ]
    host = [
        HostEvent(200, 900, "dfl.chunk.fetch"),  # before the window
        HostEvent(1500, 2300, "dfl.chunk.fetch"),  # 300 of idle
        HostEvent(2350, 2450, "dfl.chunk.slice"),  # 100
        HostEvent(2450, 2650, "dfl.chunk.dispatch"),  # 150
        HostEvent(3500, 4050, "dfl.chunk.fetch"),  # 50
        HostEvent(4060, 4200, "dfl.chunk.slice"),  # overlaps the dispatch:
        HostEvent(4150, 4350, "dfl.chunk.dispatch"),  # 240 for the two, not 290
        HostEvent(2300, 2700, "dfl.chunk"),
    ]
    modules = [[(0, 2000), (2600, 4000), (4300, 5200)]]
    return Summary([sorted(ops, key=lambda o: o.start)], modules, host,
                   [1000, 2200, 4100, 5300], 1000, 5000)


def test_span_idle_counts_each_idle_ns_once(summary):
    assert spans.boundaries(summary) == 3
    assert spans.span_idle_s(summary, {"dfl.chunk.fetch"}) == pytest.approx(350e-9)
    both = {"dfl.chunk.slice", "dfl.chunk.dispatch"}
    assert spans.span_idle_s(summary, both) == pytest.approx(490e-9)
    assert spans.span_idle_s(summary, {"dfl.chunk.checkpoint"}) is None
    # of 900 ns idle, 60 fall in no fetch, slice or dispatch span
    idle = sum(e - s for s, e in summary.gaps(0))
    assert idle == 900


def test_scope_sums_split_every_op_once(summary):
    book = spans.ops_s(summary, spans.under(*spans.BOOKKEEPING))
    assert book == pytest.approx((100 + 50 + 50 + 200 + 100) * 1e-9)
    assert spans.ops_s(summary, lambda o: not spans.scoped(o)) == pytest.approx(160e-9)
    local, mix, ev = (summary.scope_s(k) for k in ("dfl_local", "dfl_mix", "dfl_eval"))
    # the leaf ops of the window, each under exactly one of the five
    assert local + mix + ev + book + 160e-9 == pytest.approx(2560e-9)


def test_readers(summary, monkeypatch):
    from repro.obs import trace

    monkeypatch.setattr(trace, "_counts", collections.Counter({"dfl.calls": 4, "dfl.chunk_traces": 6}))
    got = metrics.read_all([{"name": n} for n in NEW], _ctx(summary))
    assert got == pytest.approx({
        "unscoped_ms_per_round": 160e-6 / 2,
        "bookkeeping_ms_per_round": 500e-6 / 2,
        "fetch_idle_ms": 350e-6 / 3,
        "dispatch_idle_ms": 490e-6 / 3,
        "traces_per_call": 1.5,
    })


def test_readers_leave_out_what_the_program_does_not_emit(monkeypatch):
    """A program without the spans, scopes or counters (an older commit)
    gives no number for them, never 0; the readers do not raise."""
    import sys

    bare = Summary(
        ops=[[Op(0, 10, "%fusion.1", "jit(chunk_inner)/while/body/add", "loop fusion")]],
        modules=[[(0, 10)]], host=[HostEvent(0, 5, "PjitFunction(chunk_inner)")],
        marks=[0], lo=0, hi=10,
    )
    monkeypatch.setitem(sys.modules, "repro.obs.trace", None)  # no counters to import
    assert metrics.read_all([{"name": n} for n in NEW], _ctx(bare)) == {}


def test_no_call_gives_no_traces_per_call(summary, monkeypatch):
    from repro.obs import trace

    monkeypatch.setattr(trace, "_counts", collections.Counter())
    assert metrics.read_all([{"name": "traces_per_call"}], _ctx(summary)) == {}


@pytest.fixture(scope="module")
def chip_spans(tmp_path_factory):
    """A trace recorded on a TPU v5e by ``record_trace.py`` (16 paper MLPs
    on a ring with failing links, 3 chunks of 3 rounds, traced from the
    first chunk's callback), with the program's spans and scopes."""
    data = gzip.decompress(
        (ROOT / "tests" / "chipbench" / "data" / "chip_trace_spans.xplane.pb.gz").read_bytes()
    )
    path = tmp_path_factory.mktemp("chip") / "t.xplane.pb"
    path.write_bytes(data)
    return Summary.from_file(str(path), 1)


def test_chip_trace_holds_the_program_spans(chip_spans):
    names = {h.name for h in chip_spans.host}
    assert {"dfl.chunk", "dfl.chunk.slice", "dfl.chunk.dispatch", "dfl.chunk.fetch",
            "dfl.assemble"} <= names
    assert spans.boundaries(chip_spans) == 2  # the last chunk's mark lies past the end


def test_chip_trace_readers_close_both_sums(chip_spans):
    """Rounds 3..8 lie in the window, with evals at 4, 6 and 8."""
    s = chip_spans
    rounds, evals = 6, 3
    got = metrics.read_all(
        [{"name": n} for n in NEW[:4] + ["local_ms_per_round", "mix_ms_per_round",
                                         "eval_ms_per_eval", "device_idle"]],
        _ctx(s, rounds, evals),
    )
    assert set(got) == set(NEW[:4]) | {"local_ms_per_round", "mix_ms_per_round",
                                       "eval_ms_per_eval", "device_idle"}
    assert got["bookkeeping_ms_per_round"] > 0 and got["unscoped_ms_per_round"] > 0
    parts = (got["local_ms_per_round"] + got["mix_ms_per_round"] + got["bookkeeping_ms_per_round"]
             + got["unscoped_ms_per_round"] + got["eval_ms_per_eval"] * evals / rounds)
    # every leaf op once: the parts add up to the window's op time exactly
    assert parts == pytest.approx(1e3 * spans.ops_s(s, lambda o: True) / rounds)
    # leaf ops fill all but a few percent of the busy time
    assert 0.95 <= parts / (1e3 * s.busy_s / rounds) <= 1.0
    idle_per_boundary = got["device_idle"] / 100 * s.window_s * 1e3 / spans.boundaries(s)
    explained = got["fetch_idle_ms"] + got["dispatch_idle_ms"]
    assert 0.9 * idle_per_boundary <= explained <= idle_per_boundary
