"""Telemetry layer (repro.obs, DESIGN.md §17): channel specs, wire-cost
accounting against hand-counted edges/bytes, run-log schema, and the
no-perturbation contract — recording extra channels must not change the
trajectory or the legacy channels.

Executor↔train_loop bit-parity itself is pinned in tests/test_executor.py
(the executors now route through the Recorder, so those tests ARE the
Recorder parity suite); here we cover what telemetry *adds*.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import topology as T
from repro.core.commplan import FailureModel, compile_plan, compile_schedule, cyclic_map
from repro.core.initialisation import InitConfig
from repro.core.shardplan import ShardedCommPlan, _build_hyb_tables, _build_layout
from repro.data import batch_index_schedule, mnist_like, node_datasets
from repro.fed import init_fl_state, make_eval_fn, make_round_fn, run_trajectory
from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
from repro.obs import (
    BinChannel,
    BinSpec,
    Channel,
    MetricsSpec,
    Recorder,
    history_rows,
    make_wire_fn,
    param_row_bytes,
    read_run_log,
    run_manifest,
    sharded_wire_per_round,
    staleness_histogram,
    static_wire_messages,
    validate_run_log,
    write_run_log,
)
from repro.optim import sgd

N, PER_NODE, BS, B_LOCAL, ROUNDS = 6, 48, 8, 2, 8


@pytest.fixture(scope="module")
def setup():
    ds = mnist_like(N * PER_NODE + 64, seed=0)
    parts = [np.arange(i * PER_NODE, (i + 1) * PER_NODE) for i in range(N)]
    xs, ys = node_datasets(ds, parts)
    test = (ds.x[-64:], ds.y[-64:])
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])
    opt = sgd(1e-3, 0.5)
    init_one = lambda k: init_mlp(InitConfig("he_normal", 2.0), k, hidden=(32,))
    return xs, ys, test, loss_fn, opt, init_one


def _sched(rounds=ROUNDS):
    return batch_index_schedule(PER_NODE, N, BS, rounds * B_LOCAL, seed=0)


# --------------------------------------------------------------- MetricsSpec


def test_legacy_spec_orders_channels_like_the_old_outs():
    spec = MetricsSpec.legacy(True, True, wire=True)
    assert spec.names == ("train_loss", "test_loss", "sigma_ap", "sigma_an", "wire_messages")
    assert [c.name for c in spec.gated] == ["test_loss", "sigma_ap", "sigma_an"]
    assert MetricsSpec.legacy(False, False).names == ("train_loss",)


def test_spec_rejects_duplicate_names():
    with pytest.raises(ValueError):
        MetricsSpec((Channel("a"), Channel("a")))


def test_recorder_step_gates_and_orders():
    rec = Recorder(MetricsSpec((Channel("x"), Channel("y", gated=True))))

    def one(gate):
        return rec.step(
            {"x": jnp.float32(2.0)},
            gate=jnp.asarray(gate),
            gated_fn=lambda op: {"y": op * 3.0},
            operand=jnp.float32(1.0),
        )

    on = [float(v) for v in jax.jit(one)(True)]
    off = [float(v) for v in jax.jit(one)(False)]
    assert on == [2.0, 3.0]
    assert off[0] == 2.0 and np.isnan(off[1])


def test_recorder_assemble_types_and_constants():
    rec = Recorder(MetricsSpec((Channel("loss"), Channel("count", ints=True))))
    mask = np.array([True, False, True])
    hist = rec.assemble(mask, [np.array([0.5, 1.0, 1.5]), np.array([2.0, 4.0, 6.0])],
                        constants={"wire_bytes": 128})
    assert hist["round"] == [0, 2]
    assert hist["loss"] == [0.5, 1.5] and hist["count"] == [2, 6]
    assert isinstance(hist["count"][0], int)
    assert hist["wire_bytes"] == [128, 128]
    assert hist["sigma_ap"] == []  # train_loop base keys always present


def test_binspec_shapes_and_fills():
    spec = BinSpec(5, (BinChannel("a"), BinChannel("nanbuf", fill=float("nan")),
                       BinChannel("wide", width=16)))
    acc = spec.init()
    assert acc["a"].shape == (5,) and float(acc["a"].sum()) == 0.0
    assert acc["wide"].shape == (16,)
    assert np.isnan(np.asarray(acc["nanbuf"])).all()


# ----------------------------------------------------------------- wire cost


def test_param_row_bytes_hand_counted():
    params = {"w": jnp.zeros((4, 3, 2), jnp.float32), "b": jnp.zeros((4, 5), jnp.float32)}
    assert param_row_bytes(params) == (3 * 2 + 5) * 4


def test_static_wire_ring_hand_counted():
    # ring(8): 8 undirected edges → 16 messages every clean round
    plan = compile_plan(T.ring(8), backend="sparse")
    msgs = static_wire_messages(plan, 5)
    np.testing.assert_array_equal(msgs, [16] * 5)


def test_static_wire_schedule_follows_round_map():
    # cyclic period-2 over ring(8) (8 edges) and complete(8) (28 edges)
    sch = compile_schedule([T.ring(8), T.complete(8)], "dense", round_map=cyclic_map(2))
    msgs = static_wire_messages(sch, 6)
    np.testing.assert_array_equal(msgs, [16, 16, 56, 56, 16, 16])


def test_static_wire_none_for_directed():
    g = T.ring(6)
    directed = T.Graph(adjacency=np.triu(g.adjacency), name="dir", directed=True)
    plan = compile_plan(directed, backend="dense")
    assert static_wire_messages(plan, 3) is None
    assert make_wire_fn(plan) is None


def test_wire_fn_clean_masks_hand_counted():
    # ring(8) with node 0 inactive: edges (0,1) and (7,0) die → 6 live edges
    plan = compile_plan(T.ring(8), backend="sparse")
    wire = make_wire_fn(plan)
    active = jnp.ones(8, bool).at[0].set(False)
    assert float(wire(None, 0, active=active)) == 12.0
    assert float(wire(None, 0)) == 16.0


def test_wire_fn_failure_draws_match_mask_replay():
    plan = compile_plan(
        T.random_k_regular(8, 3, seed=0), backend="sparse",
        failures=FailureModel(link_p=0.6, node_p=0.8),
    )
    wire = make_wire_fn(plan)
    for s in range(4):
        key = jax.random.PRNGKey(s)
        edge_keep, node_act = plan._round_masks_ext(key, None, None)
        ek, na = np.asarray(edge_keep), np.asarray(node_act)
        uv = np.asarray(plan.event_uv)
        expect = 2.0 * sum(ek[i] and na[u] and na[v] for i, (u, v) in enumerate(uv))
        assert float(wire(key, 0)) == expect


def _host_sharded(plan, shards):
    """Host-side ShardedCommPlan (layout tables only, no device mesh) — the
    tier-1 rendering of shard_plan's sparse path (test_sharded_plan pattern)."""
    n = plan.n
    src, dst = np.asarray(plan.src), np.asarray(plan.dst)
    uid, edge_w = np.asarray(plan.edge_uid), np.asarray(plan.edge_w)
    raw_e, self_w = np.asarray(plan.raw_edge_w), np.asarray(plan.self_w)
    raw_s = np.asarray(plan.raw_self_w)
    ident = np.arange(len(src), dtype=np.int32)
    recv = _build_layout(n, shards, dst, src, uid, edge_w, raw_e, ident, self_w, raw_s)
    order = np.lexsort((dst, src))
    send = _build_layout(
        n, shards, src[order], dst[order], uid[order], edge_w[order], raw_e[order],
        ident[order], self_w, raw_s,
    )
    return ShardedCommPlan(
        base=plan, mesh=None, axis="node", n_shards=shards, nps=n // shards,
        recv=recv, send=send, hyb=_build_hyb_tables(plan, recv, shards),
    )


def test_sharded_wire_two_shard_ring_hand_counted():
    # ring(8) over 2 contiguous shards, masked (failure-active) rendering:
    # cross edges (3,4) and (7,0) → each shard pulls 2 halo rows at
    # all_to_all width h_max=2 → 2 shards × 2 rows = 4 rows per round
    plan = compile_plan(T.ring(8), backend="sparse", failures=FailureModel(link_p=0.9))
    sp = _host_sharded(plan, 2)
    params = {"w": jnp.zeros((8, 10), jnp.float32)}
    w = sharded_wire_per_round(sp, params)
    assert w["wire_rows"] == 4
    assert w["wire_bytes"] == 4 * 10 * 4
    assert w["wire_collectives"] == 1  # one all_to_all, one param leaf


def test_sharded_wire_counts_hub_gather_of_clean_hyb_mix():
    # the clean mix of this plan renders all 8 rows through the HYB hub
    # contraction, which all-gathers the payload: + 2 shards × 4 remote rows
    sp = _host_sharded(compile_plan(T.ring(8), backend="sparse"), 2)
    params = {"w": jnp.zeros((8, 10), jnp.float32), "b": jnp.zeros((8,), jnp.float32)}
    w = sharded_wire_per_round(sp, params)
    assert w["wire_rows"] == 4 + 2 * 4
    assert w["wire_bytes"] == 12 * (10 + 1) * 4
    assert w["wire_collectives"] == 2 * 2  # (halo + hub gather) × two leaves


# ----------------------------------------------- executor wire integration


def test_trajectory_reports_static_wire(setup):
    xs, ys, test, loss_fn, opt, init_one = setup
    plan = compile_plan(T.ring(N), backend="dense")
    rf = make_round_fn(loss_fn, opt, plan)
    state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
    state, hist = run_trajectory(
        state, rf, xs, ys, _sched(), n_rounds=ROUNDS, eval_every=3,
        eval_fn=make_eval_fn(loss_fn), eval_batch=test,
    )
    assert hist["wire_messages"] == [2 * N] * len(hist["round"])
    row_bytes = param_row_bytes(state.params)
    assert hist["wire_bytes"] == [2 * N * row_bytes] * len(hist["round"])


def test_trajectory_traced_wire_replays_key_stream(setup):
    """Under failures the in-scan count must replay exactly the k_mix
    stream the rounds consume — verified by re-deriving it from the
    initial state's rng on the host."""
    xs, ys, test, loss_fn, opt, init_one = setup
    plan = compile_plan(T.ring(N), backend="dense")
    rf = make_round_fn(loss_fn, opt, plan, link_p=0.5)
    state0 = init_fl_state(jax.random.PRNGKey(1), N, init_one, opt)
    _, hist = run_trajectory(
        state0, rf, xs, ys, _sched(), n_rounds=ROUNDS, eval_every=1,
    )
    eff = rf.plan  # make_round_fn recompiled the plan with the failure model
    rng = state0.rng
    uv = np.asarray(eff.event_uv)
    for r in range(ROUNDS):
        rng, k_mix = jax.random.split(rng)
        ek, na = (np.asarray(a) for a in eff._round_masks_ext(k_mix, None, None))
        expect = 2 * sum(bool(ek[i] and na[u] and na[v]) for i, (u, v) in enumerate(uv))
        assert hist["wire_messages"][r] == expect
    assert any(m < 2 * N for m in hist["wire_messages"])  # failures actually bit


def test_telemetry_does_not_perturb_trajectory(setup):
    """The wire channel rides the same scan: params, PRNG and the legacy
    channels must be bit-identical with and without it."""
    xs, ys, test, loss_fn, opt, init_one = setup
    plan = compile_plan(T.ring(N), backend="dense")
    rf = make_round_fn(loss_fn, opt, plan, link_p=0.5)
    bare = lambda state, batch: rf(state, batch)  # no .plan attr → no wire
    common = dict(n_rounds=ROUNDS, eval_every=3, eval_fn=make_eval_fn(loss_fn),
                  eval_batch=test, track_sigmas=True)
    s_wire = init_fl_state(jax.random.PRNGKey(2), N, init_one, opt)
    s_wire, h_wire = run_trajectory(s_wire, rf, xs, ys, _sched(), **common)
    s_bare = init_fl_state(jax.random.PRNGKey(2), N, init_one, opt)
    s_bare, h_bare = run_trajectory(s_bare, bare, xs, ys, _sched(), **common)
    assert "wire_messages" in h_wire and "wire_messages" not in h_bare
    for a, b in zip(jax.tree_util.tree_leaves(s_wire), jax.tree_util.tree_leaves(s_bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ("round", "train_loss", "test_loss", "sigma_ap", "sigma_an"):
        assert h_wire[k] == h_bare[k]


def test_trajectory_on_chunk_streams_history(setup):
    xs, ys, test, loss_fn, opt, init_one = setup
    plan = compile_plan(T.ring(N), backend="dense")
    rf = make_round_fn(loss_fn, opt, plan)
    state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
    seen = []
    state, hist = run_trajectory(
        state, rf, xs, ys, _sched(), n_rounds=ROUNDS, eval_every=2, chunk_size=3,
        on_chunk=lambda r0, r1, h: seen.append((r0, r1, h)),
    )
    assert [(r0, r1) for r0, r1, _ in seen] == [(0, 3), (3, 6), (6, 8)]
    streamed = [r for _, _, h in seen for r in h["round"]]
    assert streamed == hist["round"]
    streamed_loss = [v for _, _, h in seen for v in h["train_loss"]]
    assert streamed_loss == hist["train_loss"]
    assert all("wire_bytes" in h for _, _, h in seen)


# --------------------------------------------------------- health channels


def test_staleness_histogram_edges():
    h = staleness_histogram(np.array([1.0, 0.0, 3.0, 0.0]), horizon=8.0)
    assert h["counts"] == [1.0, 0.0, 3.0, 0.0]
    assert h["edges"] == [0.0, 2.0, 4.0, 6.0, 8.0]


# ------------------------------------------------------- spans and scopes


def _run(script: str, *args: str, timeout: int = 300) -> str:
    """Run ``script`` in a fresh interpreter, bounded by ``timeout``: a
    profiler or a forced device count must not touch this process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", script, *args],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_PROFILED = textwrap.dedent(
    """
    import glob, json, sys
    import jax, numpy as np
    from repro.core import topology as T
    from repro.core.commplan import FailureModel, compile_plan
    from repro.core.initialisation import InitConfig
    from repro.data import batch_index_schedule, mnist_like, node_datasets
    from repro.fed import init_fl_state, make_eval_fn, make_round_fn, run_trajectory
    from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
    from repro.optim import sgd

    N, PER_NODE, BS, B_LOCAL, ROUNDS = 6, 48, 8, 2, 9
    ds = mnist_like(N * PER_NODE + 64, seed=0)
    xs, ys = node_datasets(ds, [np.arange(i * PER_NODE, (i + 1) * PER_NODE) for i in range(N)])
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])
    opt = sgd(1e-3, 0.5)
    init_one = lambda k: init_mlp(InitConfig("he_normal", 2.0), k, hidden=(32,))
    plan = compile_plan(T.ring(N), backend="dense", failures=FailureModel(link_p=0.8))
    rf = make_round_fn(loss_fn, opt, plan)
    sched = batch_index_schedule(PER_NODE, N, BS, ROUNDS * B_LOCAL, seed=0)

    def run():
        s = init_fl_state(jax.random.PRNGKey(1), N, init_one, opt)
        s, _ = run_trajectory(
            s, rf, xs, ys, sched, n_rounds=ROUNDS, eval_every=2, eval_fn=make_eval_fn(loss_fn),
            eval_batch=(ds.x[-64:], ds.y[-64:]), track_sigmas=True, chunk_size=3,
            b_local=B_LOCAL, on_chunk=lambda *a: None,
        )
        return jax.block_until_ready(s)

    off = run()
    jax.profiler.start_trace(sys.argv[1])
    on = run()
    jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        glob.glob(sys.argv[1] + "/plugins/profile/*/*.xplane.pb")[0]
    )
    spans = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in pd.planes if plane.name.startswith("/host")
        for line in plane.lines for e in line.events if e.name.startswith("dfl.")
    ]
    same = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(off), jax.tree_util.tree_leaves(on))
    )
    print(json.dumps({"spans": sorted(spans, key=lambda s: s[1]), "same": same}))
    """
)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A 3-chunk ``run_trajectory`` with the profiler off, then on."""
    out = _run(_PROFILED, str(tmp_path_factory.mktemp("prof")))
    return json.loads(out.strip().splitlines()[-1])


def test_profiled_run_nests_chunk_spans(profiled):
    from repro.obs.trace import SPANS

    spans = profiled["spans"]
    assert {s[0] for s in spans} <= set(SPANS)
    (traj,) = [s for s in spans if s[0] == "dfl.trajectory"]
    chunks = [s for s in spans if s[0] == "dfl.chunk"]
    assert len(chunks) == 3
    inside = lambda s, c: c[1] <= s[1] and s[2] <= c[2]  # noqa: E731
    for c in chunks:
        assert inside(c, traj)
        kids = [s for s in spans if s[0].startswith("dfl.chunk.") and inside(s, c)]
        assert [k[0] for k in kids] == ["dfl.chunk.slice", "dfl.chunk.dispatch", "dfl.chunk.fetch"]
        assert kids[0][2] <= kids[1][1] and kids[1][2] <= kids[2][1]
    (asm,) = [s for s in spans if s[0] == "dfl.assemble"]
    assert inside(asm, traj) and asm[1] >= max(c[2] for c in chunks)


def test_profiler_does_not_perturb_trajectory(profiled):
    assert profiled["same"]


def test_executor_counts_calls_and_chunk_traces(setup):
    from repro.obs.trace import COUNTERS, counts

    xs, ys, test, loss_fn, opt, init_one = setup
    rf = make_round_fn(loss_fn, opt, compile_plan(T.ring(N), backend="dense"))
    before = counts()
    for _ in range(2):
        state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
        run_trajectory(state, rf, xs, ys, _sched(), n_rounds=ROUNDS, chunk_size=4)
    after = counts()
    assert set(after) <= set(COUNTERS)
    # one chunk shape: one trace a call, each drawing its batches by gather on the CPU
    for name in ("dfl.calls", "dfl.chunk_traces", "batch.select_gather"):
        assert after[name] - before.get(name, 0) == 2
    assert after.get("batch.select_onehot", 0) == before.get("batch.select_onehot", 0)


@pytest.mark.parametrize("rendering", ["onehot", "gather"])
def test_batch_selection_counter_follows_rendering(monkeypatch, setup, rendering):
    """A chunk program forced to one rendering moves that counter alone."""
    from repro.fed import batching
    from repro.obs.trace import COUNTERS, counts

    monkeypatch.setattr(batching, "select_rendering", lambda *a: rendering)
    xs, ys, test, loss_fn, opt, init_one = setup
    rf = make_round_fn(loss_fn, opt, compile_plan(T.ring(N), backend="dense"))
    before = counts()
    state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
    run_trajectory(state, rf, xs, ys, _sched(), n_rounds=ROUNDS)
    after = counts()
    other = "gather" if rendering == "onehot" else "onehot"
    for r, delta in ((rendering, 1), (other, 0)):
        name = f"batch.select_{r}"
        assert name in COUNTERS
        assert after.get(name, 0) - before.get(name, 0) == delta


def test_round_body_scopes_in_chunk_lowering(setup):
    from repro.fed.executor import _as_round_schedule, _build_chunk_fn, _device_data
    from repro.obs.trace import SCOPES

    xs, ys, test, loss_fn, opt, init_one = setup
    rf = make_round_fn(loss_fn, opt, compile_plan(T.ring(N), backend="dense"), link_p=0.5)
    chunk, _, _, _ = _build_chunk_fn(
        rf, N, make_eval_fn(loss_fn), True, wire_fn=make_wire_fn(rf.plan)
    )
    state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
    sched = jnp.asarray(_as_round_schedule(_sched(), ROUNDS, B_LOCAL))[:2]
    text = chunk.lower(
        state, sched, jnp.ones(2, bool), _device_data(xs, ys, test)
    ).as_text(debug_info=True)
    # the halo lives in the sharded mix; the model scopes in the CNN and VGG forwards
    model = {s for s in SCOPES if s.startswith("model_")}
    assert model == {"model_conv", "model_pool", "model_dense"}
    for name in set(SCOPES) - {"halo_exchange"} - model:
        assert re.search(rf'["/]{name}/', text), name
    assert "model_" not in text


def test_model_scopes_in_vgg_chunk_lowering():
    """The VGG forward's scopes reach the local steps' ops wrapped in the
    transformations' names (what ``chipbench/scopes.py`` strips)."""
    from repro.fed.executor import _as_round_schedule, _build_chunk_fn, _device_data
    from repro.models.paper_models import init_vgg16, vgg16_forward

    n, per_node = 2, 8
    xs = jnp.zeros((n, per_node, 32, 32, 3))
    ys = jnp.zeros((n, per_node), jnp.int32)
    loss_fn = lambda p, b: classifier_loss(vgg16_forward(p, b[0]), b[1])  # noqa: E731
    opt = sgd(1e-3, 0.5)
    init_one = lambda k: init_vgg16(InitConfig("he_normal", 1.0), k, width_mult=0.125)  # noqa: E731
    rf = make_round_fn(loss_fn, opt, compile_plan(T.ring(n), backend="dense"))
    chunk, _, _, _ = _build_chunk_fn(rf, n, make_eval_fn(loss_fn), False)
    state = init_fl_state(jax.random.PRNGKey(0), n, init_one, opt)
    sched = batch_index_schedule(per_node, n, 4, 2, seed=0)
    text = chunk.lower(
        state, jnp.asarray(_as_round_schedule(sched, 1, 2)), jnp.ones(1, bool),
        _device_data(xs, ys, (xs[0], ys[0])),
    ).as_text(debug_info=True)
    for name in ("model_conv", "model_pool", "model_dense"):
        for wrapped in (f"jvp({name})", f"transpose(jvp({name}))", f"vmap({name})"):
            assert f'"{wrapped}/' in text, wrapped


_SHARDED_SCOPES = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import glob, re
    import jax, numpy as np
    jax.config.update("jax_dump_ir_to", sys.argv[1])
    from repro.core import topology as T
    from repro.core.commplan import FailureModel, compile_plan
    from repro.core.initialisation import InitConfig
    from repro.data import batch_index_schedule, mnist_like, node_datasets
    from repro.fed import init_fl_state, make_eval_fn, run_sharded_trajectory
    from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
    from repro.obs.trace import SCOPES, counts
    from repro.optim import sgd

    N, PER_NODE, BS, B_LOCAL, ROUNDS = 8, 16, 8, 2, 2
    ds = mnist_like(N * PER_NODE + 16, seed=0)
    xs, ys = node_datasets(ds, [np.arange(i * PER_NODE, (i + 1) * PER_NODE) for i in range(N)])
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])
    opt = sgd(1e-3, 0.5)
    plan = compile_plan(
        T.random_k_regular(N, 4, seed=1), backend="sparse", failures=FailureModel(link_p=0.8)
    ).shard(n_shards=4)
    s0 = init_fl_state(
        jax.random.PRNGKey(0), N, lambda k: init_mlp(InitConfig("he_normal", 2.0), k, hidden=(16,)), opt
    )
    run_sharded_trajectory(
        s0, loss_fn, opt, plan, xs, ys,
        batch_index_schedule(PER_NODE, N, BS, ROUNDS * B_LOCAL, seed=0),
        n_rounds=ROUNDS, eval_every=1, eval_fn=make_eval_fn(loss_fn),
        eval_batch=(ds.x[-16:], ds.y[-16:]), track_sigmas=True, b_local=B_LOCAL,
    )
    texts = [open(f).read() for f in glob.glob(sys.argv[1] + "/*.mlir")]
    (text,) = [t for t in texts if "dfl_local" in t]
    missing = [s for s in SCOPES if s != "dfl_wire" and not s.startswith("model_")
               and not re.search('["/]' + s + '/', text)]
    assert not missing, missing
    assert re.search(r'dfl_mix/[^"]*halo_exchange/', text)
    assert counts() == {"dfl.calls": 1, "dfl.chunk_traces": 1, "batch.select_gather": 1}, counts()
    print("SHARDED_SCOPES_OK")
    """
)


def test_sharded_trajectory_scopes_in_lowering(tmp_path):
    """Four virtual devices: the sharded round body carries every scope of
    the one-device body (the delivered-message replay aside: the halo's
    wire cost is a plan static) and the halo exchange nests under the mix."""
    assert "SHARDED_SCOPES_OK" in _run(_SHARDED_SCOPES, str(tmp_path))


# ------------------------------------------------------------ run-log export


def test_run_log_round_trip(tmp_path):
    manifest = run_manifest({"fig": "smoke", "lr": 0.1}, seed=7, argv=["x", "--y"])
    hist = {"round": [0, 3], "train_loss": [1.0, float("nan")], "test_loss": [0.5, 0.4],
            "sigma_ap": [], "sigma_an": []}
    rows = history_rows(hist)
    path = tmp_path / "run.jsonl"
    n = write_run_log(path, [manifest, *rows, {"kind": "summary", "final": 0.4}])
    assert n == 4
    back = read_run_log(path)
    assert back[0]["kind"] == "manifest" and back[0]["seed"] == 7
    assert back[1] == {"kind": "round", "round": 0, "train_loss": 1.0, "test_loss": 0.5}
    assert back[2]["train_loss"] is None  # NaN sanitised to null (strict JSON)
    assert validate_run_log(path) == []
    # strict JSON end to end: stdlib parser with no NaN extension accepts it
    for line in path.read_text().splitlines():
        json.loads(line, parse_constant=lambda _: pytest.fail("non-strict JSON"))


def test_run_log_schema_gate_catches_breakage(tmp_path):
    manifest = run_manifest({}, seed=0)
    bad = dict(manifest)
    del bad["git_rev"]
    path = tmp_path / "bad.jsonl"
    write_run_log(path, [bad, {"kind": "round", "round": 0}])
    assert any("git_rev" in p for p in validate_run_log(path))
    write_run_log(path, [{"kind": "round", "round": 0}])
    assert any("manifest" in p for p in validate_run_log(path))
    write_run_log(path, [manifest])
    assert any("no data records" in p for p in validate_run_log(path))


def test_history_rows_uses_bin_axis_for_event_histories():
    hist = {"bin": [0, 1], "time": [2.0, 4.0], "messages": [4, 4], "round": []}
    rows = history_rows(hist, kind="bin")
    assert [r["kind"] for r in rows] == ["bin", "bin"]
    assert rows[1]["messages"] == 4 and rows[1]["time"] == 4.0


# ---------------------------------------------------------------------------
# dashboard + bench timing split
# ---------------------------------------------------------------------------


def test_dashboard_bench_report_matches_committed(tmp_path):
    # the CI gate's premise: the renderer is deterministic, so regenerating
    # from the committed artifacts reproduces the committed report exactly
    import pathlib
    import subprocess
    import sys as _sys

    root = pathlib.Path(__file__).resolve().parent.parent
    out_md = tmp_path / "BENCH_REPORT.md"
    out_html = tmp_path / "dash.html"
    subprocess.run(
        [_sys.executable, str(root / "tools" / "dashboard.py"), "--bench", str(root),
         "--out-md", str(out_md), "--out-html", str(out_html)],
        check=True, capture_output=True,
    )
    assert out_md.read_text() == (root / "BENCH_REPORT.md").read_text()
    html_text = out_html.read_text()
    assert "<table>" in html_text and "Headline timings" in html_text


def test_dashboard_run_mode_renders_telemetry(tmp_path):
    import pathlib
    import subprocess
    import sys as _sys

    root = pathlib.Path(__file__).resolve().parent.parent
    log = tmp_path / "run.jsonl"
    manifest = run_manifest({"model": "mlp", "rounds": 2}, seed=3)
    rows = history_rows({"round": [0, 1], "train_loss": [1.5, 1.25], "wire_bytes": [64, 64]})
    write_run_log(log, [manifest, *rows, {"kind": "summary", "final_train_loss": 1.25}])
    out = tmp_path / "run.md"
    subprocess.run(
        [_sys.executable, str(root / "tools" / "dashboard.py"), "--run", str(log),
         "--out-md", str(out)],
        check=True, capture_output=True,
    )
    text = out.read_text()
    assert "Manifest" in text and "History (2 round records)" in text
    assert "wire_bytes" in text and "summary" in text


def test_chunk_timer_splits_compile_from_steady():
    from benchmarks.common import ChunkTimer

    t = ChunkTimer()
    # first chunk carries ~8 s of compile on top of 4 rounds of steady work;
    # the trailing short chunk (recompiled) must not pollute the median
    t.walls = [10.0, 2.0, 2.2, 1.8, 5.0]
    t.sizes = [4, 4, 4, 4, 2]
    compile_s, steady = t.split()
    assert steady == pytest.approx(0.5)
    assert compile_s == pytest.approx(10.0 - 0.5 * 4)
    single = ChunkTimer()
    single.walls, single.sizes = [4.0], [8]
    assert single.split() == (0.0, pytest.approx(0.5))


def test_check_bench_prefers_steady_timing_keys():
    import importlib.util
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location("check_bench", root / "tools" / "check_bench.py")
    cb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cb)
    conflated = {"us_per_event": 9.0, "sec_per_round_sync": 1.0, "final_loss": 2.0}
    assert sorted(cb._timing_keys(conflated)) == ["sec_per_round_sync", "us_per_event"]
    split = dict(conflated, us_per_event_steady=3.0, compile_seconds_event=5.0)
    assert cb._timing_keys(split) == ["us_per_event_steady"]
