"""Node-sharded CommPlan rendering: parity, determinism, batched events.

The sharded rendering's contract (DESIGN.md §15) is *bit*-parity: the same
plan run over a node-sharded mesh must produce bit-identical results to the
single-device operator — same per-row accumulation order through the
``[local | halo]`` gather space, same replicated failure draws.  Host-side
layout compilation is pure (tables must be deterministic), and the batched
event path must replay the sequential event stream exactly.

Multi-device cases run in a subprocess with 8 forced host devices (the
``tests/test_distributed.py`` pattern).  The executor and gossip-estimation
cases are tier-1 (about 15 s each); the operator sweep over every backend,
failure model and shard count is marked slow.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology as T
from repro.core.commplan import FailureModel, compile_plan
from repro.core.shardplan import _build_layout
from repro.core.topology import batch_events_by_color

_SCRIPT_OPERATORS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import topology as T
    from repro.core.commplan import FailureModel, compile_plan
    from repro.core.shardplan import shard_plan
    from repro.launch.mesh import make_production_mesh, n_fl_nodes, node_mesh

    # mesh satellite: explicit device counts scale the pod shape down
    assert n_fl_nodes(n_devices=8) == 8
    mesh = make_production_mesh(n_devices=8)
    assert int(np.prod(list(mesh.shape.values()))) == 8
    assert node_mesh(8).axis_names == ("node",)

    rng = np.random.default_rng(0)
    for graph in (T.random_k_regular(16, 4, seed=1), T.barabasi_albert(16, 3, seed=2)):
        n = graph.n
        x = jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))
        params = {"w": jnp.asarray(rng.normal(size=(n, 3, 2)).astype(np.float32)), "b": x}
        for failures in (FailureModel(), FailureModel(link_p=0.7, node_p=0.9)):
            key = jax.random.PRNGKey(42) if failures.active else None
            for backend in ("sparse", "dense"):
                plan = compile_plan(graph, backend=backend, failures=failures)
                ref = plan.mix(params, key=key)
                ref_spread = plan.spread(x, key=key)
                ref_min = plan.spread_min(x, key=key)
                for s in (1, 2, 4):
                    sp = shard_plan(plan, n_shards=s)
                    got = sp.mix(params, key=key)
                    for k in params:
                        assert np.array_equal(np.asarray(ref[k]), np.asarray(got[k])), (
                            graph.name,
                            backend,
                            failures.active,
                            s,
                            k,
                        )
                    assert np.array_equal(
                        np.asarray(ref_spread), np.asarray(sp.spread(x, key=key))
                    ), (graph.name, backend, failures.active, s, "spread")
                    assert np.array_equal(
                        np.asarray(ref_min), np.asarray(sp.spread_min(x, key=key))
                    ), (graph.name, backend, failures.active, s, "spread_min")
    print("OPERATORS_OK")
    """
)

_SCRIPT_EXECUTOR = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core import topology as T
    from repro.core.commplan import FailureModel, compile_plan
    from repro.core.initialisation import InitConfig
    from repro.data import batch_index_schedule, mnist_like, node_datasets
    from repro.fed import (
        init_fl_state,
        make_eval_fn,
        make_round_fn,
        run_sharded_trajectory,
        run_trajectory,
    )
    from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
    from repro.optim import sgd

    N, PER_NODE, BS, B_LOCAL, ROUNDS = 8, 32, 8, 2, 6
    ds = mnist_like(N * PER_NODE + 32, seed=0)
    parts = [np.arange(i * PER_NODE, (i + 1) * PER_NODE) for i in range(N)]
    xs, ys = node_datasets(ds, parts)
    test = (ds.x[-32:], ds.y[-32:])
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])
    opt = sgd(1e-3, 0.5)
    init_one = lambda k: init_mlp(InitConfig("he_normal", 2.0), k, hidden=(16,))
    eval_fn = make_eval_fn(loss_fn)
    sched = batch_index_schedule(PER_NODE, N, BS, ROUNDS * B_LOCAL, seed=0)
    graph = T.random_k_regular(N, 4, seed=1)
    common = dict(eval_every=3, eval_fn=eval_fn, eval_batch=test, track_sigmas=True)
    for link_p in (1.0, 0.8):
        plan = compile_plan(graph, backend="sparse")
        rf = make_round_fn(loss_fn, opt, plan, link_p=link_p)
        s0 = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
        s_ref, h_ref = run_trajectory(
            s0, rf, xs, ys, sched, n_rounds=ROUNDS, b_local=B_LOCAL, **common
        )
        for S in (2, 4):
            p2 = plan if link_p == 1.0 else plan.with_options(failures=FailureModel(link_p=link_p))
            sp = p2.shard(n_shards=S)
            s0b = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
            s_sh, h_sh = run_sharded_trajectory(
                s0b, loss_fn, opt, sp, xs, ys, sched, n_rounds=ROUNDS, b_local=B_LOCAL, **common
            )
            for a, b in zip(
                jax.tree_util.tree_leaves(s_ref.params),
                jax.tree_util.tree_leaves(s_sh.params),
            ):
                assert np.array_equal(np.asarray(a), np.asarray(b)), (link_p, S)
            for col in ("train_loss", "test_loss", "sigma_ap", "sigma_an"):
                r, g = np.asarray(h_ref[col]), np.asarray(h_sh[col])
                assert np.isnan(r).tolist() == np.isnan(g).tolist(), (link_p, S, col)
                assert np.nanmax(np.abs(r - g), initial=0.0) < 5e-6, (link_p, S, col)
    print("EXECUTOR_OK")
    """
)

_SCRIPT_GOSSIP = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core import topology as T
    from repro.core.commplan import FailureModel, compile_plan
    from repro.gossip import estimate_all, estimate_size_leaderless

    graph = T.random_k_regular(16, 4, seed=3)
    key = jax.random.PRNGKey(7)
    for failures in (FailureModel(), FailureModel(link_p=0.85)):
        plan = compile_plan(
            graph,
            backend="sparse",
            failures=failures,
            data_sizes=np.arange(1, 17, dtype=np.float64),
        )
        ref = estimate_all(plan, pi_rounds=5, ps_rounds=8, key=key)
        ref_l = estimate_size_leaderless(plan, 8, key)
        for S in (2, 4):
            sp = plan.shard(n_shards=S)
            got = estimate_all(sp, pi_rounds=5, ps_rounds=8, key=key)
            got_l = estimate_size_leaderless(sp, 8, key)
            assert np.array_equal(np.asarray(ref.n_hat), np.asarray(got.n_hat)), S
            assert np.array_equal(np.asarray(ref.vnorm), np.asarray(got.vnorm)), S
            assert np.array_equal(np.asarray(ref_l), np.asarray(got_l)), S
    print("GOSSIP_OK")
    """
)


def _run(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env, timeout=420
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_operators_bit_identical():
    """mix / spread / spread_min over {1, 2, 4} shards, dense and sparse,
    clean and failing, must be bit-identical to the single-device plan."""
    assert "OPERATORS_OK" in _run(_SCRIPT_OPERATORS)


def test_sharded_executor_parity():
    """run_sharded_trajectory: final params bit-identical to run_trajectory,
    psum-reduced metrics within float tolerance, NaN eval mask preserved."""
    assert "EXECUTOR_OK" in _run(_SCRIPT_EXECUTOR)


def test_sharded_gossip_estimation_parity():
    """The estimation engine over a sharded plan reproduces the unsharded
    estimates bit-exactly (spread / spread_min through the halo exchange)."""
    assert "GOSSIP_OK" in _run(_SCRIPT_GOSSIP)


def _layout_inputs(plan):
    src = np.asarray(plan.src)
    dst = np.asarray(plan.dst)
    return (
        plan.n,
        dst,
        src,
        np.asarray(plan.edge_uid),
        np.asarray(plan.edge_w),
        np.asarray(plan.raw_edge_w),
        np.arange(len(src), dtype=np.int32),
        np.asarray(plan.self_w),
        np.asarray(plan.raw_self_w),
    )


def test_halo_tables_deterministic():
    """Layout compilation is a pure function of the plan: two builds must
    produce identical tables and halo plans (the executor caches them as
    compile-time constants, so nondeterminism would break resume/replay)."""
    plan = compile_plan(T.barabasi_albert(24, 3, seed=5), backend="sparse")
    n, own, far, uid, ew, rew, perm, sw, rsw = _layout_inputs(plan)
    a = _build_layout(n, 4, own, far, uid, ew, rew, perm, sw, rsw)
    b = _build_layout(n, 4, own, far, uid, ew, rew, perm, sw, rsw)
    assert a.h_max == b.h_max
    assert a.pos == b.pos
    for f in ("seg", "gat", "uid", "edge_w", "gown", "gfar", "valid", "perm", "send"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)), np.asarray(getattr(b, f)))


def test_halo_layout_covers_all_edges():
    """Every CSR edge lands in exactly one shard slice, and every remote
    endpoint has a gather position in its owner's ``[local | halo]`` space."""
    plan = compile_plan(T.random_k_regular(24, 4, seed=6), backend="sparse")
    n, own, far, uid, ew, rew, perm, sw, rsw = _layout_inputs(plan)
    layout = _build_layout(n, 4, own, far, uid, ew, rew, perm, sw, rsw)
    nps = n // 4
    valid = np.asarray(layout.valid)
    assert int(valid.sum()) == len(far)
    gat = np.asarray(layout.gat)
    gfar = np.asarray(layout.gfar)
    for s in range(4):
        for g, fg in zip(gat[s][valid[s]], gfar[s][valid[s]]):
            if s * nps <= fg < (s + 1) * nps:
                assert g == fg - s * nps
            else:
                assert g == layout.pos[s][int(fg)]


def test_batched_events_match_sequential():
    """event_mix_batch over colour-batched events replays the sequential
    event stream bit-exactly — clean and with per-event failure draws."""
    graph = T.random_k_regular(12, 4, seed=1)
    stream = T.poisson_event_stream(graph, horizon=3.0, rate=1.0, seed=5)
    batches = batch_events_by_color(stream, graph)
    assert batches.n_events == stream.n_events
    el = graph.edge_list()
    for row in np.asarray(batches.edges):
        touched = [v for e in row if e >= 0 for v in (el[e, 0], el[e, 1])]
        assert len(touched) == len(set(touched)), row
    for failures in (FailureModel(), FailureModel(link_p=0.8, node_p=0.9)):
        plan = compile_plan(graph, backend="sparse", failures=failures)
        params = {
            "w": jnp.asarray(np.random.default_rng(0).normal(size=(12, 4)).astype(np.float32)),
        }
        base_key = jax.random.PRNGKey(3)
        seq = params
        for i in range(stream.n_events):
            k = jax.random.fold_in(base_key, i) if failures.active else None
            seq = plan.event_mix(seq, int(stream.edges[i]), k)
        bat = params
        for b in range(batches.n_batches):
            keys = None
            if failures.active:
                idx = jnp.asarray(np.maximum(batches.event_index[b], 0))
                keys = jax.vmap(lambda i: jax.random.fold_in(base_key, i))(idx)
            bat = plan.event_mix_batch(bat, jnp.asarray(batches.edges[b]), keys)
        np.testing.assert_array_equal(np.asarray(seq["w"]), np.asarray(bat["w"]))


def test_mesh_exports():
    """Satellite regression: ``n_fl_nodes`` is exported and usable without
    touching device state (the 8-device shapes are covered in the slow
    operators subprocess, where the forced host devices exist)."""
    from repro.launch import mesh as M

    assert "n_fl_nodes" in M.__all__
    assert M.n_fl_nodes() == 16
    assert M.n_fl_nodes(multi_pod=True) == 32
