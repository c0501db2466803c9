"""Fused round executor: parity with the legacy per-round train_loop.

The executor re-uses the exact ``round_fn`` that ``make_round_fn`` builds and
gathers its minibatches from ``batch_index_schedule`` — the same PRNG stream
and the same batch order as ``train_loop`` + ``node_batch_iterator``.  The
trajectory (params, opt state, rng) must therefore be bit-identical.  The
recorded per-round losses are reductions that the legacy loop and the scan
compute in separately compiled XLA programs, which may sum in another
order: they agree within ``METRIC_ULPS`` units in the last place
(DESIGN.md §11).  Lanes of one vmapped sweep are not bitwise either: XLA
may round each lane of a batched contraction differently.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import topology as T
from repro.core.commplan import compile_plan
from repro.core.faults import crash_burst
from repro.core.membership import membership_schedule
from repro.core.initialisation import InitConfig
from repro.data import batch_index_schedule, mnist_like, node_batch_iterator, node_datasets
from repro.fed import (
    batching,
    init_fl_state,
    make_eval_fn,
    make_round_fn,
    run_elastic_trajectory,
    run_sweep,
    run_trajectory,
    stack_states,
    train_loop,
    unstack_states,
)
from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
from repro.optim import sgd

N, PER_NODE, BS, B_LOCAL, ROUNDS = 6, 48, 8, 2, 10
# per-round loss reductions compiled in two programs: 1-2 ulp seen on CPU
METRIC_ULPS = 4


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


def _batches(xs, ys, seed=0):
    it = node_batch_iterator(xs, ys, BS, seed=seed)
    while True:
        b = [next(it) for _ in range(B_LOCAL)]
        yield (np.stack([q.x for q in b], 1), np.stack([q.y for q in b], 1))


def _schedule(seed=0, rounds=ROUNDS):
    return batch_index_schedule(PER_NODE, N, BS, rounds * B_LOCAL, seed=seed)


def _assert_within_ulps(a, b, max_ulps=METRIC_ULPS):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    assert a.shape == b.shape
    ulps = np.abs(a.view(np.int32).astype(np.int64) - b.view(np.int32).astype(np.int64))
    assert np.all(np.sign(a) == np.sign(b)) and ulps.max(initial=0) <= max_ulps, (a, b, ulps)


def _assert_states_bit_equal(s1, s2):
    for a, b in zip(jax.tree_util.tree_leaves(s1), jax.tree_util.tree_leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _run_both(setup, plan, link_p=1.0, chunk_size=0, **round_kw):
    xs, ys, test, loss_fn, opt, init_one = setup
    eval_fn = make_eval_fn(loss_fn)
    rf = make_round_fn(loss_fn, opt, plan, link_p=link_p, **round_kw)
    common = dict(eval_every=3, eval_fn=eval_fn, eval_batch=test, track_sigmas=True)
    s_leg = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
    s_leg, h_leg = train_loop(s_leg, rf, _batches(xs, ys), n_rounds=ROUNDS, **common)
    s_ex = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
    s_ex, h_ex = run_trajectory(
        s_ex, rf, xs, ys, _schedule(), n_rounds=ROUNDS, chunk_size=chunk_size, **common
    )
    return (s_leg, h_leg), (s_ex, h_ex)


def _assert_parity(leg, ex):
    (s_leg, h_leg), (s_ex, h_ex) = leg, ex
    _assert_states_bit_equal(s_leg, s_ex)
    assert h_leg["round"] == h_ex["round"]
    assert h_leg["sigma_ap"] == h_ex["sigma_ap"]
    assert h_leg["sigma_an"] == h_ex["sigma_an"]
    # loss reductions: separately compiled programs → a few ulp
    _assert_within_ulps(h_leg["train_loss"], h_ex["train_loss"])
    _assert_within_ulps(h_leg["test_loss"], h_ex["test_loss"])


def test_parity_dense_backend(setup):
    plan = compile_plan(T.complete(N), backend="dense")
    _assert_parity(*_run_both(setup, plan))


def test_parity_sparse_backend(setup):
    plan = compile_plan(T.random_k_regular(N, 3, seed=0), backend="sparse")
    _assert_parity(*_run_both(setup, plan))


def test_parity_dense_with_failures(setup):
    """Failure draws come from the state's PRNG stream — the scanned stream
    must match the per-round one draw for draw."""
    plan = compile_plan(T.complete(N), backend="dense")
    _assert_parity(*_run_both(setup, plan, link_p=0.5))


def test_parity_sparse_with_failures(setup):
    plan = compile_plan(T.random_k_regular(N, 3, seed=0), backend="sparse")
    _assert_parity(*_run_both(setup, plan, link_p=0.6))


def test_parity_chunked(setup):
    """Chunk boundaries (incl. a ragged final chunk) don't change anything."""
    plan = compile_plan(T.complete(N), backend="dense")
    _assert_parity(*_run_both(setup, plan, chunk_size=4))


def test_host_iterator_matches_schedule(setup):
    """Satellite contract: the vectorised host iterator and the on-device
    gather schedule select the same samples in the same order."""
    xs, ys, *_ = setup
    sched = batch_index_schedule(PER_NODE, N, BS, 3 * (PER_NODE // BS) + 2, seed=7)
    it = node_batch_iterator(xs, ys, BS, seed=7)
    node = np.arange(N)[:, None]
    for k in range(sched.shape[0]):  # crosses epoch reshuffle boundaries
        b = next(it)
        np.testing.assert_array_equal(b.y, ys[node, sched[k]])
        np.testing.assert_array_equal(b.x, xs[node, sched[k]])


def test_schedule_indices_cover_epochs():
    sched = batch_index_schedule(32, 4, 8, 8, seed=0)  # exactly 2 epochs
    assert sched.shape == (8, 4, 8)
    for node in range(4):
        for epoch in range(2):
            idx = sched[epoch * 4 : (epoch + 1) * 4, node].ravel()
            assert sorted(idx.tolist()) == list(range(32))  # full pass, no repeats


def test_sweep_matches_stacked_independent_runs(setup):
    """vmapped sweep axis ≡ the same runs executed independently."""
    xs, ys, test, loss_fn, opt, _ = setup
    eval_fn = make_eval_fn(loss_fn)
    rf = make_round_fn(loss_fn, opt, T.complete(N))
    # sweep over (gain, seed): different init scales and different init keys
    variants = [(1.0, 0), (2.5, 1)]
    states = [
        init_fl_state(
            jax.random.PRNGKey(s), N,
            lambda k, g=g: init_mlp(InitConfig("he_normal", g), k, hidden=(32,)), opt,
        )
        for g, s in variants
    ]
    common = dict(n_rounds=ROUNDS, eval_every=3, eval_fn=eval_fn, eval_batch=test, track_sigmas=True)
    swept, hists = run_sweep(stack_states(states), rf, xs, ys, _schedule(), **common)
    finals = unstack_states(swept)
    assert len(hists) == len(variants)
    for state, hist in zip(states, hists):
        s_ind, h_ind = run_trajectory(state, rf, xs, ys, _schedule(), **common)
        for a, b in zip(jax.tree_util.tree_leaves(s_ind), jax.tree_util.tree_leaves(finals.pop(0))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
        assert hist["round"] == h_ind["round"]
        np.testing.assert_allclose(hist["train_loss"], h_ind["train_loss"], rtol=1e-5)
        np.testing.assert_allclose(hist["test_loss"], h_ind["test_loss"], rtol=1e-5)
        np.testing.assert_allclose(hist["sigma_an"], h_ind["sigma_an"], rtol=1e-4, atol=1e-9)


def test_sweep_per_run_schedules(setup):
    """schedule_per_run routes run i through schedule i — probed with
    IDENTICAL init states so only the schedule axis can cause divergence."""
    xs, ys, test, loss_fn, opt, init_one = setup
    rf = make_round_fn(loss_fn, opt, T.complete(N))
    state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
    kw = dict(n_rounds=ROUNDS, eval_every=3, schedule_per_run=True)
    # control: same schedule for both runs → the same trajectory, up to the
    # per-lane rounding of the vmapped program (the sweep-vs-independent
    # tolerance of test_sweep_matches_stacked_independent_runs)
    same = np.stack([_schedule(seed=0)] * 2)
    _, h_same = run_sweep([state, state], rf, xs, ys, same, **kw)
    np.testing.assert_allclose(h_same[0]["train_loss"], h_same[1]["train_loss"], rtol=1e-5)
    # distinct schedules → run 1 must diverge from run 0 beyond that slack
    diff = np.stack([_schedule(seed=0), _schedule(seed=1)])
    _, h_diff = run_sweep([state, state], rf, xs, ys, diff, **kw)
    # run 0 kept schedule 0: the same program on the same inputs, bitwise
    assert h_diff[0]["train_loss"] == h_same[0]["train_loss"]
    assert not np.allclose(h_diff[1]["train_loss"], h_diff[0]["train_loss"], rtol=1e-5)


def test_no_eval_history_is_empty(setup):
    xs, ys, test, loss_fn, opt, init_one = setup
    rf = make_round_fn(loss_fn, opt, T.complete(N))
    state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
    _, hist = run_trajectory(state, rf, xs, ys, _schedule(), n_rounds=ROUNDS)
    assert hist["round"] == [] and hist["train_loss"] == []


# ------------------------------------------------ minibatch selection
# the rendering draw_batch resolves on a TPU, forced here on the CPU by
# replacing the rule (the helper looks it up at each trace)
def _force(monkeypatch, rendering):
    monkeypatch.setattr(batching, "select_rendering", lambda *a: rendering)


def _wide_items(rng, shape, dtype):
    """Items whose f32 values span many binades, with the values whose
    bf16 hi/mid/lo parts carry across a binade (2^(e+1) − 2^(e−9) + 2^(e−23))."""
    x = rng.standard_normal(shape) * 10.0 ** rng.uniform(-20, 20, shape)
    x = x.astype(np.float32).reshape(-1)
    e = rng.integers(-60, 60, 8).astype(np.float64)
    x[:8] = (2.0 ** (e + 1) - 2.0 ** (e - 9) + 2.0 ** (e - 23)).astype(np.float32)
    return jnp.asarray(x.reshape(shape), dtype)


@pytest.mark.parametrize("rendering", ["onehot", "gather"])
@pytest.mark.parametrize("case", ["repeats", "items_eq_drawn"])
@pytest.mark.parametrize("item_shape", [(28, 28, 1), (32, 32, 3), (784,)], ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_draw_batch_equals_gather(monkeypatch, dtype, item_shape, case, rendering):
    """Either rendering returns ``xs[node, idx]`` bit for bit: indices drawn
    with repeats inside a batch, or every item drawn once."""
    _force(monkeypatch, rendering)
    rng = np.random.default_rng(0)
    n, b, bs = 3, 2, 4
    items = b * bs if case == "items_eq_drawn" else 3 * b * bs
    xs = _wide_items(rng, (n, items) + item_shape, dtype)
    ys = jnp.asarray(rng.integers(0, 10, (n, items)), jnp.int32)
    if case == "items_eq_drawn":
        idx = np.stack([rng.permutation(items) for _ in range(n)]).reshape(n, b, bs)
    else:
        idx = rng.integers(0, items // 4, (n, b, bs))  # few distinct: repeats
        idx[:, 0, 1] = idx[:, 0, 0]
    idx = jnp.asarray(idx, jnp.int32)
    flat, shape = batching.lane_dense(xs)
    assert flat.shape == (n, items, int(np.prod(item_shape))) and shape == item_shape
    bx, by = batching.draw_batch(flat, ys, idx, shape)
    node = np.arange(n)[:, None, None]
    assert bx.dtype == xs.dtype and bx.shape == (n, b, bs) + item_shape
    np.testing.assert_array_equal(np.asarray(bx), np.asarray(xs)[node, np.asarray(idx)])
    np.testing.assert_array_equal(np.asarray(by), np.asarray(ys)[node, np.asarray(idx)])


KAPPA = batching._TPU_ONEHOT_KAPPA


@pytest.mark.parametrize(
    "platform, dtype, items, drawn, want",
    [
        # both cells: 256 items a node, 8 × 16 drawn; F (784, 3072) is not read
        ("tpu", jnp.float32, 256, 8 * 16, "onehot"),
        ("tpu", jnp.bfloat16, 256, 8 * 16, "onehot"),
        ("tpu", jnp.int32, 256, 8 * 16, "gather"),  # token ids
        ("tpu", jnp.float32, KAPPA * 128, 128, "onehot"),
        ("tpu", jnp.float32, KAPPA * 128 + 1, 128, "gather"),
        ("tpu", jnp.float32, 50_000, 128, "gather"),
        ("cpu", jnp.float32, 256, 8 * 16, "gather"),
    ],
    ids=["tpu-cells", "tpu-bf16", "tpu-int", "tpu-at-kappa",
         "tpu-above-kappa", "tpu-many-items", "cpu"],
)
def test_select_rendering_rule(platform, dtype, items, drawn, want):
    assert batching.select_rendering(platform, dtype, items, drawn) == want


def test_trajectory_params_bit_equal_across_renderings(monkeypatch, setup):
    """3 rounds of run_trajectory, forced to each rendering: the same
    batches, so bit-equal state."""
    xs, ys, test, loss_fn, opt, init_one = setup
    rf = make_round_fn(loss_fn, opt, compile_plan(T.complete(N), backend="dense"), link_p=0.5)
    finals = []
    for rendering in ("onehot", "gather"):
        _force(monkeypatch, rendering)
        state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
        finals.append(run_trajectory(state, rf, xs, ys, _schedule(rounds=3), n_rounds=3)[0])
    _assert_states_bit_equal(*finals)


def test_elastic_params_bit_equal_across_renderings(monkeypatch, setup):
    """The elastic executor's inline path (a crash burst), forced to each
    rendering: bit-equal state."""
    xs, ys, test, loss_fn, opt, init_one = setup
    g = T.ring(N)
    faults = crash_burst(g, 3, at=1, size=2, duration=2, seed=0)
    finals = []
    for rendering in ("onehot", "gather"):
        _force(monkeypatch, rendering)
        state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
        finals.append(run_elastic_trajectory(
            state, loss_fn, opt, compile_plan(g), membership_schedule(N, 3),
            xs, ys, _schedule(rounds=3), n_rounds=3, faults=faults,
        )[0])
    _assert_states_bit_equal(*finals)


_SHARDED_RENDERINGS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.core import topology as T
    from repro.core.commplan import FailureModel, compile_plan
    from repro.core.initialisation import InitConfig
    from repro.data import batch_index_schedule, mnist_like, node_datasets
    from repro.fed import batching, init_fl_state, run_sharded_trajectory
    from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
    from repro.obs.trace import counts
    from repro.optim import sgd

    N, PER_NODE, BS, B_LOCAL, ROUNDS = 8, 16, 8, 2, 3
    ds = mnist_like(N * PER_NODE, seed=0)
    xs, ys = node_datasets(ds, [np.arange(i * PER_NODE, (i + 1) * PER_NODE) for i in range(N)])
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])
    opt = sgd(1e-3, 0.5)
    plan = compile_plan(
        T.random_k_regular(N, 4, seed=1), backend="sparse", failures=FailureModel(link_p=0.8)
    ).shard(n_shards=4)
    sched = batch_index_schedule(PER_NODE, N, BS, ROUNDS * B_LOCAL, seed=0)
    finals = []
    for rendering in ("onehot", "gather"):
        batching.select_rendering = lambda *a, r=rendering: r
        s0 = init_fl_state(
            jax.random.PRNGKey(0), N,
            lambda k: init_mlp(InitConfig("he_normal", 2.0), k, hidden=(16,)), opt,
        )
        finals.append(run_sharded_trajectory(
            s0, loss_fn, opt, plan, xs, ys, sched, n_rounds=ROUNDS, b_local=B_LOCAL
        )[0])
    for a, b in zip(*(jax.tree_util.tree_leaves(f.params) for f in finals)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = counts()
    assert c["batch.select_onehot"] == c["batch.select_gather"] == 1, c
    print("SHARDED_RENDERINGS_OK")
    """
)


def test_sharded_params_bit_equal_across_renderings():
    """run_sharded_trajectory on 4 virtual devices, 3 rounds, forced to
    each rendering on every shard's local rows: bit-equal params."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_RENDERINGS],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_RENDERINGS_OK" in out.stdout
