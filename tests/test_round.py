"""The one Algorithm-1 round that every synchronous executor runs.

``fed.trainer.dfl_round`` is the round body of ``make_round_fn`` (hence
``run_trajectory``), of ``run_sharded_trajectory`` and of
``run_elastic_trajectory``.  Each case runs one executor and compares its
round 0 — params and train loss — against ``dfl_round`` called directly
with the same key, batches and plan (link failures on, so the failure key
is exercised):

- ``single``: ``run_trajectory`` over ``make_round_fn``, bit for bit;
- ``sharded``: ``run_sharded_trajectory`` over two shards of forced host
  devices (the ``tests/test_sharded_plan.py`` mesh, in a subprocess):
  params bit for bit; its loss is a ``psum`` of shard sums, another
  summation order (DESIGN.md §15), so within 4 ulp;
- ``elastic``: ``run_elastic_trajectory`` under a membership whose only
  event is a departure in the last round, so it takes its own masked path
  rather than delegating to ``run_trajectory``.  Its mix renormalises over
  the live masks even when every node is live, and its loss averages over
  them: within DESIGN.md §11's tolerance.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import _step_path, load_pytree
from repro.core import topology as T
from repro.core.commplan import FailureModel, compile_plan
from repro.core.initialisation import InitConfig
from repro.core.membership import membership_schedule
from repro.data import batch_index_schedule, mnist_like, node_datasets
from repro.fed import (
    CheckpointPolicy,
    dfl_round,
    init_fl_state,
    make_round_fn,
    run_elastic_trajectory,
    run_sharded_trajectory,
    run_trajectory,
)
from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
from repro.optim import sgd

N, PER_NODE, BS, B_LOCAL = 8, 32, 8, 2
# DESIGN.md §11: losses reduced in another order agree within 4 ulp; the
# elastic mix renormalises over its masks, a different sum
METRIC_ULPS = 4
ELASTIC_RTOL = 1e-5


def _setup():
    ds = mnist_like(N * PER_NODE, seed=0)
    parts = [np.arange(i * PER_NODE, (i + 1) * PER_NODE) for i in range(N)]
    xs, ys = node_datasets(ds, parts)
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])  # noqa: E731
    opt = sgd(1e-3, 0.5)
    init_one = lambda k: init_mlp(InitConfig("he_normal", 2.0), k, hidden=(16,))  # noqa: E731
    plan = compile_plan(
        T.random_k_regular(N, 4, seed=1), backend="sparse", failures=FailureModel(link_p=0.8)
    )
    sched = batch_index_schedule(PER_NODE, N, BS, 2 * B_LOCAL, seed=0)
    state = init_fl_state(jax.random.PRNGKey(0), N, init_one, opt)
    return xs, ys, loss_fn, opt, plan, sched, state


def _direct_round(loss_fn, opt, plan, state, xs, ys, sched):
    """Round 0 by ``dfl_round`` alone: (params, mean train loss)."""
    idx = sched[:B_LOCAL].transpose(1, 0, 2)  # (n, b, bs)
    rows = np.arange(N)[:, None, None]
    batch = (jnp.asarray(xs[rows, idx]), jnp.asarray(ys[rows, idx]))

    @jax.jit
    def one(s, b):
        params, _, _, _, losses, _ = dfl_round(
            loss_fn, opt, lambda x, key: plan.mix(x, key),
            s.params, s.opt_state, s.rng, b, failures=plan.failures,
        )
        return params, losses.mean()

    return one(state, batch)


def _executor_round(executor, loss_fn, opt, plan, state, xs, ys, sched, ckpt_dir):
    """Round 0 as ``executor`` runs it: (params, recorded train loss)."""
    if executor == "single":
        rf = make_round_fn(loss_fn, opt, plan)
        final, hist = run_trajectory(
            state, rf, xs, ys, sched[:B_LOCAL], n_rounds=1, b_local=B_LOCAL, eval_every=1
        )
        return final.params, hist["train_loss"][0]
    if executor == "sharded":
        final, hist = run_sharded_trajectory(
            state, loss_fn, opt, plan.shard(n_shards=2), xs, ys, sched[:B_LOCAL],
            n_rounds=1, b_local=B_LOCAL, eval_every=1,
        )
        return final.params, hist["train_loss"][0]
    # elastic: everyone trains in round 0, node N-1 leaves for round 1; the
    # chunk-0 checkpoint holds the carry after round 0
    run_elastic_trajectory(
        state, loss_fn, opt, plan, membership_schedule(N, 2, departures={1: [N - 1]}),
        xs, ys, sched, n_rounds=2, b_local=B_LOCAL, eval_every=1, chunk_size=1,
        checkpoint=CheckpointPolicy(ckpt_dir, keep_last=2),
    )
    payload, meta = load_pytree(_step_path(ckpt_dir, 0))
    assert meta["chunk"] == 0
    treedef = jax.tree_util.tree_structure(state.params)
    params = jax.tree_util.tree_unflatten(
        treedef, payload["carry"][: treedef.num_leaves]
    )
    return params, float(payload["outs"][0][0][0])


def compare(executor: str, ckpt_dir: str | None = None) -> None:
    xs, ys, loss_fn, opt, plan, sched, state = _setup()
    want_p, want_loss = _direct_round(loss_fn, opt, plan, state, xs, ys, sched)
    got_p, got_loss = _executor_round(
        executor, loss_fn, opt, plan, state, xs, ys, sched, ckpt_dir
    )
    for a, b in zip(jax.tree_util.tree_leaves(want_p), jax.tree_util.tree_leaves(got_p)):
        a, b = np.asarray(a), np.asarray(b)
        if executor == "elastic":
            np.testing.assert_allclose(b, a, rtol=ELASTIC_RTOL, atol=1e-7)
        else:
            np.testing.assert_array_equal(b, a)
    want_loss, got_loss = np.float32(want_loss), np.float32(got_loss)
    if executor == "single":
        assert got_loss == want_loss, (got_loss, want_loss)
    else:
        ulps = abs(int(want_loss.view(np.int32)) - int(got_loss.view(np.int32)))
        assert ulps <= METRIC_ULPS, (got_loss, want_loss)


_SHARDED = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, {tests!r})
    import test_round
    test_round.compare("sharded")
    print("SHARDED_OK")
    """
)


@pytest.mark.parametrize("executor", ["single", "sharded", "elastic"])
def test_executor_runs_the_one_round(executor, tmp_path):
    if executor != "sharded":
        compare(executor, str(tmp_path))
        return
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED.format(tests=here)],
        capture_output=True, text=True, env=env, timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
