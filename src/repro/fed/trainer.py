"""Decentralised federated training loop (paper Algorithm 1).

The node ensemble is *vectorised*: every parameter leaf carries a leading
node axis and all nodes step in one SPMD program (DESIGN.md §2).  One
communication round =

    1. ``b`` local minibatch steps per node        (Algorithm 1 lines 8–10)
    2. DecAvg aggregation over the graph           (line 14, Eq. 2)
    3. optimizer-state re-initialisation           (line 15)

``dfl_round`` is that round, written once: the fused single-chip,
node-sharded and elastic executors all run it, and own only what
surrounds it (carry layout, metric reductions, membership masks).
The round function is model-agnostic: it takes any per-node
``loss_fn(params, batch) -> scalar`` and vmaps it over the node axis.  Under
``jax.jit`` with the node axis sharded over the mesh "data" axis this is the
production training step the dry-run lowers.

Failures (Fig. 2): pass ``link_p``/``node_p`` < 1 and a PRNG key; the
round rebuilds the effective receive matrix on-device each round.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.commplan import CommPlan, FailureModel, PlanSchedule, compile_plan
from repro.core.compress import Compression, compressed_mix_with, init_residuals, mask_rows
from repro.core.topology import Graph
from repro.optim import Optimizer

PyTree = Any
LossFn = Callable[[PyTree, Any], jax.Array]

__all__ = [
    "DFLState",
    "dfl_round",
    "init_fl_state",
    "make_round_fn",
    "make_eval_fn",
    "sigma_metrics",
    "train_loop",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DFLState:
    params: PyTree  # node-stacked: every leaf (n_nodes, ...)
    opt_state: PyTree
    round: jax.Array  # scalar int32
    rng: jax.Array
    # compressed-gossip carry (core.compress, DESIGN.md §18): each node's
    # transmitted mirror, params-shaped fp32.  None (the default) is an
    # *empty* pytree child — zero leaves, so uncompressed states flatten
    # exactly as before and existing checkpoints/scans are untouched.
    residual: PyTree | None = None

    def tree_flatten(self):
        return (self.params, self.opt_state, self.round, self.rng, self.residual), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_fl_state(
    key: jax.Array,
    n_nodes: int,
    init_one: Callable[..., PyTree],
    optimizer: Optimizer,
    gains: jax.Array | np.ndarray | None = None,
) -> DFLState:
    """Uncoordinated init: every node draws independently (distinct keys) —
    the paper's premise w_i ≠ w_j at t=0 (§3).

    ``gains``: optional (n,) per-node init gain vector (or scalar,
    broadcast) — each node's own ``‖v̂_steady‖⁻¹`` from its gossip estimates
    (§4.4, ``repro.gossip``).  When given, ``init_one`` must accept
    ``(key, gain)`` and apply the gain to its random draws (e.g.
    ``lambda k, g: init_mlp(icfg.replace(gain=g), k)``).  Without it the
    single-gain ``init_one(key)`` contract is unchanged.  Fully traceable,
    so the fused warmup can inline estimation → init → training in one
    program (``fed.executor.run_warmup_trajectory``).
    """
    keys = jax.random.split(key, n_nodes + 1)
    if gains is None:
        params = jax.vmap(init_one)(keys[:n_nodes])
    else:
        g = jnp.broadcast_to(jnp.asarray(gains, jnp.float32), (n_nodes,))
        params = jax.vmap(init_one)(keys[:n_nodes], g)
    opt_state = jax.vmap(optimizer.init)(params)
    return DFLState(params=params, opt_state=opt_state, round=jnp.zeros((), jnp.int32), rng=keys[-1])


def _local_steps(
    loss_fn: LossFn, optimizer: Optimizer, params: PyTree, opt_state: PyTree, batches: Any
) -> tuple[PyTree, PyTree, jax.Array]:
    """b sequential minibatch steps for ONE node. batches: leaves (b, ...)."""

    def step(carry, batch):
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        updates, s = optimizer.update(grads, s, p)
        p = jax.tree_util.tree_map(lambda a, u: (a + u.astype(a.dtype)), p, updates)
        return (p, s), loss

    (params, opt_state), losses = jax.lax.scan(step, (params, opt_state), batches)
    return params, opt_state, losses.mean()


def dfl_round(
    loss_fn: LossFn,
    optimizer: Optimizer,
    mix: Callable[[PyTree, jax.Array | None], PyTree] | None,
    params: PyTree,
    opt_state: PyTree,
    rng: jax.Array,
    batches: Any,
    *,
    failures: FailureModel,
    compression: Compression | None = None,
    residual: PyTree | None = None,
    live: jax.Array | None = None,
) -> tuple[PyTree, PyTree, jax.Array, PyTree | None, jax.Array, jax.Array | None]:
    """One round of Algorithm 1 over a node-stacked ensemble — the body that
    ``make_round_fn``, ``run_sharded_trajectory`` and
    ``run_elastic_trajectory`` all run:

    1. split the round's mix key off ``rng``                  (``dfl_round``)
    2. b local minibatch steps per node, lines 8–10           (``dfl_local``)
    3. ``mix(x, key)``, the caller's DecAvg operator, line 14 (``dfl_mix``):
       ``key`` is the round's key under an active ``failures`` model, else
       None.  An active ``compression`` runs the error-feedback delta form
       around it (``compressed_mix_with``), carrying ``residual``.
    4. optimizer re-initialisation, line 15                   (``dfl_reinit``)

    ``mix=None`` trains locally only: no mix, no re-initialisation.
    ``live`` ((n,) bool) freezes the nodes outside it: they keep their
    params, optimizer state and mirror (their local steps are computed and
    discarded — static shapes).  The caller's ``mix`` should treat them as
    identity rows (``active=``).

    Returns ``(params, opt_state, rng, residual, losses, key)``: ``losses``
    per node, and ``key`` the key the mix consumed, so the caller's other
    draws of the round (sketches, wire counts) ride the same failures.
    """
    with jax.named_scope("dfl_round"):
        rng, k_mix = jax.random.split(rng)

    with jax.named_scope("dfl_local"):
        new_p, new_o, losses = jax.vmap(partial(_local_steps, loss_fn, optimizer))(
            params, opt_state, batches
        )
        if live is not None:
            new_p = mask_rows(live, new_p, params)
            new_o = mask_rows(live, new_o, opt_state)
    params, opt_state = new_p, new_o

    key = k_mix if failures.active else None
    if mix is not None:
        with jax.named_scope("dfl_mix"):
            if compression is not None and compression.active:
                params, residual = compressed_mix_with(
                    lambda q: mix(q, key), params, residual, compression,
                    update_mask=live,
                )
            else:
                params = mix(params, key)
        with jax.named_scope("dfl_reinit"):
            fresh = jax.vmap(optimizer.init)(params)
            opt_state = fresh if live is None else mask_rows(live, fresh, opt_state)
    return params, opt_state, rng, residual, losses, key


def make_round_fn(
    loss_fn: LossFn,
    optimizer: Optimizer,
    plan: CommPlan | PlanSchedule | Graph,
    data_sizes: np.ndarray | None = None,
    link_p: float = 1.0,
    node_p: float = 1.0,
    aggregate: bool = True,
    compression: Compression | None = None,
):
    """Build the jittable communication-round function.

    ``plan`` is a compiled ``CommPlan`` (``core.commplan.compile_plan``) or a
    time-varying ``PlanSchedule`` (``compile_schedule``) — the round body
    then mixes with the plan active at ``state.round``, switching operators
    by round index *inside* any enclosing scan (DESIGN.md §13); a raw
    ``Graph`` is accepted for convenience and compiled with the "auto"
    backend.  ``data_sizes``/``link_p``/``node_p`` override the plan's own
    settings when given (the plan is recompiled, cheap and host-side).

    Returns ``round_fn(state, node_batches) -> (state, metrics)`` where
    ``node_batches`` leaves are (n_nodes, b, batch, ...): b local minibatches
    per node per round (Appendix A: b = 8).  The round is :func:`dfl_round`;
    ``aggregate=False`` trains locally only.

    ``compression`` (an active ``core.compress.Compression``) switches the
    aggregation to the error-feedback delta form over the same plan
    operator; the per-node mirror rides ``state.residual`` (seeded lazily
    with zeros when absent — the fused executors seed it before their scan
    so the carry structure is static).  ``compression=None`` or codec
    ``"none"`` leaves the round body *bit-identical* to before.
    """
    failures = FailureModel(link_p=link_p, node_p=node_p)
    if isinstance(plan, Graph):
        plan = compile_plan(plan, backend="auto", data_sizes=data_sizes, failures=failures)
    elif failures.active or data_sizes is not None:
        # override only the knobs actually given: data_sizes alone must not
        # silently replace the plan's own failure model with the inactive one
        plan = plan.with_options(
            data_sizes=data_sizes, failures=failures if failures.active else None
        )
    comp = compression if (aggregate and compression is not None and compression.active) else None

    def round_fn(state: DFLState, node_batches: Any) -> tuple[DFLState, dict]:
        r = state.round

        def mix(x, key):
            return plan.select(r).mix(x, plan.round_key(key, r))

        residual = state.residual
        if comp is not None and residual is None:  # legacy train_loop path (no seeding)
            residual = init_residuals(state.params)
        params, opt_state, rng, residual, losses, _ = dfl_round(
            loss_fn, optimizer, mix if aggregate else None,
            state.params, state.opt_state, state.rng, node_batches,
            failures=plan.failures, compression=comp, residual=residual,
        )
        with jax.named_scope("dfl_round"):
            new_state = DFLState(
                params=params, opt_state=opt_state, round=state.round + 1, rng=rng,
                residual=residual,
            )
            train_loss = losses.mean()
        return new_state, {"train_loss": train_loss, "train_loss_per_node": losses}

    # the *effective* plan (overrides applied) — the executor's wire-cost
    # accountant reads it to count exactly the edges this round_fn mixes over;
    # the compression config rides along for codec-aware byte accounting
    round_fn.plan = plan if aggregate else None
    round_fn.compression = comp
    return round_fn


def make_eval_fn(loss_fn: LossFn, batch_eval: bool = True):
    """Mean test loss of every node's model on the (global) test set —
    the paper's headline observable ("mean test cross-entropy loss")."""

    @jax.jit
    def eval_fn(params: PyTree, test_batch: Any) -> jax.Array:
        per_node = jax.vmap(lambda p: loss_fn(p, test_batch))(params)
        return per_node

    return eval_fn


def sigma_metrics(params: PyTree) -> dict[str, jax.Array]:
    """σ_an / σ_ap over the full node-stacked parameter matrix W (§3).

    σ_ap: mean over nodes of the std across that node's parameters;
    σ_an: mean over parameters of the std across nodes.

    Streaming per-leaf moment accumulation: equivalent to std over the
    concatenated (n, d_total) matrix but never materialises it, so the
    fused executor can run this every eval round on device for free.
    """
    leaves = [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in jax.tree_util.tree_leaves(params)]
    d_total = sum(l.shape[1] for l in leaves)
    # σ_ap: two-pass per-node moments accumulated across leaves
    mean_n = sum(l.sum(axis=1) for l in leaves) / d_total  # (n,)
    var_n = sum(((l - mean_n[:, None]) ** 2).sum(axis=1) for l in leaves) / d_total
    # σ_an: per-parameter std across nodes, reduced leaf by leaf
    an_sum = sum(jnp.std(l, axis=0).sum() for l in leaves)
    return {
        "sigma_ap": jnp.sqrt(var_n).mean(),
        "sigma_an": an_sum / d_total,
    }


def train_loop(
    state: DFLState,
    round_fn,
    batches: Iterable[Any],
    n_rounds: int,
    eval_every: int = 0,
    eval_fn=None,
    eval_batch=None,
    track_sigmas: bool = False,
    progress: bool = False,
) -> tuple[DFLState, dict[str, list]]:
    """Python-level driver (checkpoint hooks etc. live in launch/train.py).

    Legacy per-round-dispatch path; ``repro.fed.executor.run_trajectory`` is
    the fused equivalent (same round_fn, bit-identical results).  Metrics are
    collected as device scalars and converted to floats once at the end, so
    eval rounds no longer block the dispatch pipeline (unless ``progress``
    forces a readback to print).
    """
    jit_round = jax.jit(round_fn)
    jit_sigmas = jax.jit(sigma_metrics)
    history: dict[str, list] = {"round": [], "train_loss": [], "test_loss": [], "sigma_ap": [], "sigma_an": []}
    for r in range(n_rounds):
        state, metrics = jit_round(state, next(batches))
        if eval_every and (r % eval_every == 0 or r == n_rounds - 1):
            history["round"].append(r)
            history["train_loss"].append(metrics["train_loss"])
            if eval_fn is not None:
                tl = eval_fn(state.params, eval_batch)
                history["test_loss"].append(jnp.mean(tl))
            if track_sigmas:
                s = jit_sigmas(state.params)
                history["sigma_ap"].append(s["sigma_ap"])
                history["sigma_an"].append(s["sigma_an"])
            if progress:
                msg = f"round {r:4d} train {float(history['train_loss'][-1]):.4f}"
                if history["test_loss"]:
                    msg += f" test {float(history['test_loss'][-1]):.4f}"
                print(msg, flush=True)
    return state, {
        k: [float(v) if isinstance(v, jax.Array) else v for v in vs]
        for k, vs in history.items()
    }
