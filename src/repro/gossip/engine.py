"""Device-resident gossip estimation engine (paper §4.4) over CommPlan backends.

The paper's *uncoordinated* initialisation needs every node to estimate
``‖v_steady‖`` (or the system size n and a family exponent) from nothing but
neighbour exchanges.  ``core.gossip`` pins those protocols down as a host
numpy reference; this module is the production rendering: jitted,
``lax.scan``-chunked programs that execute over the **same** compiled
``CommPlan`` a training run uses — dense / sparse / ppermute backend, same
sharding rules, and per-edge/per-node failure draws keyed exactly like the
training round's (``CommPlan.round_masks``).  Estimation traffic therefore
rides the same unreliable links as DecAvg itself, which is the whole point
of calling the init "uncoordinated".

One gossip round is ``CommPlan.spread`` — the send-form (column-stochastic,
mass-conserving) transpose of the DecAvg receive operator; for undirected
unit-weight graphs that is exactly the paper's Eq. 3 matrix ``A'``.

Protocols
---------
``push_sum``               (s, w) ratio gossip → every node's estimate of the
                           uniform average of an arbitrary (n, k) payload.
``estimate_size``          n̂ from push-sum of a leader one-hot.
``estimate_mean_degree``   ⟨k⟩ from push-sum of local degrees.
``power_iteration_norm``   ‖v̂_steady‖ per node: power-iterate x ← A'x from
                           x₀ = 1 (mass conservation ⇒ x → n·v), then
                           push-sum the moments [x², 1_leader] so each node
                           normalises n·‖v‖² by its own size estimate.
``estimate_all``           one fused program producing (n̂, ‖v̂‖, ⟨k̂⟩).
``make_gain_estimator``    key → (n,) per-node init gains, jit-closable into
                           the fused estimate→init→train warmup
                           (``fed.executor.run_warmup_trajectory``).

Every per-round failure key is ``fold_in(key, round_index)`` with a global
round counter across phases, so a host reference can replay the exact
Bernoulli sequence (see tests/test_gossip_engine.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.commplan import CommPlan, PlanSchedule, compile_plan, compile_schedule
from repro.core.shardplan import ShardedCommPlan, shard_plan
from repro.core.topology import EventStream, Graph

from .walker import poll_degrees_device

__all__ = [
    "GossipEstimates",
    "as_plan",
    "spread_rounds",
    "push_sum",
    "estimate_size",
    "estimate_size_leaderless",
    "estimate_mean_degree",
    "power_iteration_norm",
    "estimate_all",
    "gains_from_estimates",
    "gain_from_degree_sample",
    "make_gain_estimator",
    "spread_events",
    "push_sum_events",
    "estimate_size_leaderless_events",
]

Plan = CommPlan | PlanSchedule | ShardedCommPlan

_EPS = 1e-30  # guards 1/z before mass from the leader one-hot arrives
# below this, a node's push-sum weight of the leader one-hot is "exactly
# zero up to fp32 underflow": the budget never carried the leader's mass
# there.  Reached nodes hold z ≥ (1/(Δ+1))^rounds ≫ this for any sane
# budget, so the threshold cleanly separates "no estimate yet" from "noisy
# estimate" (see ``reached`` below).
_UNREACHED = 1e-20


def as_plan(graph_or_plan: Graph | Plan, backend: str = "auto") -> Plan:
    """Estimation plans are unit-data-size: Eq. 3 weights, not |D_j|-weighted.

    (Mass conservation — hence push-sum correctness — holds for any
    transposed row-stochastic operator, but the ‖v_steady‖ the *init* needs
    is the stationary vector of the unweighted A', so the engine insists on
    it.)  A ``CommPlan`` / ``PlanSchedule`` is accepted as-is when it
    already qualifies; otherwise its graph(s)/failures are recompiled
    without data sizes.  Over a ``PlanSchedule`` every protocol round rides
    the plan active at that gossip round — estimation happens on the
    *dynamic* graph nodes actually see.
    """
    if isinstance(graph_or_plan, PlanSchedule):
        if graph_or_plan.data_sizes is None:
            return graph_or_plan
        return compile_schedule(
            [p.graph for p in graph_or_plan.plans],
            backend=graph_or_plan.backend,
            failures=graph_or_plan.failures,
            round_map=graph_or_plan.round_map,
        )
    if isinstance(graph_or_plan, ShardedCommPlan):
        # gossip over the node-sharded rendering: estimation's spread /
        # spread_min scans run through the halo-exchange collectives and
        # stay bit-identical to the single-device operator
        sp = graph_or_plan
        if sp.data_sizes is None:
            return sp
        base = compile_plan(sp.graph, backend=sp.backend, failures=sp.failures)
        return shard_plan(base, mesh=sp.mesh, axis=sp.axis)
    if isinstance(graph_or_plan, CommPlan):
        if graph_or_plan.data_sizes is None:
            return graph_or_plan
        # NOT with_options(data_sizes=None): there None means "keep current"
        return compile_plan(
            graph_or_plan.graph,
            backend=graph_or_plan.backend,
            failures=graph_or_plan.failures,
        )
    return compile_plan(graph_or_plan, backend=backend)


def _scan_rounds(
    plan: Plan,
    op: str,
    x0: jax.Array,
    rounds: int,
    key: jax.Array | None,
    round_offset: int,
    trace: bool,
    active: jax.Array | None = None,
):
    """rounds × ``plan.<op>`` as one ``lax.scan``; per-round failure key is
    ``fold_in(key, round_offset + r)`` so phases of a multi-stage protocol
    consume a single global round counter (``round_offset`` may be traced —
    a budget-dependent phase boundary).  Over a ``PlanSchedule`` the round
    index also selects the active plan (and folds its id into the key).
    ``active``, when given, is a traced live-round count ≤ rounds: rounds
    past it are identity (the swept-budget masking — one program shape for
    a whole budget grid, ``fed.executor.run_warmup_sweep``)."""
    if plan.failures.active and key is None:
        raise ValueError("failure model active: gossip needs a PRNG key")
    def body(x, r):
        k = None if key is None else jax.random.fold_in(key, r)
        x1 = getattr(plan.select(r), op)(x, plan.round_key(k, r))
        if active is not None:
            x1 = jnp.where(r - round_offset < active, x1, x)
        return x1, (x1 if trace else None)

    steps = jnp.arange(rounds) + jnp.asarray(round_offset, jnp.int32)
    x, tr = jax.lax.scan(body, jnp.asarray(x0, jnp.float32), steps)
    return (x, tr) if trace else x


def _scan_spread(plan, x0, rounds, key, round_offset, trace, active=None):
    return _scan_rounds(plan, "spread", x0, rounds, key, round_offset, trace, active)


def _scan_spread_min(plan, x0, rounds, key, round_offset, active=None):
    return _scan_rounds(plan, "spread_min", x0, rounds, key, round_offset, False, active)


def spread_rounds(
    plan: Plan | Graph,
    values: jax.Array,
    rounds: int,
    key: jax.Array | None = None,
    *,
    round_offset: int = 0,
    trace: bool = False,
    active: jax.Array | None = None,
):
    """``rounds`` applications of the send operator to an (n,) / (n, k) payload.

    With ``trace=True`` also returns the (rounds, n[, k]) per-round states —
    the raw material of the convergence diagnostics.  ``active`` (a traced
    live-round count) freezes the tail rounds for swept-budget grids.
    """
    return _scan_spread(as_plan(plan), values, rounds, key, round_offset, trace, active)


def push_sum(
    plan: Plan | Graph,
    values: jax.Array,
    rounds: int,
    key: jax.Array | None = None,
    *,
    round_offset: int = 0,
    trace: bool = False,
    active: jax.Array | None = None,
):
    """Kempe push-sum: track (s, w), both spread with the same draws; s/w is
    every node's running estimate of the uniform average (mass conservation
    makes this exact in the limit even under per-round failure draws).

    ``values``: (n,) or (n, k).  Returns per-node averages of that shape;
    with ``trace=True`` returns (estimates, per-round estimates).
    """
    plan = as_plan(plan)
    x = jnp.asarray(values, jnp.float32)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    payload = jnp.concatenate([x, jnp.ones((x.shape[0], 1), jnp.float32)], axis=1)
    out = _scan_spread(plan, payload, rounds, key, round_offset, trace, active)
    payload, tr = out if trace else (out, None)
    ratio = payload[:, :-1] / payload[:, -1:]
    if squeeze:
        ratio = ratio[:, 0]
    if not trace:
        return ratio
    tr_ratio = tr[..., :-1] / tr[..., -1:]
    return ratio, (tr_ratio[..., 0] if squeeze else tr_ratio)


def estimate_size(
    plan: Plan | Graph,
    rounds: int,
    key: jax.Array | None = None,
    *,
    leader: int = 0,
    round_offset: int = 0,
    active: jax.Array | None = None,
) -> jax.Array:
    """Every node's n̂ after ``rounds`` of push-sum of a leader one-hot."""
    plan = as_plan(plan)
    one_hot = jnp.zeros(plan.n, jnp.float32).at[leader].set(1.0)
    avg = push_sum(plan, one_hot, rounds, key, round_offset=round_offset, active=active)
    return 1.0 / jnp.maximum(avg, _EPS)


def estimate_size_leaderless(
    plan: Plan | Graph,
    rounds: int,
    key: jax.Array,
    *,
    n_sketches: int = 32,
    round_offset: int = 0,
    active: jax.Array | None = None,
    return_sketches: bool = False,
):
    """Leaderless n̂ by extrema propagation — **no distinguished node**.

    Every node draws ``n_sketches`` iid Exp(1) values; each round is one
    ``spread_min`` exchange (coordinate-wise min over the live
    neighbourhood, same per-edge failure draws as the concurrent push
    traffic for the same key/round counter).  Once the minima have flooded
    the graph, each coordinate holds the min of n Exp(1) draws ~ Exp(n), so
    ``n̂ = (m-1) / Σ_sketches min`` is the unbiased size estimate (Baquero
    et al.'s extrema propagation; relative noise ≈ 1/√(m-2)).

    Replaces the leader-one-hot pathway of ``estimate_size``: no node is
    special, and the failure mode is graceful — a node that heard nothing
    still averages its own draws to n̂ ≈ 1, i.e. gain ≈ 1, the honest
    no-knowledge default (no ``reached`` bookkeeping needed).

    ``key`` is mandatory (the sketch draws); it splits once into
    (sketch-draw key, per-round failure key).
    """
    plan = as_plan(plan)
    if key is None:
        raise ValueError("estimate_size_leaderless draws sketches: a PRNG key is required")
    k_draw, k_round = jax.random.split(key)
    sketches = jax.random.exponential(k_draw, (plan.n, n_sketches))
    n_hat, mins = _sketch_n_hat(
        plan, sketches, rounds,
        k_round if plan.failures.active else None,
        round_offset, active,
    )
    return (n_hat, mins) if return_sketches else n_hat


def _sketch_n_hat(plan, sketches, rounds, key, round_offset=0, active=None):
    """Shared core of the leaderless estimators: propagate the (n, m) Exp(1)
    sketches by min-exchange and invert the summed minima — (n̂, mins)."""
    mins = _scan_spread_min(plan, sketches, rounds, key, round_offset, active)
    m = sketches.shape[1]
    return (m - 1) / jnp.maximum(mins.sum(axis=1), _EPS), mins


# ------------------------------------------------- event-driven (barrier-free)
def _scan_events(plan: Plan | Graph, op: str, x0: jax.Array, stream: EventStream, key):
    """``stream.envelope`` × ``plan.event_<op>`` as one ``lax.scan``; the
    per-event failure key is ``fold_in(key, event_index)`` — the event
    analogue of the per-round ``fold_in`` discipline, so a host reference
    given the realised keep flags replays the exact sequence.  Padding
    events (edge = -1) are the identity, which is what lets streams of
    different realised lengths share one compiled program.

    Over a K > 1 ``PlanSchedule`` the scan also carries the event *times*:
    each event executes under the plan active in its unit-time window
    (``PlanSchedule.event_stream`` samples streams with per-window edge
    ids) and the window's plan id folds into the per-event failure key
    (``event_key``) — the event-path mirror of ``round_key``, so resampled
    plans draw independent node/link outages."""
    plan = as_plan(plan)
    if isinstance(plan, PlanSchedule) and plan.k == 1:
        # the K = 1 contract: a size-1 schedule IS the static plan
        plan = plan.plans[0]
    if plan.failures.active and key is None:
        raise ValueError("failure model active: event gossip needs a PRNG key")
    edges = jnp.asarray(stream.edges)

    if isinstance(plan, PlanSchedule):
        times = jnp.asarray(stream.times)

        def body(x, inp):
            i, e, t = inp
            k = None if key is None else jax.random.fold_in(key, i)
            return getattr(plan, f"event_{op}")(x, e, t, k), None

        idx = jnp.arange(stream.envelope, dtype=jnp.int32)
        x, _ = jax.lax.scan(body, jnp.asarray(x0, jnp.float32), (idx, edges, times))
        return x

    def body(x, inp):
        i, e = inp
        k = None if key is None else jax.random.fold_in(key, i)
        return getattr(plan, f"event_{op}")(x, e, k), None

    idx = jnp.arange(stream.envelope, dtype=jnp.int32)
    x, _ = jax.lax.scan(body, jnp.asarray(x0, jnp.float32), (idx, edges))
    return x


def spread_events(
    plan: Plan | Graph,
    values: jax.Array,
    stream: EventStream,
    key: jax.Array | None = None,
) -> jax.Array:
    """Apply an ``EventStream`` of pairwise push exchanges to an (n,) / (n, k)
    payload — the barrier-free rendering of ``spread_rounds``: mass is
    conserved event by event, no global round counter exists, and estimation
    progresses exactly as fast as the Poisson clocks fire."""
    return _scan_events(plan, "spread", values, stream, key)


def push_sum_events(
    plan: Plan | Graph,
    values: jax.Array,
    stream: EventStream,
    key: jax.Array | None = None,
) -> jax.Array:
    """Event-driven Kempe push-sum: (s, w) ride the same pairwise exchanges,
    s/w is every node's running average estimate — uncoordinated consensus
    with no synchronisation barrier (numpy reference:
    ``core.gossip.push_sum_events_reference``)."""
    plan = as_plan(plan)
    x = jnp.asarray(values, jnp.float32)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    payload = jnp.concatenate([x, jnp.ones((x.shape[0], 1), jnp.float32)], axis=1)
    out = _scan_events(plan, "spread", payload, stream, key)
    ratio = out[:, :-1] / jnp.maximum(out[:, -1:], _EPS)
    return ratio[:, 0] if squeeze else ratio


def estimate_size_leaderless_events(
    plan: Plan | Graph,
    stream: EventStream,
    key: jax.Array,
    *,
    n_sketches: int = 32,
    return_sketches: bool = False,
):
    """Leaderless n̂ over an event stream — fully uncoordinated estimation:
    no distinguished node *and* no round barrier.  Each node's Exp(1)
    sketches flood by pairwise min exchanges as edge clocks fire
    (``CommPlan.event_spread_min``); the estimator and its failure mode
    (unreached nodes degrade to n̂ ≈ 1 → gain ≈ 1) match
    ``estimate_size_leaderless`` sketch for sketch."""
    plan = as_plan(plan)
    if key is None:
        raise ValueError("estimate_size_leaderless_events draws sketches: a PRNG key is required")
    k_draw, k_event = jax.random.split(key)
    sketches = jax.random.exponential(k_draw, (plan.n, n_sketches))
    mins = _scan_events(
        plan, "spread_min", sketches, stream,
        k_event if plan.failures.active else None,
    )
    n_hat = (n_sketches - 1) / jnp.maximum(mins.sum(axis=1), _EPS)
    return (n_hat, mins) if return_sketches else n_hat


def estimate_mean_degree(
    plan: Plan | Graph,
    rounds: int,
    key: jax.Array | None = None,
    *,
    round_offset: int = 0,
) -> jax.Array:
    plan = as_plan(plan)
    deg = jnp.asarray(plan.graph.degrees, jnp.float32)
    return push_sum(plan, deg, rounds, key, round_offset=round_offset)


@dataclasses.dataclass(frozen=True)
class GossipEstimates:
    """Per-node estimates, every field (n,).  Registered as a pytree so a
    fused program can return it from inside jit.  ``reached`` flags nodes
    the leader's mass actually visited within the budget — estimates at
    un-reached nodes are meaningless (see ``make_gain_estimator``)."""

    n_hat: jax.Array
    vnorm: jax.Array
    mean_degree: jax.Array
    reached: jax.Array

    def tree_flatten(self):
        return (self.n_hat, self.vnorm, self.mean_degree, self.reached), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    GossipEstimates,
    GossipEstimates.tree_flatten,
    GossipEstimates.tree_unflatten,
)


def _centrality_moments(
    plan, pi_rounds, ps_rounds, key, leader, extra=None, active_pi=None, active_ps=None
):
    """Shared two-phase core of the ‖v_steady‖ estimators.

    Phase 1 — power iteration: ``x ← A'x`` from ``x₀ = 1``; A' is
    column-stochastic so ``Σx = n`` is invariant while ``A'^t → v·1ᵀ``, and
    ``x → n·v`` with no explicit normalisation.  Phase 2 — push-sum of the
    payload ``[x², 1_leader, *extra]`` under the continuing round counter
    (``round_offset=pi_rounds``, one failure-key discipline across phases).
    Returns ``(x, avg, reached, z)`` with ``z`` clamp-guarded and
    ``reached`` = the leader's mass actually arrived within the budget.
    ``active_pi``/``active_ps`` are the swept-budget live-round masks; the
    push-sum phase then starts its round counter at the *live* phase-1
    budget, so a masked run consumes exactly the failure draws a genuinely
    ``active``-round estimator would — budget-b sweep cells replay as
    standalone budget-b runs, failures included.
    """
    x = spread_rounds(plan, jnp.ones(plan.n, jnp.float32), pi_rounds, key, active=active_pi)
    one_hot = jnp.zeros(plan.n, jnp.float32).at[leader].set(1.0)
    cols = [x * x, one_hot] + ([extra] if extra is not None else [])
    avg = push_sum(
        plan, jnp.stack(cols, axis=1), ps_rounds, key,
        round_offset=pi_rounds if active_pi is None else active_pi,
        active=active_ps,
    )
    reached = avg[:, 1] > _UNREACHED
    z = jnp.maximum(avg[:, 1], _EPS)
    return x, avg, reached, z


def power_iteration_norm(
    plan: Plan | Graph,
    pi_rounds: int,
    ps_rounds: int,
    key: jax.Array | None = None,
    *,
    leader: int = 0,
    active_pi: jax.Array | None = None,
    active_ps: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """Gossip estimate of ``‖v_steady‖₂`` at every node (two fused phases,
    ``_centrality_moments``): each node normalises its power-iterated
    centrality moment by its own concurrent size estimate —
    ``‖v̂‖ = √(m2·z)``, ``n̂ = 1/z``.  ``reached`` is False where the budget
    never delivered the leader's mass (the estimates there are meaningless;
    downstream gain builders fall back to 1.0).

    Over a ``PlanSchedule`` the iterated operator is the round-indexed
    product of the schedule's send matrices — the centrality of the dynamic
    graph as nodes actually experience it.

    Numpy reference: ``core.gossip.power_iteration_norm_reference`` (parity
    tested across backends, topologies and failure draws).
    """
    plan = as_plan(plan)
    x, avg, reached, z = _centrality_moments(
        plan, pi_rounds, ps_rounds, key, leader,
        active_pi=active_pi, active_ps=active_ps,
    )
    return {
        "vnorm": jnp.sqrt(jnp.maximum(avg[:, 0] * z, 0.0)),
        "n_hat": 1.0 / z,
        "x": x,
        "reached": reached,
    }


def estimate_all(
    plan: Plan | Graph,
    *,
    pi_rounds: int,
    ps_rounds: int,
    key: jax.Array | None = None,
    leader: int = 0,
) -> GossipEstimates:
    """One fused program for the full §4.4 estimate set: the power-iterated
    centrality moment, the leader one-hot and the local degrees all share a
    single push-sum phase (and its failure draws).  Over a ``PlanSchedule``
    the degree payload is the round-0 plan's — what each node locally knows
    when estimation starts."""
    plan = as_plan(plan)
    deg = jnp.asarray(plan.graph.degrees, jnp.float32)
    _, avg, reached, z = _centrality_moments(plan, pi_rounds, ps_rounds, key, leader, extra=deg)
    return GossipEstimates(
        n_hat=1.0 / z,
        vnorm=jnp.sqrt(jnp.maximum(avg[:, 0] * z, 0.0)),
        mean_degree=avg[:, 2],
        reached=reached,
    )


# ------------------------------------------------------------ gains (device)
def gains_from_estimates(
    n_hat: jax.Array,
    vnorm: jax.Array | None = None,
    family_exponent: float | None = None,
) -> jax.Array:
    """Vectorised device mirror of ``core.initialisation.gain_from_estimates``.

    Priority (and argument validation) match the host function: a direct
    ``vnorm`` estimate wins (gain = 1/‖v̂‖, per node); otherwise a family
    exponent α gives ``n̂^α`` (α = 1/2 when omitted — the homogeneous-graph
    assumption of Fig. 5).  Passing both raises, like the host.
    """
    if vnorm is not None and family_exponent is not None:
        raise ValueError(
            "give either a vnorm estimate or a family_exponent, not both — "
            "see core.initialisation.gain_from_estimates for the priority rule"
        )
    if vnorm is not None:
        return 1.0 / jnp.maximum(jnp.asarray(vnorm, jnp.float32), _EPS)
    alpha = 0.5 if family_exponent is None else family_exponent
    return jnp.asarray(n_hat, jnp.float32) ** alpha


def gain_from_degree_sample(n_hat: jax.Array, degree_sample: jax.Array) -> jax.Array:
    """Device mirror of the host degree-sample gain:
    ``‖v‖² ≈ ⟨(k+1)²⟩ / (n̂·⟨k+1⟩²)`` per node, gain = 1/‖v̂‖.

    ``n_hat``: (n,) per-node size estimates; ``degree_sample``: (m,) shared
    or (n, m) per-node polled degrees.  Rounds n̂ like the host path.
    """
    k1 = jnp.asarray(degree_sample, jnp.float32) + 1.0
    m2 = jnp.mean(k1**2, axis=-1)
    m1 = jnp.mean(k1, axis=-1)
    n_r = jnp.round(jnp.asarray(n_hat, jnp.float32))
    vnorm = jnp.sqrt(m2 / (n_r * m1**2))
    return 1.0 / jnp.maximum(vnorm, _EPS)


def make_gain_estimator(
    plan: Plan | Graph,
    *,
    pi_rounds: int,
    ps_rounds: int,
    mode: str = "vnorm",
    family_exponent: float | None = None,
    leader: int = 0,
    walk_length: int = 16,
    n_walks: int = 64,
    leaderless: bool = False,
    n_sketches: int = 32,
) -> Callable[..., jax.Array]:
    """Build the jittable ``(key[, budget]) → (n,) gains`` warmup function.

    Modes (the three §4.4 knowledge regimes):
      ``vnorm``   power-iteration ‖v̂‖ per node → gain = 1/‖v̂‖ (default);
      ``alpha``   size-only: push-sum n̂ → gain = n̂^α;
      ``degree``  push-sum n̂ + per-node on-device random-walk degree polls
                  → closed-form ‖v̂‖ (the Fig. 5 sampled-degree pathway).

    ``leaderless=True`` replaces every leader-one-hot size estimate with the
    exponential-random-minimum sketches (``estimate_size_leaderless``): no
    distinguished node, sketch traffic riding the same per-round failure
    draws as the concurrent push-sum phase, and the ``reached`` fallback
    becomes unnecessary — an unreached node's own sketches already average
    to n̂ ≈ 1, i.e. gain ≈ 1.  ``vnorm`` then normalises the power-iterated
    moment by the sketch n̂ instead of the leader column.

    ``plan`` may be a ``PlanSchedule``: all protocol rounds then follow the
    round-indexed dynamic topology (including the degree walks).

    The returned callable is pure jax — ``fed.executor.run_warmup_trajectory``
    closes over it so estimate → per-node gain → init → train compiles as
    one program with no host round-trip.  Its optional second argument is a
    *traced* gossip budget (live rounds per phase, ≤ the static
    ``pi_rounds``/``ps_rounds``): build one estimator at the grid's max
    budget and ``fed.executor.run_warmup_sweep`` vmaps a whole
    (budget × seed) grid through one program.

    Budget under-runs (leader pathways): a node the leader's mass never
    reached within ``ps_rounds`` has *no* size estimate (its push-sum weight
    is exactly zero); naively inverting the clamp would hand it an
    astronomically wrong gain that silently NaNs training.  Such nodes fall
    back to gain = 1.0 — the honest no-knowledge default (unscaled He),
    which is exactly what an uncoordinated node that heard nothing would
    use.
    """
    plan = as_plan(plan)
    if mode not in ("vnorm", "alpha", "degree"):
        raise ValueError(f"unknown gain estimator mode {mode!r}")
    if mode == "vnorm" and family_exponent is not None:
        raise ValueError("family_exponent only applies to mode='alpha'")
    scheduled = isinstance(plan, PlanSchedule)

    def estimate_gains(
        key: jax.Array | None, budget: jax.Array | None = None
    ) -> jax.Array:
        if leaderless:
            if key is None:
                raise ValueError("leaderless estimation draws sketches: key required")
            k_sketch, key = jax.random.split(key)
        k_gossip, k_walk = (
            (None, None) if key is None else tuple(jax.random.split(key))
        )

        def sketch_size(rounds, round_offset=0, active=None):
            # ride the SAME per-round keys (hence failure draws) as the
            # concurrent push-sum phase: fold the phase key stream
            sketches = jax.random.exponential(k_sketch, (plan.n, n_sketches))
            n_hat, _ = _sketch_n_hat(
                plan, sketches, rounds,
                k_gossip if plan.failures.active else None,
                round_offset, active,
            )
            return n_hat

        if mode == "vnorm":
            if leaderless:
                x = spread_rounds(
                    plan, jnp.ones(plan.n, jnp.float32), pi_rounds, k_gossip,
                    active=budget,
                )
                # phase 2's round counter starts at the LIVE phase-1 budget,
                # like _centrality_moments: masked ≡ standalone budget run
                offset2 = pi_rounds if budget is None else budget
                m2 = push_sum(
                    plan, (x * x)[:, None], ps_rounds, k_gossip,
                    round_offset=offset2, active=budget,
                )[:, 0]
                n_hat = sketch_size(ps_rounds, round_offset=offset2, active=budget)
                vnorm = jnp.sqrt(jnp.maximum(m2 / jnp.maximum(n_hat, 1.0), 0.0))
                return gains_from_estimates(n_hat, vnorm=vnorm)
            est = power_iteration_norm(
                plan, pi_rounds, ps_rounds, k_gossip, leader=leader,
                active_pi=budget, active_ps=budget,
            )
            gains = gains_from_estimates(est["n_hat"], vnorm=est["vnorm"])
            reached = est["reached"]
        else:
            if leaderless:
                n_hat = sketch_size(ps_rounds, active=budget)
                reached = None
            else:
                n_hat = estimate_size(
                    plan, ps_rounds, k_gossip, leader=leader, active=budget
                )
                reached = n_hat < 1.0 / _UNREACHED
            if mode == "alpha":
                gains = gains_from_estimates(n_hat, family_exponent=family_exponent)
            else:
                if k_walk is None:
                    k_walk = jax.random.PRNGKey(0)
                sample = poll_degrees_device(
                    plan.graph,
                    np.arange(plan.n),  # static start set: every node polls itself
                    walk_length=walk_length,
                    n_walks=n_walks,
                    key=k_walk,
                    plan=plan,  # walks ride the same failure draws as training
                )
                gains = gain_from_degree_sample(n_hat, sample)
            if reached is None:
                return gains
        return jnp.where(reached, gains, 1.0)

    return estimate_gains
