"""CommPlan: compile a ``Graph`` into an executable mixing backend.

The paper's dynamics depend only on the communication network's *structure*
(eigenvector centralities, degrees, spectral gap), but how a round of DecAvg
*executes* on hardware is a separate engineering choice.  ``compile_plan``
makes that choice a config knob: it lowers a ``Graph`` (+ optional per-node
data sizes + a failure model) into one of three interchangeable backends, all
implementing Eq. 2 exactly (DESIGN.md §3):

``dense``     the (n, n) receive-matrix einsum — reference semantics, any
              topology, O(n²·d); the paper-faithful baseline.
``sparse``    CSR/edge-list gather + ``segment_sum`` scatter — O(E·d), makes
              n in the thousands tractable; ``repro.kernels.mix.sparse``
              holds a blocked block-sparse Pallas kernel of the same
              contraction, which no plan dispatches to.
``ppermute``  greedy edge colouring → each colour class is a matching = one
              ``ppermute`` round inside ``shard_map``; moves degree·|w| bytes
              per node instead of n·|w|.  Generalises the circulant-only
              schedule to arbitrary static undirected graphs.

Failure semantics are uniform across backends: one Bernoulli(link_p) draw per
*undirected edge* (both endpoints agree by construction — the draw is keyed
on the edge's index in ``Graph.edge_list()``) and one Bernoulli(node_p) per
node; the effective receive operator renormalises over the surviving
neighbourhood.  Identical keys therefore give identical effective operators
on every backend, which is what the parity property tests assert.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .compress import Compression, compressed_mix, compressed_spread, init_residuals
from .decavg import (
    MIX_PRECISION,
    mix_pytree,
    mix_pytree_colored,
    mix_pytree_hyb,
    mix_pytree_pairwise,
    mix_pytree_pairwise_batch,
    mix_pytree_sparse,
    spread_min_pairwise,
    spread_pairwise,
)
from .mixing import receive_matrix
from .topology import Graph

PyTree = Any

__all__ = [
    "BACKENDS",
    "CommPlan",
    "FailureModel",
    "PlanSchedule",
    "RoundMap",
    "auto_backend",
    "compile_plan",
    "compile_schedule",
    "cyclic_map",
    "sequence_map",
]

BACKENDS = ("dense", "sparse", "ppermute")

# On a TPU the dense mix is one MXU contraction per leaf, O(n²·d), and the
# sparse mix a gather and scatter of every directed edge row, O((nnz + n)·d).
# Per unit of d the sparse form costs this many times the dense form's n²
# term (fitted by a sweep of both on a TPU v5e, PERF.md §6): "auto" goes
# dense while n² ≤ _TPU_DENSE_KAPPA · (nnz + n).
_TPU_DENSE_KAPPA = 300


def auto_backend(platform: str, n: int, nnz: int) -> str:
    """The backend ``backend="auto"`` resolves to for ``n`` nodes joined by
    ``nnz`` directed (receive) edges on ``platform`` (``jax.default_backend()``).

    On the TPU, dense wherever its (n, n) contraction costs less than the
    sparse edge gather/scatter; elsewhere dense up to n = 64, the crossover
    the CPU mixing sweep measured (DESIGN.md §3)."""
    if platform == "tpu":
        return "dense" if n * n <= _TPU_DENSE_KAPPA * (nnz + n) else "sparse"
    return "dense" if n <= 64 else "sparse"


def _resolve_auto(n: int, nnz: int) -> str:
    """``auto_backend`` on the default platform, counted in ``repro.obs.trace``."""
    from repro.obs.trace import count  # local import: repro.obs builds on CommPlan

    backend = auto_backend(jax.default_backend(), n, nnz)
    count(f"commplan.auto_{backend}")
    return backend


def _draw_failure_masks(
    failures: "FailureModel", n_edges: int, n: int, key: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(edge_keep (n_edges,), node_active (n,)) — the uniform failure draw.

    Shared by ``CommPlan`` (width = the plan's own edge count) and
    ``PlanSchedule`` (width = the schedule's shared edge *envelope*, so the
    draw shape is static while the active plan varies by round)."""
    k_link, k_node = jax.random.split(key)
    if failures.link_p < 1.0:
        edge_keep = jax.random.uniform(k_link, (max(n_edges, 1),)) < failures.link_p
    else:
        edge_keep = jnp.ones((max(n_edges, 1),), dtype=bool)
    if failures.node_p < 1.0:
        active = jax.random.bernoulli(k_node, failures.node_p, (n,))
    else:
        active = jnp.ones((n,), dtype=bool)
    return edge_keep, active


@dataclasses.dataclass(frozen=True)
class FailureModel:
    """Per-round Bernoulli link/node survival probabilities (paper §4.1)."""

    link_p: float = 1.0
    node_p: float = 1.0

    @property
    def active(self) -> bool:
        return self.link_p < 1.0 or self.node_p < 1.0


@dataclasses.dataclass(frozen=True)
class CommPlan:
    """A compiled, backend-specific execution plan for one DecAvg round.

    Produced by ``compile_plan``; all array fields are device arrays ready to
    be closed over by a jitted round function.  ``mix(params, key)`` is the
    single entry point every consumer dispatches through; ``key`` is required
    iff ``failures.active``.
    """

    graph: Graph
    backend: str
    failures: FailureModel
    data_sizes: np.ndarray | None
    # ---- dense ----
    receive: jax.Array | None = None  # (n, n) static row-stochastic operator
    adjacency: jax.Array | None = None  # (n, n) original adjacency
    edge_uid_matrix: jax.Array | None = None  # (n, n) int32 undirected edge ids
    # ---- sparse (CSR receive order, dst-sorted) ----
    src: jax.Array | None = None  # (nnz,) int32
    dst: jax.Array | None = None  # (nnz,) int32
    edge_uid: jax.Array | None = None  # (nnz,) int32 → undirected edge index
    edge_w: jax.Array | None = None  # (nnz,) statically normalised weights
    self_w: jax.Array | None = None  # (n,) statically normalised self weights
    raw_edge_w: jax.Array | None = None  # (nnz,) unnormalised A[dst,src]·s[src]
    raw_self_w: jax.Array | None = None  # (n,) unnormalised s
    # ---- sparse HYB layout (static-topology fast path) ----
    slot_idx: jax.Array | None = None  # (S, n) int32, self-padded
    slot_w: jax.Array | None = None  # (S, n) statically normalised
    hyb_self_w: jax.Array | None = None  # (n,), 0 at hub rows
    hub_rows: jax.Array | None = None  # (H,) int32
    hub_m: jax.Array | None = None  # (H, n) dense receive rows incl. self
    # ---- ppermute / colored ----
    partners: np.ndarray | None = None  # (n_colors, n) static int32
    color_edge_uid: jax.Array | None = None  # (n_colors, n) int32, -1 unmatched
    color_w: jax.Array | None = None  # (n_colors, n) statically normalised
    color_raw_w: jax.Array | None = None  # (n_colors, n) unnormalised
    # ---- event-driven (asynchronous) rendering, undirected plans only ----
    event_uv: jax.Array | None = None  # (max(n_edges,1), 2) int32 endpoints
    event_w: jax.Array | None = None  # (max(n_edges,1), 2) [M[u,v], M[v,u]]
    n_edges: int = 0  # undirected edge count (failure draw width)

    @property
    def n(self) -> int:
        return self.graph.n

    @property
    def n_colors(self) -> int:
        return 0 if self.partners is None else self.partners.shape[0]

    # --------------------------------------------------- one-plan schedule
    # A CommPlan is the K = 1 schedule: these members mirror PlanSchedule's
    # with identity meaning, so a caller indexes any plan by round —
    # ``plan.select(r).<op>(x, plan.round_key(key, r))`` — without asking
    # its type.
    @property
    def plans(self) -> tuple["CommPlan", ...]:
        return (self,)

    def select(self, round_index=None) -> "CommPlan":
        return self

    def round_key(self, key: jax.Array | None, round_index=None) -> jax.Array | None:
        return key

    # ------------------------------------------------------------- execution
    def _masked(self, active, edge_live) -> bool:
        """Does this round need the renormalising masked path?  True when the
        failure model is active OR a deterministic membership/fault mask was
        supplied — the static fast paths (precomputed weights, HYB) encode
        the all-alive operator and must not serve masked rounds."""
        return self.failures.active or active is not None or edge_live is not None

    def mix(
        self,
        params: PyTree,
        key: jax.Array | None = None,
        *,
        active: jax.Array | None = None,
        edge_live: jax.Array | None = None,
        compression: Compression | None = None,
        residual: PyTree | None = None,
    ) -> PyTree:
        """One DecAvg aggregation of a node-stacked pytree.

        With ``compression`` (an active :class:`repro.core.compress
        .Compression` codec) the round runs the error-feedback delta form
        over this same operator and returns ``(mixed, new_residual)``
        instead — thread ``residual`` from the previous round (omitted:
        zeros).  Codec ``"none"``/``compression=None`` is the raw operator,
        bit-identical to the uncompressed path.

        Jit-friendly: ``self`` is closed over as compile-time constants, only
        ``params``/``key``/masks are traced.  ``active`` ((n,) bool) and
        ``edge_live`` ((n_edges,) bool, ``Graph.edge_list()`` order) are
        deterministic membership / fault-injection masks AND-composed with
        the Bernoulli failure draws: a masked-out node's row renormalises to
        the identity (it keeps its own model and nobody receives from it),
        exactly like a node the failure draw dropped.  The ``ppermute``
        backend here executes its colour schedule as node-axis gathers
        (single-process semantics); use ``color_round_weights`` +
        ``decavg.mix_pytree_colored`` inside ``shard_map`` for the true
        collective rendering (see launch/steps.py).
        """
        if self.failures.active and key is None:
            raise ValueError("failure model active: mix() needs a PRNG key")
        if compression is not None and compression.active:
            return compressed_mix(
                self,
                params,
                residual if residual is not None else init_residuals(params),
                key,
                compression=compression,
                active=active,
                edge_live=edge_live,
            )
        if self.backend == "dense":
            return mix_pytree(self._dense_round_matrix(key, active, edge_live), params)
        if self.backend == "sparse":
            if not self._masked(active, edge_live) and self.slot_idx is not None:
                # static topology: HYB layout (ELL slot chain + dense hub
                # rows) — the fused-gather rendering that beats the dense
                # einsum on CPU.  Failure/masked rounds renormalise per-edge,
                # so they take the segment_sum formulation below.
                return mix_pytree_hyb(
                    params, self.slot_idx, self.slot_w, self.hyb_self_w,
                    self.hub_rows, self.hub_m,
                )
            edge_w, self_w = self._sparse_round_weights(key, active, edge_live)
            return mix_pytree_sparse(params, self.src, self.dst, edge_w, self_w)
        color_w, self_w = self.color_round_weights(key, active, edge_live)
        return mix_pytree_colored(params, self.partners, color_w, self_w)

    def spread(
        self,
        values: jax.Array,
        key: jax.Array | None = None,
        *,
        active: jax.Array | None = None,
        edge_live: jax.Array | None = None,
        compression: Compression | None = None,
        residual: jax.Array | None = None,
    ) -> jax.Array:
        """One *send-form* (column-stochastic) round: ``values ← Mᵀ values``.

        With an active ``compression`` codec the round runs the delta form
        ``v + Mᵀ C(v + r) − C(v + r)`` and returns ``(values, residual)`` —
        mass-conserving for ANY codec because ``Mᵀ`` is column-stochastic
        (see ``core.compress.compressed_spread``).

        ``mix`` applies the row-stochastic receive operator ``M`` (Eq. 2);
        ``spread`` applies its transpose — column-stochastic, hence
        mass-conserving (``values.sum(0)`` is invariant), which is the
        property push-sum gossip needs (``repro.gossip``, paper §4.4).  For
        undirected graphs with unit data sizes ``Mᵀ`` *is* the paper's
        mixing matrix ``A'`` of Eq. 3: node j keeps ``1/(k_j+1)`` of its
        mass and pushes ``1/(k_j+1)`` along each live edge.

        Same backends, same sharding rules and — crucially — the same
        per-edge/per-node failure draws *and* membership masks as ``mix``
        for the same arguments: estimation traffic rides exactly the links
        training rides.  Because the masked ``M`` keeps every row summing
        to 1 (masked-out rows renormalise to the identity), ``Mᵀ`` stays
        column-stochastic: total mass is conserved under any mask.

        ``values``: (n,) or (n, k) float payload.  Returns the same shape.
        """
        if self.failures.active and key is None:
            raise ValueError("failure model active: spread() needs a PRNG key")
        if compression is not None and compression.active:
            return compressed_spread(
                self,
                values,
                residual if residual is not None else jnp.zeros(
                    jnp.shape(values), jnp.float32
                ),
                key,
                compression=compression,
                active=active,
                edge_live=edge_live,
            )
        x = jnp.asarray(values, jnp.float32)
        squeeze = x.ndim == 1
        if squeeze:
            x = x[:, None]
        if self.backend == "dense":
            m = self._dense_round_matrix(key, active, edge_live)
            out = jnp.einsum("ji,jk->ik", m, x, precision=MIX_PRECISION)
        elif self.backend == "sparse":
            edge_w, self_w = self._sparse_round_weights(key, active, edge_live)
            contrib = edge_w[:, None] * x[self.dst]
            # self term first, then edges (decavg.mix_pytree_sparse)
            out = (self_w[:, None] * x).at[self.src].add(contrib)
        else:
            color_w, self_w = self.color_round_weights(key, active, edge_live)
            partners = jnp.asarray(self.partners)
            sends = color_w[:, :, None] * x[None, :, :]  # (n_colors, n, k)
            # node j receives what its colour-c partner sent: partners is an
            # involution per colour, so gathering sends at partners[c] lands
            # each edge's mass on the opposite endpoint.
            recv = sends[jnp.arange(self.n_colors)[:, None], partners]
            out = self_w[:, None] * x + recv.sum(axis=0)
        return out[:, 0] if squeeze else out

    def spread_min(
        self,
        values: jax.Array,
        key: jax.Array | None = None,
        *,
        active: jax.Array | None = None,
        edge_live: jax.Array | None = None,
    ) -> jax.Array:
        """One round of neighbourhood **min**-exchange over the live links.

        ``out[i] = min(values[i], min over i's surviving neighbourhood)`` —
        the transport the leaderless exponential-random-minimum size sketches
        ride (``repro.gossip.estimate_size_leaderless``): extrema propagate
        through exactly the per-edge/per-node failure draws and membership
        masks that ``mix`` / ``spread`` consume for the same arguments, so
        sketch traffic shares training's links round for round.  Receive
        orientation (row i's neighbours); for the undirected graphs the init
        math assumes this is symmetric.

        ``values``: (n,) or (n, k) float payload.  Returns the same shape.
        """
        if self.failures.active and key is None:
            raise ValueError("failure model active: spread_min() needs a PRNG key")
        x = jnp.asarray(values, jnp.float32)
        squeeze = x.ndim == 1
        if squeeze:
            x = x[:, None]
        inf = jnp.float32(jnp.inf)
        masked = self._masked(active, edge_live)
        if masked:
            edge_keep, node_act = self._round_masks_ext(key, active, edge_live)
        if self.backend == "dense":
            keep = self.adjacency > 0
            if masked:
                keep = keep & edge_keep[self.edge_uid_matrix]
                keep = keep & node_act[:, None] & node_act[None, :]
            nbr = jnp.where(keep[:, :, None], x[None, :, :], inf).min(axis=1)
        elif self.backend == "sparse":
            if masked:
                keep = edge_keep[self.edge_uid] & node_act[self.src] & node_act[self.dst]
                gathered = jnp.where(keep[:, None], x[self.src], inf)
            else:
                gathered = x[self.src]
            nbr = jax.ops.segment_min(
                gathered, self.dst, num_segments=self.n, indices_are_sorted=True
            )
        else:
            partners = jnp.asarray(self.partners)
            keep = self.color_edge_uid >= 0
            if masked:
                keep = keep & edge_keep[jnp.clip(self.color_edge_uid, 0, None)]
                keep = keep & node_act[None, :] & jnp.take(node_act, partners)
            cand = x[partners]  # (n_colors, n, k)
            nbr = jnp.where(keep[:, :, None], cand, inf).min(axis=0)
        out = jnp.minimum(x, nbr)
        return out[:, 0] if squeeze else out

    # ------------------------------------------------- event-driven execution
    def event_keep(self, key: jax.Array) -> jax.Array:
        """Bool scalar: did this event's exchange survive the failure model?

        The asynchronous analogue of ``round_masks``: one Bernoulli(link_p)
        for the firing edge plus one Bernoulli(node_p) per endpoint, drawn
        from the per-event key (callers fold the event index in, mirroring
        the per-round ``fold_in`` discipline).  A failed draw makes the
        *exchange* a no-op — no model moves, no message counts; the event
        executor's endpoints still wake for their local phase, exactly like
        failed-link nodes keep training in a synchronous round."""
        k_link, k_node = jax.random.split(key)
        keep = jnp.bool_(True)
        if self.failures.link_p < 1.0:
            keep = keep & (jax.random.uniform(k_link) < self.failures.link_p)
        if self.failures.node_p < 1.0:
            act = jax.random.bernoulli(k_node, self.failures.node_p, (2,))
            keep = keep & act[0] & act[1]
        return keep

    def _event_edge(self, edge, key: jax.Array | None):
        """(u, v, w_uv, w_vu) of one event; padding (edge = -1) and failed
        draws carry exactly-zero weights, i.e. the identity update."""
        if self.event_uv is None:
            raise ValueError(
                "event rendering needs an undirected CommPlan "
                "(directed plans have no event tables)"
            )
        if self.failures.active and key is None:
            raise ValueError("failure model active: event ops need a PRNG key")
        e = jnp.asarray(edge, jnp.int32)
        live = e >= 0
        if self.failures.active:
            live = live & self.event_keep(key)
        e0 = jnp.maximum(e, 0)
        w = self.event_w[e0] * live
        return self.event_uv[e0, 0], self.event_uv[e0, 1], w[0], w[1], live

    def event_mix(self, params: PyTree, edge, key: jax.Array | None = None) -> PyTree:
        """One asynchronous DecAvg event: edge ``edge``'s endpoints blend with
        the plan's receive weights (``w_u ← w_u + M[u,v]·(w_v − w_u)`` and
        symmetrically), everyone else untouched.  ``edge`` is a traced int32
        index into ``Graph.edge_list()``; -1 (the event-stream padding) is
        the identity.  Composing one event per edge reproduces ``mix`` to
        first order in the weights — the rate-1 parity property the event
        tests pin down."""
        u, v, w_uv, w_vu, _ = self._event_edge(edge, key)
        return mix_pytree_pairwise(params, u, v, w_uv, w_vu)

    def event_mix_batch(
        self, params: PyTree, edges, keys: jax.Array | None = None
    ) -> PyTree:
        """One **colour step**: a batch of simultaneous asynchronous events
        on endpoint-disjoint edges (``topology.batch_events_by_color``),
        applied as a single vectorised gather + scatter-add instead of W
        sequential pairwise updates — the ROADMAP §14 batching that recovers
        matmul-shaped work on the event path.

        ``edges``: (W,) traced int32 edge ids, -1 padding = identity.
        ``keys``: (W,) batch of *per-event* keys (``fold_in(base, i)`` with
        each event's original stream index), required iff failures are
        active — the failure draws are then bit-identical to replaying the
        same events through sequential ``event_mix``.
        """
        if self.event_uv is None:
            raise ValueError(
                "event rendering needs an undirected CommPlan "
                "(directed plans have no event tables)"
            )
        if self.failures.active and keys is None:
            raise ValueError("failure model active: event_mix_batch needs per-event keys")
        e = jnp.asarray(edges, jnp.int32)
        live = e >= 0
        if self.failures.active:
            live = live & jax.vmap(self.event_keep)(keys)
        e0 = jnp.maximum(e, 0)
        w = self.event_w[e0] * live[:, None]
        u, v = self.event_uv[e0, 0], self.event_uv[e0, 1]
        return mix_pytree_pairwise_batch(params, u, v, w[:, 0], w[:, 1])

    def event_spread(self, values: jax.Array, edge, key: jax.Array | None = None) -> jax.Array:
        """One asynchronous **push** event — the pairwise, mass-conserving
        rendering of ``spread`` (``s_u ← s_u − M[u,v]·s_u + M[v,u]·s_v``, and
        symmetrically): ``values.sum(0)`` is invariant event by event, which
        is what barrier-free push-sum estimation rides."""
        u, v, w_uv, w_vu, _ = self._event_edge(edge, key)
        x = jnp.asarray(values, jnp.float32)
        return spread_pairwise(x, u, v, w_uv, w_vu)

    def event_spread_min(self, values: jax.Array, edge, key: jax.Array | None = None) -> jax.Array:
        """One asynchronous **min** event: both endpoints take the
        coordinate-wise minimum over the live exchange — the event transport
        of the leaderless size sketches."""
        u, v, _, _, live = self._event_edge(edge, key)
        x = jnp.asarray(values, jnp.float32)
        return spread_min_pairwise(x, u, v, live)

    # ----------------------------------------------------- per-round weights
    def round_masks(self, key: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Public alias of the per-round failure draws, for host-side
        references that must key their Bernoullis identically (parity tests,
        ``core.gossip.effective_send_matrix``)."""
        return self._edge_node_masks(key)

    def _edge_node_masks(self, key: jax.Array) -> tuple[jax.Array, jax.Array]:
        """(edge_keep (n_edges,), node_active (n,)) — shared across backends."""
        return _draw_failure_masks(self.failures, self.n_edges, self.n, key)

    def _round_masks_ext(
        self, key: jax.Array | None, active, edge_live
    ) -> tuple[jax.Array, jax.Array]:
        """Bernoulli failure draws AND-composed with the deterministic
        membership / fault-injection masks.  ``edge_live`` shorter than the
        draw width (e.g. a plan's own edge count under a schedule envelope)
        pads with True — padding edges carry zero weight anyway."""
        if self.failures.active:
            edge_keep, node_act = self._edge_node_masks(key)
        else:
            edge_keep = jnp.ones((max(self.n_edges, 1),), dtype=bool)
            node_act = jnp.ones((self.n,), dtype=bool)
        if edge_live is not None:
            el = jnp.asarray(edge_live, dtype=bool)
            if el.shape[0] < edge_keep.shape[0]:
                el = jnp.pad(el, (0, edge_keep.shape[0] - el.shape[0]), constant_values=True)
            edge_keep = edge_keep & el[: edge_keep.shape[0]]
        if active is not None:
            node_act = node_act & jnp.asarray(active, dtype=bool)
        return edge_keep, node_act

    def _dense_round_matrix(
        self, key: jax.Array | None, active=None, edge_live=None
    ) -> jax.Array:
        if not self._masked(active, edge_live):
            return self.receive
        edge_keep, node_act = self._round_masks_ext(key, active, edge_live)
        keep = edge_keep[self.edge_uid_matrix] & (self.adjacency > 0)
        keep = keep & node_act[:, None] & node_act[None, :]
        a = self.adjacency * keep
        sizes = None if self.data_sizes is None else jnp.asarray(self.data_sizes, jnp.float32)
        b = a.astype(jnp.float32) + jnp.eye(self.n, dtype=jnp.float32)
        if sizes is not None:
            b = b * sizes[None, :]
        return b / b.sum(axis=1, keepdims=True)

    def _sparse_round_weights(
        self, key: jax.Array | None, active=None, edge_live=None
    ) -> tuple[jax.Array, jax.Array]:
        if not self._masked(active, edge_live):
            return self.edge_w, self.self_w
        edge_keep, node_act = self._round_masks_ext(key, active, edge_live)
        keep = edge_keep[self.edge_uid] & node_act[self.src] & node_act[self.dst]
        num = self.raw_edge_w * keep
        den = self.raw_self_w.at[self.dst].add(num, indices_are_sorted=True)
        return num / den[self.dst], self.raw_self_w / den

    def color_round_weights(
        self, key: jax.Array | None, active=None, edge_live=None
    ) -> tuple[jax.Array, jax.Array]:
        """((n_colors, n), (n,)) normalised weights for this round's schedule."""
        if not self._masked(active, edge_live):
            return self.color_w, self.self_w
        edge_keep, node_act = self._round_masks_ext(key, active, edge_live)
        matched = self.color_edge_uid >= 0
        keep = matched & edge_keep[jnp.clip(self.color_edge_uid, 0, None)]
        partners = jnp.asarray(self.partners)
        keep = keep & node_act[None, :] & jnp.take(node_act, partners)
        num = self.color_raw_w * keep
        den = self.raw_self_w + num.sum(axis=0)
        return num / den[None, :], self.raw_self_w / den

    def color_perms(self) -> list[list[tuple[int, int]]]:
        """Static ppermute (src, dst) pair lists, one per colour class."""
        perms = []
        for c in range(self.n_colors):
            p = self.partners[c]
            perms.append([(i, int(p[i])) for i in range(self.n) if p[i] != i])
        return perms

    # ------------------------------------------------------------- plumbing
    def with_options(
        self,
        *,
        backend: str | None = None,
        data_sizes: np.ndarray | None = None,
        failures: FailureModel | None = None,
    ) -> "CommPlan":
        """Recompile this plan with some knobs replaced."""
        return compile_plan(
            self.graph,
            backend=backend or self.backend,
            data_sizes=self.data_sizes if data_sizes is None else data_sizes,
            failures=failures or self.failures,
        )

    def shard(self, *, mesh=None, axis: str | None = None, n_shards: int | None = None):
        """Render this plan over a node-sharded mesh axis (DESIGN.md §15) —
        see ``core.shardplan.shard_plan`` for the partition contract."""
        from .shardplan import shard_plan  # local import: shardplan builds on CommPlan

        return shard_plan(self, mesh=mesh, axis=axis, n_shards=n_shards)


def _event_tables(graph: Graph, sizes: np.ndarray | None) -> dict:
    """Per-edge endpoint/weight tables of the event-driven rendering.

    ``event_uv[e] = (u, v)`` in ``Graph.edge_list()`` order and
    ``event_w[e] = (M[u, v], M[v, u])`` — the synchronous receive operator's
    entries, so one event per edge composes to one synchronous round to
    first order.  Padded to at least one row so a traced clamp-to-0 gather
    stays in bounds on edgeless graphs.  Directed graphs get no tables
    (a pairwise exchange has no orientation to respect).
    """
    if graph.directed:
        return {}
    edges = graph.edge_list()
    if len(edges) == 0:
        return dict(
            event_uv=jnp.zeros((1, 2), jnp.int32),
            event_w=jnp.zeros((1, 2), jnp.float32),
        )
    m = receive_matrix(graph, sizes)
    u, v = edges[:, 0], edges[:, 1]
    return dict(
        event_uv=jnp.asarray(edges),
        event_w=jnp.asarray(np.stack([m[u, v], m[v, u]], axis=1), jnp.float32),
    )


def _hyb_layout(
    graph: Graph,
    indptr: np.ndarray,
    src: np.ndarray,
    raw_edge: np.ndarray,
    s: np.ndarray,
    den: np.ndarray,
) -> dict:
    """Compile the sparse backend's HYB layout (ELL slots + dense hub rows).

    Degree-threshold heuristic: each ELL slot costs one fused full-length
    gather pass over the (n, d) ensemble, each hub row one (1, n)·(n, d)
    matmul row; measured on CPU a hub row costs about a sixth of a slot
    pass, so minimise ``n_slots(t) + n_hub(t)/6`` over thresholds t.
    Heavy-tail hubs land in the dense part (a complete graph compiles to
    "all hub" = the dense einsum, which is indeed optimal there).
    """
    n = graph.n
    deg = np.diff(indptr)
    candidates = sorted(set(deg.tolist()) | {0})
    cost = lambda t: min(t, int(deg[deg <= t].max()) if (deg <= t).any() else 0) + (deg > t).sum() / 6.0
    t = min(candidates, key=cost)
    hub = np.nonzero(deg > t)[0].astype(np.int32)
    n_slots = int(deg[deg <= t].max()) if (deg <= t).any() else 0
    slot_idx = np.tile(np.arange(n, dtype=np.int32)[None, :], (n_slots, 1))
    slot_w = np.zeros((n_slots, n), np.float64)
    is_hub = np.zeros(n, dtype=bool)
    is_hub[hub] = True
    for i in range(n):
        if is_hub[i]:
            continue
        lo, hi = indptr[i], indptr[i + 1]
        slot_idx[: hi - lo, i] = src[lo:hi]
        slot_w[: hi - lo, i] = raw_edge[lo:hi] / den[i]
    hub_m = np.zeros((len(hub), n), np.float64)
    for r, i in enumerate(hub):
        lo, hi = indptr[i], indptr[i + 1]
        hub_m[r, src[lo:hi]] = raw_edge[lo:hi] / den[i]
        hub_m[r, i] = s[i] / den[i]
    return dict(
        slot_idx=jnp.asarray(slot_idx),
        slot_w=jnp.asarray(slot_w, jnp.float32),
        hyb_self_w=jnp.asarray(np.where(is_hub, 0.0, s / den), jnp.float32),
        hub_rows=jnp.asarray(hub),
        hub_m=jnp.asarray(hub_m, jnp.float32),
    )


def compile_plan(
    graph: Graph,
    backend: str = "auto",
    data_sizes: np.ndarray | Sequence[float] | None = None,
    failures: FailureModel | None = None,
) -> CommPlan:
    """Lower a ``Graph`` into an executable ``CommPlan``.

    backend="auto" picks dense or sparse from the platform, n and the
    directed edge count (``auto_backend``).
    """
    failures = failures or FailureModel()
    if backend == "auto":
        backend = _resolve_auto(graph.n, len(graph.csr()[1]))
    if backend not in BACKENDS:
        raise ValueError(f"unknown mixing backend {backend!r}; expected one of {BACKENDS}")

    sizes = None if data_sizes is None else np.asarray(data_sizes, dtype=np.float64)
    n = graph.n
    n_edges = len(graph.edge_list())
    common = dict(
        graph=graph,
        backend=backend,
        failures=failures,
        data_sizes=None if sizes is None else sizes.copy(),
        n_edges=n_edges,
        **_event_tables(graph, sizes),
    )

    if backend == "dense":
        uid_matrix = np.zeros((n, n), dtype=np.int32)
        edges = graph.edge_list()
        if graph.directed:
            uid_matrix[edges[:, 0], edges[:, 1]] = np.arange(len(edges))
        else:
            uid_matrix[edges[:, 0], edges[:, 1]] = np.arange(len(edges))
            uid_matrix[edges[:, 1], edges[:, 0]] = np.arange(len(edges))
        return CommPlan(
            **common,
            receive=jnp.asarray(receive_matrix(graph, sizes), jnp.float32),
            adjacency=jnp.asarray(graph.adjacency),
            edge_uid_matrix=jnp.asarray(uid_matrix),
        )

    s = np.ones(n, dtype=np.float64) if sizes is None else sizes
    if backend == "sparse":
        indptr, src, uid = graph.csr()
        dst = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
        raw_edge = graph.adjacency[dst, src].astype(np.float64) * s[src]
        den = s + np.bincount(dst, weights=raw_edge, minlength=n)
        return CommPlan(
            **common,
            src=jnp.asarray(src),
            dst=jnp.asarray(dst),
            edge_uid=jnp.asarray(uid),
            edge_w=jnp.asarray(raw_edge / den[dst], jnp.float32),
            self_w=jnp.asarray(s / den, jnp.float32),
            raw_edge_w=jnp.asarray(raw_edge, jnp.float32),
            raw_self_w=jnp.asarray(s, jnp.float32),
            **_hyb_layout(graph, indptr, src, raw_edge, s, den),
        )

    # ppermute: greedy edge colouring → per-colour matchings
    coloring = graph.edge_coloring()
    partners = coloring.partners
    idx = np.arange(n)
    matched = partners != idx[None, :]
    # receive weight of edge (i, partner) at node i: A[i, partner] * s[partner]
    raw = np.where(
        matched,
        graph.adjacency[idx[None, :], partners] * s[partners],
        0.0,
    )
    den = s + raw.sum(axis=0)
    return CommPlan(
        **common,
        partners=partners,
        color_edge_uid=jnp.asarray(coloring.edge_index),
        color_w=jnp.asarray(raw / den[None, :], jnp.float32),
        color_raw_w=jnp.asarray(raw, jnp.float32),
        self_w=jnp.asarray(s / den, jnp.float32),
        raw_self_w=jnp.asarray(s, jnp.float32),
    )


# =========================================================================
# PlanSchedule: time-varying topologies as a first-class axis (DESIGN.md §13)
# =========================================================================


@dataclasses.dataclass(frozen=True)
class RoundMap:
    """round index → plan index assignment for a ``PlanSchedule``.

    ``cyclic``:   plan ``(r // period) % K`` — plans take turns, ``period``
                  rounds each.
    ``sequence``: plan ``sequence[r % len(sequence)]`` — an explicit
                  (piecewise or seeded-random/Markov-realised) assignment,
                  tiled past its horizon.
    Both forms are jit-traceable in ``r`` (integer arithmetic / one gather),
    which is what lets the executor switch operators *inside* its scan.
    """

    kind: str  # "cyclic" | "sequence"
    period: int = 1
    sequence: np.ndarray | None = None

    def __post_init__(self):
        if self.kind not in ("cyclic", "sequence"):
            raise ValueError(f"unknown round-map kind {self.kind!r}")
        if self.kind == "cyclic" and self.period < 1:
            raise ValueError("cyclic round map needs period >= 1")
        if self.kind == "sequence" and (self.sequence is None or len(self.sequence) == 0):
            raise ValueError("sequence round map needs a non-empty index sequence")


def cyclic_map(period: int = 1) -> RoundMap:
    """Plans take turns, ``period`` consecutive rounds each."""
    return RoundMap("cyclic", period=int(period))


def sequence_map(sequence) -> RoundMap:
    """Explicit per-round plan indices, tiled cyclically past the horizon."""
    return RoundMap("sequence", sequence=np.asarray(sequence, np.int32))


def _pad1(a: jax.Array, width: int, fill) -> jax.Array:
    return jnp.pad(a, (0, width - a.shape[0]), constant_values=fill)


def _stack_hyb(plans: Sequence[CommPlan], n: int) -> dict[str, jax.Array]:
    """Pad the sparse plans' HYB (ELL slots + dense hub rows) layouts to one
    envelope so the clean-path fast rendering survives scheduling.

    Slot padding is identity-index / zero-weight.  Hub-row padding repeats a
    plan's first hub (duplicate ``.set`` of the same value — harmless); a
    hub-free plan fabricates node 0's dense receive row, so the overwritten
    row carries exactly the operator value the ELL slots would produce.
    """
    s_env = max(p.slot_idx.shape[0] for p in plans)
    h_env = max(p.hub_rows.shape[0] for p in plans)
    idrow = jnp.arange(n, dtype=jnp.int32)[None, :]
    slot_idx, slot_w, hub_rows, hub_m = [], [], [], []
    for p in plans:
        s = p.slot_idx.shape[0]
        slot_idx.append(
            jnp.concatenate([p.slot_idx, jnp.tile(idrow, (s_env - s, 1))])
            if s_env > s
            else p.slot_idx
        )
        slot_w.append(jnp.pad(p.slot_w, ((0, s_env - s), (0, 0))))
        h = p.hub_rows.shape[0]
        if h_env == 0:
            hub_rows.append(p.hub_rows)
            hub_m.append(p.hub_m)
        elif h > 0:
            hub_rows.append(jnp.concatenate([p.hub_rows, jnp.repeat(p.hub_rows[:1], h_env - h)]))
            hub_m.append(jnp.concatenate([p.hub_m, jnp.repeat(p.hub_m[:1], h_env - h, axis=0)]))
        else:
            src, dst = np.asarray(p.src), np.asarray(p.dst)
            row = np.zeros(n, np.float32)
            sel = dst == 0
            row[src[sel]] = np.asarray(p.edge_w)[sel]
            row[0] = float(np.asarray(p.self_w)[0])
            hub_rows.append(jnp.zeros((h_env,), jnp.int32))
            hub_m.append(jnp.tile(jnp.asarray(row)[None, :], (h_env, 1)))
    return dict(
        slot_idx=jnp.stack(slot_idx),
        slot_w=jnp.stack(slot_w),
        hyb_self_w=jnp.stack([p.hyb_self_w for p in plans]),
        hub_rows=jnp.stack(hub_rows),
        hub_m=jnp.stack(hub_m),
    )


def _stack_plans(plans: Sequence[CommPlan]) -> dict[str, jax.Array]:
    """Stack K same-backend plans into shared-shape device buffers.

    The shared sparsity envelope: CSR edge arrays pad to the max nnz with
    zero-weight (src = dst = n-1) entries — appended, so per-plan ``dst``
    stays sorted and ``segment_sum(indices_are_sorted=True)`` stays valid —
    and colour layouts pad to the max colour count with unmatched
    (identity-partner, zero-weight, uid = -1) classes.  Padding carries
    exactly-zero weights, so gathered plans execute the unpadded operator.
    """
    backend = plans[0].backend
    st: dict[str, jax.Array] = {}
    if backend == "dense":
        for f in ("receive", "adjacency", "edge_uid_matrix"):
            st[f] = jnp.stack([getattr(p, f) for p in plans])
    elif backend == "sparse":
        n = plans[0].n
        nnz = max(p.src.shape[0] for p in plans)
        st["src"] = jnp.stack([_pad1(p.src, nnz, n - 1) for p in plans])
        st["dst"] = jnp.stack([_pad1(p.dst, nnz, n - 1) for p in plans])
        st["edge_uid"] = jnp.stack([_pad1(p.edge_uid, nnz, 0) for p in plans])
        st["edge_w"] = jnp.stack([_pad1(p.edge_w, nnz, 0.0) for p in plans])
        st["raw_edge_w"] = jnp.stack([_pad1(p.raw_edge_w, nnz, 0.0) for p in plans])
        st["self_w"] = jnp.stack([p.self_w for p in plans])
        st["raw_self_w"] = jnp.stack([p.raw_self_w for p in plans])
        st.update(_stack_hyb(plans, n))
    else:  # ppermute
        n = plans[0].n
        nc = max(p.n_colors for p in plans)
        idrow = np.arange(n, dtype=np.int32)

        def pad_colors(a, fill, k):
            a = jnp.asarray(a)
            return jnp.pad(a, ((0, nc - k), (0, 0)), constant_values=fill)

        st["partners"] = jnp.stack(
            [
                jnp.asarray(
                    np.concatenate(
                        [p.partners, np.tile(idrow[None, :], (nc - p.n_colors, 1))]
                    )
                    if nc > p.n_colors
                    else p.partners
                )
                for p in plans
            ]
        )
        st["color_edge_uid"] = jnp.stack(
            [pad_colors(p.color_edge_uid, -1, p.n_colors) for p in plans]
        )
        st["color_w"] = jnp.stack([pad_colors(p.color_w, 0.0, p.n_colors) for p in plans])
        st["color_raw_w"] = jnp.stack(
            [pad_colors(p.color_raw_w, 0.0, p.n_colors) for p in plans]
        )
        st["self_w"] = jnp.stack([p.self_w for p in plans])
        st["raw_self_w"] = jnp.stack([p.raw_self_w for p in plans])
    if all(p.event_uv is not None for p in plans):
        # event tables pad to the edge envelope with (0, 0) endpoints and
        # exactly-zero weights — a padded event id is the identity update
        ev = max(p.event_uv.shape[0] for p in plans)
        st["event_uv"] = jnp.stack(
            [jnp.pad(p.event_uv, ((0, ev - p.event_uv.shape[0]), (0, 0))) for p in plans]
        )
        st["event_w"] = jnp.stack(
            [jnp.pad(p.event_w, ((0, ev - p.event_w.shape[0]), (0, 0))) for p in plans]
        )
    return st


@dataclasses.dataclass(frozen=True)
class PlanSchedule:
    """A time-varying mixing operator: K compiled ``CommPlan``s + a round map.

    The K plans share one backend, one failure model and one shape envelope
    (``_stack_plans``), so ``select(round)`` — a handful of gathers at a
    traced plan index — yields a ``CommPlan`` view *inside* jit/scan/vmap:
    the executor's scanned round body switches operators by round index with
    no host round-trip, and the gossip engine estimates on the dynamic graph
    nodes actually see.

    Contracts:
    * K = 1 is the static case and stays **bit-identical** to the plain
      ``CommPlan`` path: ``select`` returns the underlying plan itself (no
      gather, no padding) and ``round_key`` leaves failure keys untouched.
    * K > 1 folds the active plan index into every failure key
      (``round_key``), so resampled plans draw independent failures.
    * All plans must share the node count; data sizes are per-node and
      shared across plans.
    """

    plans: tuple[CommPlan, ...]
    round_map: RoundMap
    stacked: dict[str, jax.Array] = dataclasses.field(default_factory=dict, repr=False)
    n_edges_env: int = 0

    # ------------------------------------------------------------- metadata
    @property
    def k(self) -> int:
        return len(self.plans)

    @property
    def n(self) -> int:
        return self.plans[0].n

    @property
    def backend(self) -> str:
        return self.plans[0].backend

    @property
    def failures(self) -> FailureModel:
        return self.plans[0].failures

    @property
    def data_sizes(self) -> np.ndarray | None:
        return self.plans[0].data_sizes

    @property
    def n_edges(self) -> int:
        """Failure-draw and ``edge_live`` width: the shared edge envelope,
        as every round's ``select`` view carries it."""
        return self.n_edges_env

    @property
    def graph(self) -> Graph:
        """The round-0 plan's graph — size metadata and the "what a node sees
        at estimation start" anchor (degrees payloads, walker start checks)."""
        return self.plans[0].graph

    # ------------------------------------------------------------ selection
    def plan_index(self, round_index) -> jax.Array:
        """Traceable round → plan index (int32 scalar)."""
        r = jnp.asarray(round_index, jnp.int32)
        if self.k == 1:
            return jnp.zeros_like(r)
        m = self.round_map
        if m.kind == "cyclic":
            return (r // m.period) % self.k
        seq = jnp.asarray(m.sequence)
        return seq[r % seq.shape[0]]

    def round_key(self, key: jax.Array | None, round_index) -> jax.Array | None:
        """Fold the active plan id into a per-round failure key (satellite
        contract): K > 1 resampled plans draw independent failures; K = 1
        leaves the key untouched, reproducing the static plan's draws
        exactly."""
        if key is None or self.k == 1:
            return key
        return jax.random.fold_in(key, self.plan_index(round_index))

    def select(self, round_index) -> CommPlan:
        """The round's ``CommPlan``: K = 1 → the plan itself (bit-identical
        static path); K > 1 → a gathered view over the stacked envelope,
        traceable in ``round_index``.  The view's ``graph`` field is the
        round-0 graph (size metadata only) and its ``n_edges`` is the shared
        envelope, so failure draws have one static shape for every round."""
        if self.k == 1:
            return self.plans[0]
        i = self.plan_index(round_index)
        t = lambda name: (
            jnp.take(self.stacked[name], i, axis=0) if name in self.stacked else None
        )
        return CommPlan(
            graph=self.plans[0].graph,
            backend=self.backend,
            failures=self.failures,
            data_sizes=self.plans[0].data_sizes,
            receive=t("receive"),
            adjacency=t("adjacency"),
            edge_uid_matrix=t("edge_uid_matrix"),
            src=t("src"),
            dst=t("dst"),
            edge_uid=t("edge_uid"),
            edge_w=t("edge_w"),
            self_w=t("self_w"),
            raw_edge_w=t("raw_edge_w"),
            raw_self_w=t("raw_self_w"),
            slot_idx=t("slot_idx"),
            slot_w=t("slot_w"),
            hyb_self_w=t("hyb_self_w"),
            hub_rows=t("hub_rows"),
            hub_m=t("hub_m"),
            partners=t("partners"),
            color_edge_uid=t("color_edge_uid"),
            color_w=t("color_w"),
            color_raw_w=t("color_raw_w"),
            event_uv=t("event_uv"),
            event_w=t("event_w"),
            n_edges=self.n_edges_env,
        )

    # ------------------------------------------------------------ execution
    def mix(
        self,
        params: PyTree,
        round_index,
        key: jax.Array | None = None,
        *,
        active: jax.Array | None = None,
        edge_live: jax.Array | None = None,
        compression: Compression | None = None,
        residual: PyTree | None = None,
    ) -> PyTree:
        """One DecAvg round under the plan active at ``round_index``.
        ``edge_live`` is read at the schedule's shared edge *envelope* width
        (``n_edges_env``), indexed by the active plan's own edge uids.
        ``compression``/``residual`` follow ``CommPlan.mix``: an active
        codec returns ``(mixed, new_residual)``."""
        return self.select(round_index).mix(
            params, self.round_key(key, round_index), active=active,
            edge_live=edge_live, compression=compression, residual=residual,
        )

    def spread(
        self,
        values: jax.Array,
        round_index,
        key: jax.Array | None = None,
        *,
        active: jax.Array | None = None,
        edge_live: jax.Array | None = None,
        compression: Compression | None = None,
        residual: jax.Array | None = None,
    ) -> jax.Array:
        """One send-form (push) round under the active plan."""
        return self.select(round_index).spread(
            values, self.round_key(key, round_index), active=active,
            edge_live=edge_live, compression=compression, residual=residual,
        )

    def spread_min(
        self,
        values: jax.Array,
        round_index,
        key: jax.Array | None = None,
        *,
        active: jax.Array | None = None,
        edge_live: jax.Array | None = None,
    ) -> jax.Array:
        """One min-exchange round under the active plan (leaderless sketches)."""
        return self.select(round_index).spread_min(
            values, self.round_key(key, round_index), active=active, edge_live=edge_live
        )

    # ------------------------------------------------- event-driven execution
    def _window(self, time) -> jax.Array:
        """Unit-time window index of an event timestamp (1 window = 1 round
        of the round map), traceable in ``time``."""
        return jnp.floor(jnp.asarray(time, jnp.float32)).astype(jnp.int32)

    def event_key(self, key: jax.Array | None, time) -> jax.Array | None:
        """Fold the plan id active at ``time``'s window into a per-event
        failure key — the event-path mirror of ``round_key`` (satellite
        contract): K > 1 plans draw independent per-event node/link outages;
        K = 1 leaves the key untouched, bit-identical to the static plan."""
        if key is None or self.k == 1:
            return key
        return jax.random.fold_in(key, self.plan_index(self._window(time)))

    def event_mix(self, params: PyTree, edge, time, key: jax.Array | None = None) -> PyTree:
        """One asynchronous DecAvg event under the plan active at ``time``.
        ``edge`` indexes the active plan's own ``Graph.edge_list()`` (use
        ``event_stream`` to sample streams with per-window edge ids)."""
        w = self._window(time)
        return self.select(w).event_mix(params, edge, self.event_key(key, time))

    def event_spread(self, values: jax.Array, edge, time, key: jax.Array | None = None) -> jax.Array:
        """One asynchronous push event under the plan active at ``time``."""
        w = self._window(time)
        return self.select(w).event_spread(values, edge, self.event_key(key, time))

    def event_spread_min(
        self, values: jax.Array, edge, time, key: jax.Array | None = None
    ) -> jax.Array:
        """One asynchronous min event under the plan active at ``time``."""
        w = self._window(time)
        return self.select(w).event_spread_min(values, edge, self.event_key(key, time))

    def _host_plan_index(self, round_index: int) -> int:
        """Host (numpy) replica of ``plan_index`` — event-stream sampling and
        parity references resolve the active plan without tracing."""
        if self.k == 1:
            return 0
        m = self.round_map
        if m.kind == "cyclic":
            return (int(round_index) // m.period) % self.k
        seq = np.asarray(m.sequence)
        return int(seq[int(round_index) % len(seq)])

    def event_stream(self, horizon: float, rate: float = 1.0, seed: int = 0):
        """Sample a Poisson edge-clock stream over the *schedule*: each
        unit-time window draws its events from the plan active in that
        window (edge ids in that plan's own edge order), windows concatenate
        into one time-sorted stream.  K = 1 delegates to the static sampler
        bit-identically."""
        from .topology import EventStream, poisson_event_stream

        if self.k == 1:
            return poisson_event_stream(self.plans[0].graph, horizon, rate=rate, seed=seed)
        n_windows = int(np.ceil(horizon))
        times, edges = [], []
        for w in range(n_windows):
            g = self.plans[self._host_plan_index(w)].graph
            span = min(1.0, horizon - w)
            win = poisson_event_stream(g, span, rate=rate, seed=seed + w)
            k = win.n_events
            times.append(np.asarray(win.times[:k]) + w)
            edges.append(np.asarray(win.edges[:k]))
        t = np.concatenate(times) if times else np.zeros(0, np.float64)
        e = np.concatenate(edges) if edges else np.zeros(0, np.int32)
        return EventStream(
            times=np.asarray(t, np.float32),
            edges=np.asarray(e, np.int32),
            n_events=len(t),
            horizon=float(horizon),
            rates=np.full(len(self.plans[0].graph.edge_list()), float(rate)),
        )

    def round_masks(self, key: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Envelope-width failure draws — what every selected plan consumes.
        Host references replaying a schedule must draw at this width (then
        index masks by the active plan's own edge uids)."""
        return _draw_failure_masks(self.failures, self.n_edges_env, self.n, key)

    def stacked_csr(self) -> dict[str, jax.Array]:
        """Stacked CSR views of every plan's graph, padded to one envelope:
        ``indptr`` (K, n+1), ``indices``/``uid`` (K, nnz_env), ``deg`` (K, n)
        int32 and ``degrees`` (K, n) float32 — the random-walk degree
        pollers' per-round transition tables (``repro.gossip.walker``)."""
        graphs = [p.graph for p in self.plans]
        csrs = [g.csr() for g in graphs]
        nnz = max(len(c[1]) for c in csrs)
        pad = lambda a: np.pad(a, (0, nnz - len(a)))
        return dict(
            indptr=jnp.asarray(np.stack([c[0] for c in csrs])),
            indices=jnp.asarray(np.stack([pad(c[1]) for c in csrs])),
            uid=jnp.asarray(np.stack([pad(c[2]) for c in csrs])),
            deg=jnp.asarray(np.stack([np.diff(c[0]).astype(np.int32) for c in csrs])),
            degrees=jnp.asarray(
                np.stack([g.degrees for g in graphs]), jnp.float32
            ),
        )

    # ------------------------------------------------------------- plumbing
    def with_options(
        self,
        *,
        backend: str | None = None,
        data_sizes: np.ndarray | None = None,
        failures: FailureModel | None = None,
    ) -> "PlanSchedule":
        """Recompile the whole schedule with some knobs replaced."""
        return compile_schedule(
            [p.graph for p in self.plans],
            backend=backend or self.backend,
            data_sizes=self.data_sizes if data_sizes is None else data_sizes,
            failures=failures or self.failures,
            round_map=self.round_map,
        )


def compile_schedule(
    graphs: Sequence[Graph],
    backend: str = "auto",
    data_sizes: np.ndarray | Sequence[float] | None = None,
    failures: FailureModel | None = None,
    round_map: RoundMap | None = None,
) -> PlanSchedule:
    """Lower K graphs (+ a round→plan map) into a ``PlanSchedule``.

    Every graph compiles through ``compile_plan`` with the same backend /
    data sizes / failure model; the per-plan buffers are then stacked into
    the shared shape envelope.  ``round_map`` defaults to ``cyclic_map(1)``
    (round-robin); ``topology.churn_sequence`` builds Markov-churned graph
    sequences to feed here.
    """
    graphs = list(graphs)
    if not graphs:
        raise ValueError("compile_schedule needs at least one graph")
    if len({g.n for g in graphs}) != 1:
        raise ValueError(
            f"all plans in a schedule must share the node count, got "
            f"{[g.n for g in graphs]}"
        )
    if backend == "auto":
        backend = _resolve_auto(graphs[0].n, max(len(g.csr()[1]) for g in graphs))
    plans = tuple(
        compile_plan(g, backend=backend, data_sizes=data_sizes, failures=failures)
        for g in graphs
    )
    round_map = round_map or cyclic_map(1)
    if round_map.kind == "sequence" and int(np.max(round_map.sequence)) >= len(plans):
        raise ValueError(
            f"round map references plan {int(np.max(round_map.sequence))} but the "
            f"schedule holds only {len(plans)} plans"
        )
    stacked = _stack_plans(plans) if len(plans) > 1 else {}
    return PlanSchedule(
        plans=plans,
        round_map=round_map,
        stacked=stacked,
        n_edges_env=max(p.n_edges for p in plans),
    )
