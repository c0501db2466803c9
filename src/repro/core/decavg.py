"""DecAvg / "Decay" aggregation (paper Eq. 2) and its TPU renderings.

Execution backends of the same operator, all consuming parameter pytrees
with a leading node axis ``(n, ...)`` (compiled and dispatched by
``repro.core.commplan``, DESIGN.md §3):

1. ``mix_pytree``            — dense ``w_new[i] = Σ_j M[i,j] w[j]`` einsum with
                               the receive matrix.  Reference semantics, works
                               for any topology, any failure pattern.  Under
                               pjit with the node axis sharded over ``data``,
                               XLA lowers the contraction to an all-gather of
                               the full parameter ensemble — the *paper-faithful
                               baseline* of the §Perf story.
2. ``mix_pytree_sparse``     — edge-list gather-scatter: gather each receive
                               edge's source row, weight, ``segment_sum`` into
                               the destination.  O(E·d) compute / bytes instead
                               of O(n²·d) — the backend that makes n in the
                               thousands tractable.  ``mix_pytree_hyb`` is the
                               CPU-fast rendering of the same operator (ELL
                               slot chain + dense hub rows); ``repro.kernels
                               .mix`` additionally provides the blocked
                               block-sparse Pallas kernel for the TPU hot-spot.
3. ``mix_pytree_colored``    — edge-coloured collective schedule for *any*
                               static undirected graph: each colour class is a
                               matching, i.e. one ``ppermute`` round inside
                               ``shard_map`` (generalises the circulant-only
                               schedule).  Falls back to gather semantics when
                               no mesh axis is given — same math, same
                               schedule, single-process.
4. ``mix_pytree_circulant``  — the original circulant-only ``ppermute`` shift
                               schedule, kept for regular rings/tori where the
                               offset structure is known a priori.

Failure modelling (paper §4.1, Fig. 2): each *link* or *node* is active per
round with probability p; inactive nodes still train locally but are
momentarily isolated.  ``failure_receive_matrix`` rebuilds the round's
effective row-stochastic operator for the dense backend; the sparse/colored
backends apply per-edge keep masks and renormalise via segment sums (see
``commplan``).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .topology import Graph

__all__ = [
    "mix_pytree",
    "mix_array",
    "mix_pytree_sparse",
    "mix_pytree_hyb",
    "mix_pytree_colored",
    "mix_pytree_circulant",
    "mix_pytree_pairwise",
    "mix_pytree_pairwise_batch",
    "spread_pairwise",
    "spread_min_pairwise",
    "failure_receive_matrix",
    "link_failure_mask",
    "node_failure_mask",
]

PyTree = Any

# Mixing contractions run at full f32 precision.  A TPU's default f32 matmul
# rounds its inputs to bf16, which would round every node's parameters to 8
# mantissa bits each round and bury the small per-round updates the
# trajectory is made of.  On the CPU this is the default anyway.
MIX_PRECISION = jax.lax.Precision.HIGHEST


def _bcast(w: jax.Array, ndim: int) -> jax.Array:
    """Reshape a 1-D weight vector to broadcast over ``ndim - 1`` trailing dims."""
    return w.reshape(w.shape + (1,) * (ndim - 1))


def mix_array(m: jax.Array, x: jax.Array) -> jax.Array:
    """``x_new[i] = Σ_j m[i, j] x[j]`` over the leading node axis.

    fp32 accumulation regardless of parameter dtype: the mixing weights are
    O(1/k) and parameter magnitudes shrink by ‖v_steady‖ during diffusion, so
    bf16 accumulation would lose exactly the signal the paper studies.

    Implemented as a tensordot over the node axis WITHOUT flattening: under
    pjit the trailing dims keep their model-axis sharding, so the only
    communication is the node-axis gather inherent to dense mixing (a
    reshape-to-(n, -1) here would force a full model-axis all-gather).
    """
    out = jnp.tensordot(
        m, x, axes=[[1], [0]], precision=MIX_PRECISION, preferred_element_type=jnp.float32
    )
    return out.astype(x.dtype)


def mix_pytree(m: jax.Array, params: PyTree) -> PyTree:
    """DecAvg over every leaf of a node-stacked parameter pytree."""
    return jax.tree_util.tree_map(lambda w: mix_array(m, w), params)


def mix_pytree_sparse(
    params: PyTree,
    src: jax.Array,
    dst: jax.Array,
    edge_w: jax.Array,
    self_w: jax.Array,
) -> PyTree:
    """DecAvg via edge-list gather-scatter (CSR order, dst-sorted).

    ``out[i] = self_w[i] * x[i] + Σ_{e: dst[e]=i} edge_w[e] * x[src[e]]``

    Weights must already be normalised (rows of the effective receive matrix
    sum to 1) — ``commplan`` precomputes them statically or renormalises per
    round under failures.  fp32 accumulation for the same reason as
    ``mix_array``.

    Each row accumulates its self term first, then its edges in CSR order,
    written as a scatter into the self term.  XLA rewrites
    ``a + segment_sum(b)`` into that scatter in some programs and not in
    others, so the order is spelled out to keep every rendering of the
    operator (eager, jitted, node-sharded) bit-identical.
    """
    def mix_leaf(x: jax.Array) -> jax.Array:
        gathered = jnp.take(x, src, axis=0).astype(jnp.float32)
        contrib = _bcast(edge_w, x.ndim) * gathered
        out = (_bcast(self_w, x.ndim) * x.astype(jnp.float32)).at[dst].add(
            contrib, indices_are_sorted=True
        )
        return out.astype(x.dtype)

    return jax.tree_util.tree_map(mix_leaf, params)


def mix_pytree_hyb(
    params: PyTree,
    slot_idx: jax.Array,
    slot_w: jax.Array,
    self_w: jax.Array,
    hub_rows: jax.Array | None,
    hub_m: jax.Array | None,
) -> PyTree:
    """DecAvg via the HYB (ELL + dense hub rows) sparse layout.

    The CPU-fast rendering of the sparse backend: low-degree rows execute as
    a chain of weighted full-length gathers (one per ELL slot — XLA fuses the
    chain into a single pass, so S slots cost far less than one materialised
    (nnz, d) gather), and the few heavy-tail hub rows as one small dense
    (H, n) matmul.  ``slot_idx``/``slot_w`` are (S, n) — slot s holds node
    i's s-th neighbour (self-index with weight 0 when exhausted or when i is
    a hub row); ``hub_m`` holds the hubs' full receive rows including their
    self weight.  Weights must be normalised.
    """

    def mix_leaf(x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        acc = _bcast(self_w, x.ndim) * xf
        for s in range(slot_idx.shape[0]):
            acc = acc + _bcast(slot_w[s], x.ndim) * jnp.take(xf, slot_idx[s], axis=0)
        if hub_rows is not None and hub_rows.shape[0]:
            hub_out = jnp.tensordot(
                hub_m, xf, axes=[[1], [0]], precision=MIX_PRECISION,
                preferred_element_type=jnp.float32,
            )
            acc = acc.at[hub_rows].set(hub_out)
        return acc.astype(x.dtype)

    return jax.tree_util.tree_map(mix_leaf, params)


def mix_pytree_colored(
    params: PyTree,
    partners: np.ndarray,
    color_w: jax.Array,
    self_w: jax.Array,
    axis_name: str | Sequence[str] | None = None,
) -> PyTree:
    """DecAvg over an edge-coloured schedule (arbitrary undirected graphs).

    partners: static (n_colors, n) int array — colour c's matching as an
    involution (partners[c, i] == i when unmatched).  color_w: (n_colors, n)
    receive weight of the edge (i, partners[c, i]) at node i (0 when
    unmatched); self_w: (n,).  Weights must be normalised.

    With ``axis_name`` set this must run inside ``shard_map`` with the node
    axis sharded one node per device group: each colour class becomes one
    ``ppermute`` (matchings are involutions, hence valid permutations), and
    ``color_w`` / ``self_w`` must be passed as node-sharded operands (their
    local shards).  Without ``axis_name`` the same schedule executes as
    node-axis gathers — identical math, single process — and ``partners``
    may be a *traced* array (a ``PlanSchedule``-selected colour table); the
    collective rendering needs static host perms and keeps requiring numpy.
    """
    if axis_name is None:
        partners = jnp.asarray(partners)
        n_colors = partners.shape[0]

        def mix_leaf(x: jax.Array) -> jax.Array:
            acc = _bcast(self_w, x.ndim) * x.astype(jnp.float32)
            for c in range(n_colors):
                shifted = jnp.take(x, partners[c], axis=0)
                acc = acc + _bcast(color_w[c], x.ndim) * shifted.astype(jnp.float32)
            return acc.astype(x.dtype)

        return jax.tree_util.tree_map(mix_leaf, params)

    partners = np.asarray(partners)
    n_colors, n = partners.shape
    axis_size = jax.lax.psum(1, axis_name)
    if axis_size != n:
        raise ValueError(
            f"colored ppermute schedule needs one node per device group: axis size {axis_size} != n {n}"
        )
    perms = [
        [(i, int(partners[c, i])) for i in range(n) if partners[c, i] != i]
        for c in range(n_colors)
    ]

    def mix_leaf_collective(x: jax.Array) -> jax.Array:
        acc = _bcast(self_w, x.ndim) * x.astype(jnp.float32)
        for c in range(n_colors):
            if not perms[c]:
                continue
            shifted = jax.lax.ppermute(x, axis_name, perms[c])
            acc = acc + _bcast(color_w[c], x.ndim) * shifted.astype(jnp.float32)
        return acc.astype(x.dtype)

    return jax.tree_util.tree_map(mix_leaf_collective, params)


def mix_pytree_pairwise(
    params: PyTree,
    u: jax.Array,
    v: jax.Array,
    w_uv: jax.Array,
    w_vu: jax.Array,
) -> PyTree:
    """One event-driven pairwise DecAvg exchange on edge (u, v).

    The asynchronous rendering of Eq. 2 (DESIGN.md §14): when edge (u, v)'s
    Poisson clock fires, only its two endpoints move —

        ``w_u ← w_u + w_uv·(w_v − w_u)``   and symmetrically for v.

    ``u``/``v`` are traced int32 scalars; ``w_uv``/``w_vu`` traced float32
    weights, normally the synchronous plan's receive entries ``M[u, v]`` /
    ``M[v, u]`` so composing one event per edge reproduces the synchronous
    round to first order in the weights (the rate-1 parity property).  A
    masked event (dead edge, padding) passes ``w = 0`` and is the exact
    identity.  fp32 blend for the same reason as ``mix_array``.
    """

    def mix_leaf(x: jax.Array) -> jax.Array:
        xu, xv = x[u].astype(jnp.float32), x[v].astype(jnp.float32)
        new_u = xu + w_uv * (xv - xu)
        new_v = xv + w_vu * (xu - xv)
        return x.at[u].set(new_u.astype(x.dtype)).at[v].set(new_v.astype(x.dtype))

    return jax.tree_util.tree_map(mix_leaf, params)


def mix_pytree_pairwise_batch(
    params: PyTree,
    u: jax.Array,
    v: jax.Array,
    w_uv: jax.Array,
    w_vu: jax.Array,
) -> PyTree:
    """One **colour step**: simultaneous pairwise exchanges on a batch of
    endpoint-disjoint edges (ROADMAP §14's batched event rendering).

    ``u``/``v``: (W,) int32 endpoint vectors; ``w_uv``/``w_vu``: (W,) f32
    receive weights.  The edges must be pairwise vertex-disjoint (a matching
    — ``topology.batch_events_by_color`` produces such batches), so the W
    sequential ``mix_pytree_pairwise`` updates commute and collapse into one
    vectorised gather + scatter-*add* of the per-endpoint deltas.  The add
    form keeps padding safe: a masked event passes ``w = 0``, contributes an
    exactly-zero delta, and may alias any row (including a live endpoint)
    without an ordering hazard — unlike scatter-set, whose result under
    duplicate indices is implementation-defined.  Each live endpoint
    receives ``x_u + w_uv·(x_v − x_u)`` — the same expression the pairwise
    form computes, so a batched replay matches the sequential scan.
    """

    def mix_leaf(x: jax.Array) -> jax.Array:
        xu, xv = x[u].astype(jnp.float32), x[v].astype(jnp.float32)
        du = _bcast(w_uv, x.ndim) * (xv - xu)
        dv = _bcast(w_vu, x.ndim) * (xu - xv)
        return x.at[u].add(du.astype(x.dtype)).at[v].add(dv.astype(x.dtype))

    return jax.tree_util.tree_map(mix_leaf, params)


def spread_pairwise(
    values: jax.Array,
    u: jax.Array,
    v: jax.Array,
    w_uv: jax.Array,
    w_vu: jax.Array,
) -> jax.Array:
    """One event-driven **push** exchange on edge (u, v) — mass-conserving.

    The asynchronous rendering of the send-form operator Mᵀ: u hands the
    fraction ``w_uv = M[u, v]`` of its mass to v and receives ``w_vu·s_v``
    back —

        ``s_u ← s_u − w_uv·s_u + w_vu·s_v``   and symmetrically for v,

    so ``s_u + s_v`` (hence the global sum) is invariant for *any* weights —
    the property event-driven push-sum rides (``repro.gossip``).  Composing
    one event per edge matches the synchronous ``CommPlan.spread`` to first
    order, same as the mix form.  ``values``: (n,) or (n, k) float32.
    """
    xu, xv = values[u], values[v]
    give_u, give_v = w_uv * xu, w_vu * xv
    return values.at[u].set(xu - give_u + give_v).at[v].set(xv - give_v + give_u)


def spread_min_pairwise(values: jax.Array, u: jax.Array, v: jax.Array, keep: jax.Array) -> jax.Array:
    """One event-driven **min** exchange on edge (u, v): both endpoints take
    the elementwise minimum (identity when ``keep`` is False) — the event
    transport of the leaderless size sketches."""
    xu, xv = values[u], values[v]
    lo = jnp.minimum(xu, xv)
    return values.at[u].set(jnp.where(keep, lo, xu)).at[v].set(jnp.where(keep, lo, xv))


def mix_pytree_circulant(
    params: PyTree,
    offsets: Sequence[int],
    axis_name: str | Sequence[str],
    weights: jax.Array | None = None,
) -> PyTree:
    """Circulant DecAvg on a sharded node axis via ``jax.lax.ppermute``.

    Must be called inside ``shard_map`` where ``axis_name`` indexes the node
    shards (one node per device group along the FL axis).  For a circulant
    graph with offset set S (degree k = 2|S|), the DecAvg receive weights with
    uniform data are 1/(k+1) for self and each of the 2|S| neighbours.

    weights: optional (2|S|+1,) receive weights ordered [self, +s1, -s1, ...],
    for non-uniform data sizes.
    """
    n_terms = 2 * len(offsets) + 1
    if weights is None:
        w = jnp.full((n_terms,), 1.0 / n_terms, dtype=jnp.float32)
    else:
        w = weights.astype(jnp.float32)

    axis_size = jax.lax.psum(1, axis_name)

    def mix_leaf(x: jax.Array) -> jax.Array:
        acc = w[0] * x.astype(jnp.float32)
        t = 1
        for s in offsets:
            for sign in (1, -1):
                perm = [(i, (i + sign * s) % axis_size) for i in range(axis_size)]
                shifted = jax.lax.ppermute(x, axis_name, perm)
                acc = acc + w[t] * shifted.astype(jnp.float32)
                t += 1
        return acc.astype(x.dtype)

    return jax.tree_util.tree_map(mix_leaf, params)


def link_failure_mask(key: jax.Array, graph: Graph, p: float) -> jax.Array:
    """Symmetric Bernoulli(p) mask over the graph's edges (Fig. 2a)."""
    a = jnp.asarray(graph.adjacency)
    u = jax.random.uniform(key, a.shape)
    upper = jnp.triu(u, k=1)
    keep = (upper < p) & (jnp.triu(a, k=1) > 0)
    keep = keep | keep.T
    return keep.astype(a.dtype)


def node_failure_mask(key: jax.Array, graph: Graph, p: float) -> jax.Array:
    """Adjacency with all edges of inactive nodes removed (Fig. 2b).

    An inactive node neither sends nor receives this round, but keeps training
    locally (its receive row collapses to identity below).
    """
    a = jnp.asarray(graph.adjacency)
    active = jax.random.bernoulli(key, p, (graph.n,))
    m = active[:, None] & active[None, :]
    return (a * m).astype(a.dtype)


def failure_receive_matrix(adjacency: jax.Array, data_sizes: jax.Array | None = None) -> jax.Array:
    """Row-stochastic DecAvg receive operator for a (possibly masked) adjacency.

    Jax-traceable version of ``core.mixing.receive_matrix`` so per-round
    failure masks can stay on-device inside the jitted round function.
    """
    n = adjacency.shape[0]
    b = adjacency.astype(jnp.float32) + jnp.eye(n, dtype=jnp.float32)
    if data_sizes is not None:
        b = b * data_sizes[None, :].astype(jnp.float32)
    return b / b.sum(axis=1, keepdims=True)
