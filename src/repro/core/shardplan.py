"""Node-sharded rendering of a ``CommPlan``: DecAvg over a device mesh axis.

Every other rendering in ``core.commplan`` materialises the full node axis on
one device; this module partitions the FL node dimension **contiguously**
across a mesh axis (DESIGN.md §15) and executes the same effective operator
with per-shard work plus static halo collectives:

* **intra-shard edges** — the global receive CSR is dst-sorted, so each
  shard's in-edges are one contiguous slice of it; the slice runs as the
  usual gather + ``segment_sum`` (padded with dummy-segment entries, so the
  per-row accumulation order — hence the floating-point result — is
  bit-identical to the single-device segment-sum rendering).
* **cross-shard edges** — a static halo-exchange plan: for every shard
  offset δ with traffic, each shard gathers the rows its offset-δ neighbour
  needs (a per-shard send-index table) and one ``jax.lax.ppermute`` moves
  the buffers; received rows are appended to the local block in a fixed
  deterministic order, and edge gather indices point into that
  ``[local | halo]`` buffer.

Failure draws stay **globally keyed**: every shard redraws the full
(n_edges,) / (n,) Bernoulli masks from the same (replicated) per-round key,
so a sharded round keeps the exact per-edge draws of the single-device plan
— the bit-parity property ``tests/test_sharded_plan.py`` pins down.

``spread`` (the send-form operator gossip rides) uses a second, src-sorted
layout of the same edges with its own halo plan; ``spread_min`` reuses the
receive layout with ``segment_min``.  The dense backend shards the receive
matrix by rows (one ``all_gather`` of the payload — the paper-faithful
baseline's communication pattern made explicit); the ppermute backend keeps
its one-node-per-device contract and runs the colour matchings as true
per-colour ``ppermute`` rounds.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .commplan import CommPlan, _draw_failure_masks
from .decavg import MIX_PRECISION, _bcast, mix_pytree_colored

PyTree = Any

__all__ = ["ShardedCommPlan", "shard_plan"]

_F32 = jnp.float32


# ---------------------------------------------------------------------------
# host-side layout compilation
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Layout:
    """One sharded edge layout (receive- or send-sorted) + its halo plan.

    All per-shard tables carry a leading ``(n_shards, ...)`` axis and enter
    ``shard_map`` as node-axis-sharded operands; ``h_max`` is a static int
    baked into the (single) ``all_to_all`` halo exchange.

    ``seg``    (S, E) local segment index of the *owning* endpoint
               (padding rows point at the dummy segment ``nps``);
    ``gat``    (S, E) gather index into the ``[local | halo]`` buffer;
    ``uid``    (S, E) global undirected edge id (failure-draw key);
    ``gown``/``gfar`` (S, E) global ids of the owning / gathered endpoint;
    ``perm``   (S, E) position of the edge in the global receive-CSR arrays;
    ``send``   (S, S, H) local rows shard q ships to every other shard,
               padded per pair to the uniform width ``h_max`` so the whole
               halo moves as ONE ``all_to_all`` per round (collective
               rendezvous dominates small payloads, so k per-offset
               ``ppermute`` rounds lose to one padded exchange).
    """

    nps: int
    n_shards: int
    h_max: int
    seg: jax.Array
    gat: jax.Array
    uid: jax.Array
    edge_w: jax.Array
    raw_edge_w: jax.Array
    gown: jax.Array
    gfar: jax.Array
    valid: jax.Array
    perm: jax.Array
    self_w: jax.Array  # (S, nps) statically normalised self weights
    raw_self_w: jax.Array  # (S, nps)
    send: jax.Array  # (S, S, h_max) all_to_all send tables
    # host-side gather-position maps: pos[s][global node] → row in shard s's
    # ``[local | halo]`` buffer, for compiling further per-shard index tables
    # (the HYB slot chain) against this layout's halo plan
    pos: tuple[dict, ...] = ()

    def tables(self) -> dict[str, jax.Array]:
        """The shard_map operand dict (all leading-axis node-sharded)."""
        return {
            "seg": self.seg,
            "gat": self.gat,
            "uid": self.uid,
            "edge_w": self.edge_w,
            "raw_edge_w": self.raw_edge_w,
            "gown": self.gown,
            "gfar": self.gfar,
            "valid": self.valid,
            "perm": self.perm,
            "self_w": self.self_w,
            "raw_self_w": self.raw_self_w,
            "send": self.send,
        }

    @property
    def halo_rows(self) -> int:
        """Rows each shard ships cross-device per round — the padded
        ``all_to_all`` width times the S-1 remote destinations (the q→q
        block of the exchange never leaves the device)."""
        return (self.n_shards - 1) * self.h_max


def _build_layout(
    n: int,
    n_shards: int,
    own: np.ndarray,
    far: np.ndarray,
    uid: np.ndarray,
    edge_w: np.ndarray,
    raw_edge_w: np.ndarray,
    perm: np.ndarray,
    self_w: np.ndarray,
    raw_self_w: np.ndarray,
) -> _Layout:
    """Compile one (own-sorted) edge layout into per-shard tables + halo plan.

    ``own`` must be sorted ascending (dst for the receive layout, src for the
    send layout); edges of shard s are then the contiguous slice whose owner
    falls in ``[s*nps, (s+1)*nps)``.  Fully deterministic: halo rows are the
    sorted unique remote endpoints, laid out per source shard in ascending
    shard order at the uniform ``all_to_all`` width ``h_max``.
    """
    nps = n // n_shards
    bounds = np.searchsorted(own, np.arange(1, n_shards + 1) * nps)
    starts = np.concatenate([[0], bounds[:-1]])
    env = max(int((bounds - starts).max()), 1)

    # remote needs: needs[s][q] = sorted global nodes shard s must pull from q
    needs: list[dict[int, np.ndarray]] = [{} for _ in range(n_shards)]
    for s in range(n_shards):
        f = far[starts[s] : bounds[s]]
        remote = f[(f < s * nps) | (f >= (s + 1) * nps)]
        for q in np.unique(remote // nps):
            needs[s][int(q)] = np.unique(remote[remote // nps == q])

    h_max = max((len(nd) for ns in needs for nd in ns.values()), default=0)
    pos: list[dict[int, int]] = [{} for _ in range(n_shards)]
    send = np.zeros((n_shards, n_shards, max(h_max, 1)), np.int32)
    for s in range(n_shards):
        for q, nd in needs[s].items():
            send[q, s, : len(nd)] = (nd - q * nps).astype(np.int32)
            for j, g in enumerate(nd):
                # gather space is [local | recv block of shard 0 | shard 1 |…]
                pos[s][int(g)] = nps + q * h_max + j

    seg = np.full((n_shards, env), nps, np.int32)
    gat = np.zeros((n_shards, env), np.int32)
    uid_t = np.zeros((n_shards, env), np.int32)
    ew_t = np.zeros((n_shards, env), np.float32)
    rew_t = np.zeros((n_shards, env), np.float32)
    gown_t = np.zeros((n_shards, env), np.int32)
    gfar_t = np.zeros((n_shards, env), np.int32)
    valid_t = np.zeros((n_shards, env), bool)
    perm_t = np.zeros((n_shards, env), np.int32)
    for s in range(n_shards):
        sl = slice(starts[s], bounds[s])
        m = bounds[s] - starts[s]
        lo = s * nps
        f = far[sl]
        seg[s, :m] = (own[sl] - lo).astype(np.int32)
        gat[s, :m] = [
            int(g) - lo if lo <= g < lo + nps else pos[s][int(g)] for g in f
        ]
        uid_t[s, :m] = uid[sl]
        ew_t[s, :m] = edge_w[sl]
        rew_t[s, :m] = raw_edge_w[sl]
        gown_t[s, :m] = own[sl]
        gfar_t[s, :m] = f
        valid_t[s, :m] = True
        perm_t[s, :m] = perm[sl]

    return _Layout(
        nps=nps,
        n_shards=n_shards,
        h_max=h_max,
        seg=jnp.asarray(seg),
        gat=jnp.asarray(gat),
        uid=jnp.asarray(uid_t),
        edge_w=jnp.asarray(ew_t),
        raw_edge_w=jnp.asarray(rew_t),
        gown=jnp.asarray(gown_t),
        gfar=jnp.asarray(gfar_t),
        valid=jnp.asarray(valid_t),
        perm=jnp.asarray(perm_t),
        self_w=jnp.asarray(self_w.reshape(n_shards, nps), jnp.float32),
        raw_self_w=jnp.asarray(raw_self_w.reshape(n_shards, nps), jnp.float32),
        send=jnp.asarray(send),
        pos=tuple(pos),
    )


def _build_hyb_tables(plan: CommPlan, recv: _Layout, n_shards: int) -> dict | None:
    """Shard the sparse backend's HYB layout against the receive halo plan.

    The ELL slot chain is row-parallel (per owned row: self term then one
    fused gather per slot, in slot order), so re-pointing each slot index at
    the ``[local | halo]`` buffer preserves the exact accumulation order of
    ``mix_pytree_hyb`` — the clean-topology sharded mix stays bit-identical
    to the single-device ``CommPlan.mix``.  Heavy-tail hub rows keep their
    full-length dense receive rows (their halo would approach n anyway) and
    contract against an all-gathered payload; padding hub slots scatter to
    the out-of-range row ``nps``, which JAX's scatter drops.
    """
    if plan.slot_idx is None:
        return None
    slot_idx = np.asarray(plan.slot_idx)  # (n_slots, n)
    slot_w = np.asarray(plan.slot_w)
    hyb_self = np.asarray(plan.hyb_self_w)
    hub_rows = np.asarray(plan.hub_rows)
    hub_m = np.asarray(plan.hub_m)
    n = plan.n
    nps = n // n_shards
    n_slots = slot_idx.shape[0]
    slot_pos = np.zeros((n_shards, n_slots, nps), np.int32)
    for q in range(n_shards):
        lo = q * nps
        for s in range(n_slots):
            for r in range(nps):
                g = int(slot_idx[s, lo + r])
                slot_pos[q, s, r] = g - lo if lo <= g < lo + nps else recv.pos[q][g]
    owner = hub_rows // nps if len(hub_rows) else np.zeros(0, np.int64)
    h_max = int(max((np.sum(owner == q) for q in range(n_shards)), default=0)) if len(hub_rows) else 0
    hub_loc = np.full((n_shards, h_max), nps, np.int32)  # pad → dropped scatter
    hub_m_t = np.zeros((n_shards, h_max, n), np.float32)
    for q in range(n_shards):
        rows = np.nonzero(owner == q)[0]
        for j, ri in enumerate(rows):
            hub_loc[q, j] = int(hub_rows[ri]) - q * nps
            hub_m_t[q, j] = hub_m[ri]
    return {
        "slot_pos": jnp.asarray(slot_pos),
        "slot_w": jnp.asarray(
            slot_w.reshape(n_slots, n_shards, nps).transpose(1, 0, 2), jnp.float32
        ),
        "hyb_self": jnp.asarray(hyb_self.reshape(n_shards, nps), jnp.float32),
        "hub_loc": jnp.asarray(hub_loc),
        "hub_m": jnp.asarray(hub_m_t),
    }


# ---------------------------------------------------------------------------
# the sharded plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedCommPlan:
    """A ``CommPlan`` rendered over a node-sharded mesh axis.

    Drop-in for the gossip engine's operator protocol: ``mix`` / ``spread``
    / ``spread_min`` take globally shaped payloads, run one ``shard_map``
    internally (jit/scan-traceable) and return globally shaped results that
    are bit-identical to the single-device segment-sum rendering of the same
    plan.  ``local_*`` variants run *inside* an enclosing ``shard_map`` (the
    sharded executor) on per-shard blocks.
    """

    base: CommPlan
    mesh: Mesh
    axis: str
    n_shards: int
    nps: int
    recv: _Layout | None = None  # sparse backends
    send: _Layout | None = None
    hyb: dict | None = None  # sharded HYB tables (clean sparse mix)

    # ------------------------------------------------------------- metadata
    @property
    def n(self) -> int:
        return self.base.n

    @property
    def graph(self):
        return self.base.graph

    @property
    def backend(self) -> str:
        return self.base.backend

    @property
    def failures(self):
        return self.base.failures

    @property
    def data_sizes(self):
        return self.base.data_sizes

    @property
    def n_edges(self) -> int:
        return self.base.n_edges

    # a static plan: round selection is the identity, as on ``CommPlan``
    def select(self, round_index=None) -> "ShardedCommPlan":
        return self

    def round_key(self, key: jax.Array | None, round_index=None) -> jax.Array | None:
        return key

    def cross_shard_rows_per_round(self, op: str = "mix") -> int:
        """Total rows moved across devices per round, all collectives of the
        op included (static — the weak-scaling benchmark's traffic axis)."""
        if self.backend == "dense":
            return self.n_shards * (self.n - self.nps)
        layout = self.send if op == "spread" else self.recv
        if layout is None:
            return 0
        rows = self.n_shards * layout.halo_rows
        if op == "mix" and not self.failures.active and self.hyb is not None:
            if self.hyb["hub_loc"].shape[-1]:
                # heavy-tail hub rows contract against an all-gathered payload
                rows += self.n_shards * (self.n - self.nps)
        return rows

    def collectives_per_round(self, op: str = "mix") -> int:
        """Collective launches per round per payload leaf (static)."""
        if self.n_shards == 1:
            return 0
        if self.backend == "dense":
            return 1
        if self.backend == "ppermute":
            return sum(1 for p in self.base.color_perms() if p)
        layout = self.send if op == "spread" else self.recv
        k = 1 if layout is not None and layout.h_max else 0
        if op == "mix" and not self.failures.active and self.hyb is not None:
            if self.hyb["hub_loc"].shape[-1]:
                k += 1
        return k

    def cross_shard_bytes_per_round(self, row_bytes: int, op: str = "mix") -> int:
        """Cross-shard traffic per round for a payload of ``row_bytes`` per
        node row — the weak-scaling benchmark's bytes axis."""
        return self.cross_shard_rows_per_round(op) * row_bytes

    # ----------------------------------------------------------- primitives
    def _halo_gather(self, x: jax.Array, layout: _Layout, t: dict) -> jax.Array:
        """(nps, ...) local block → (nps + S·h_max, ...) ``[local | halo]``.

        One ``all_to_all`` moves every shard's padded send blocks at once —
        the recv block of source shard q lands at rows ``nps + q*h_max``."""
        if layout.h_max == 0 or self.n_shards == 1:
            return x
        with jax.named_scope("halo_exchange"):
            buf = jnp.take(x, t["send"][0], axis=0)  # (S, h_max, ...)
            recv = jax.lax.all_to_all(buf, self.axis, split_axis=0, concat_axis=0)
            halo = recv.reshape((self.n_shards * layout.h_max,) + x.shape[1:])
            return jnp.concatenate([x, halo], axis=0)

    def _masks(self, key: jax.Array) -> tuple[jax.Array, jax.Array]:
        """The plan's global failure draw, replicated on every shard: same
        key → bit-identical masks to the single-device ``round_masks``."""
        return _draw_failure_masks(self.failures, self.n_edges, self.n, key)

    def _recv_round_weights(
        self, key: jax.Array | None, t: dict
    ) -> tuple[jax.Array, jax.Array]:
        """Per-shard (edge_w, self_w) of this round's effective operator —
        the sharded ``_sparse_round_weights`` (same values, same per-row
        accumulation order for the renormalising denominator)."""
        if not self.failures.active:
            return t["edge_w"][0], t["self_w"][0]
        edge_keep, active = self._masks(key)
        keep = t["valid"][0] & edge_keep[t["uid"][0]]
        keep = keep & active[t["gfar"][0]] & active[t["gown"][0]]
        num = t["raw_edge_w"][0] * keep
        # padding edges (segment nps) fall outside the scatter and drop
        den = t["raw_self_w"][0].at[t["seg"][0]].add(
            num, indices_are_sorted=True, mode="drop"
        )
        den_pad = jnp.concatenate([den, jnp.ones((1,), _F32)])
        return num / den_pad[t["seg"][0]], t["raw_self_w"][0] / den

    # -------------------------------------------------------- local bodies
    def local_mix(self, params: PyTree, key: jax.Array | None, t: dict) -> PyTree:
        """One DecAvg round on per-shard blocks — call inside ``shard_map``
        with ``t = recv.tables()`` passed as node-sharded operands."""
        if self.backend == "dense":
            return self._local_dense("mix", params, key)
        layout = self.recv
        if not self.failures.active and self.hyb is not None:
            return self._local_mix_hyb(params, t)
        edge_w, self_w = self._recv_round_weights(key, t)
        seg, gat = t["seg"][0], t["gat"][0]

        def mix_leaf(x: jax.Array) -> jax.Array:
            x_all = self._halo_gather(x, layout, t)
            gathered = jnp.take(x_all, gat, axis=0).astype(_F32)
            contrib = _bcast(edge_w, x.ndim) * gathered
            # self term first, then edges: decavg.mix_pytree_sparse's order
            out = (_bcast(self_w, x.ndim) * x.astype(_F32)).at[seg].add(
                contrib, indices_are_sorted=True, mode="drop"
            )
            return out.astype(x.dtype)

        return jax.tree_util.tree_map(mix_leaf, params)

    def _local_mix_hyb(self, params: PyTree, t: dict) -> PyTree:
        """Sharded rendering of the clean-topology HYB mix: the per-row slot
        chain gathers from the ``[local | halo]`` buffer in the same slot
        order as ``mix_pytree_hyb`` (bit-identical accumulation), hub rows
        contract their full dense receive rows against an all-gathered
        payload."""
        slot_pos, slot_w = t["slot_pos"][0], t["slot_w"][0]
        self_w = t["hyb_self"][0]
        n_hub = t["hub_loc"].shape[-1]

        def mix_leaf(x: jax.Array) -> jax.Array:
            xf = x.astype(_F32)
            x_all = self._halo_gather(x, self.recv, t).astype(_F32)
            acc = _bcast(self_w, x.ndim) * xf
            for s in range(slot_pos.shape[0]):
                acc = acc + _bcast(slot_w[s], x.ndim) * jnp.take(
                    x_all, slot_pos[s], axis=0
                )
            if n_hub:
                x_full = jax.lax.all_gather(xf, self.axis, axis=0, tiled=True)
                hub_out = jnp.tensordot(
                    t["hub_m"][0], x_full, axes=[[1], [0]],
                    precision=MIX_PRECISION, preferred_element_type=_F32,
                )
                acc = acc.at[t["hub_loc"][0]].set(hub_out)
            return acc.astype(x.dtype)

        return jax.tree_util.tree_map(mix_leaf, params)

    def local_spread(self, x: jax.Array, key: jax.Array | None, t: dict) -> jax.Array:
        """Send-form round on the (nps, k) local block (src-sorted layout)."""
        if self.backend == "dense":
            return self._local_dense("spread", x, key)
        layout = self.send
        if not self.failures.active:
            edge_w, self_w = t["edge_w"][0], t["self_w"][0]
        else:
            # the renormalising denominator is indexed by the *remote* dst
            # endpoint, so each shard replays the global replicated reduction
            # (masks are replicated anyway; O(nnz) elementwise work)
            edge_keep, active = self._masks(key)
            g = self.base
            keep = edge_keep[g.edge_uid] & active[g.src] & active[g.dst]
            num_g = g.raw_edge_w * keep
            den_g = g.raw_self_w.at[g.dst].add(num_g, indices_are_sorted=True)
            p = t["perm"][0]
            edge_w = jnp.where(
                t["valid"][0], num_g[p] / den_g[t["gfar"][0]], jnp.float32(0.0)
            )
            i = jax.lax.axis_index(self.axis)
            den_l = jax.lax.dynamic_slice_in_dim(den_g, i * self.nps, self.nps)
            self_w = t["raw_self_w"][0] / den_l
        x_all = self._halo_gather(x, layout, t)
        contrib = edge_w[:, None] * x_all[t["gat"][0]]
        return (self_w[:, None] * x).at[t["seg"][0]].add(
            contrib, indices_are_sorted=True, mode="drop"
        )

    def local_spread_min(
        self, x: jax.Array, key: jax.Array | None, t: dict
    ) -> jax.Array:
        """Min-exchange round on the (nps, k) local block (receive layout)."""
        if self.backend == "dense":
            return self._local_dense("spread_min", x, key)
        layout = self.recv
        keep = t["valid"][0]
        if self.failures.active:
            edge_keep, active = self._masks(key)
            keep = keep & edge_keep[t["uid"][0]]
            keep = keep & active[t["gfar"][0]] & active[t["gown"][0]]
        x_all = self._halo_gather(x, layout, t)
        gathered = jnp.where(keep[:, None], x_all[t["gat"][0]], jnp.float32(jnp.inf))
        nbr = jax.ops.segment_min(
            gathered, t["seg"][0], num_segments=self.nps + 1, indices_are_sorted=True
        )[: self.nps]
        return jnp.minimum(x, nbr)

    def _local_dense(self, op: str, payload, key: jax.Array | None):
        """Row-block rendering of the dense backend: the (replicated) round
        matrix is sliced at ``axis_index`` and the payload all-gathered —
        dense mixing's inherent node-axis gather, made explicit."""
        m = self.base._dense_round_matrix(key)
        i = jax.lax.axis_index(self.axis)
        if op == "mix":
            block = jax.lax.dynamic_slice_in_dim(m, i * self.nps, self.nps, axis=0)

            def mix_leaf(x: jax.Array) -> jax.Array:
                x_full = jax.lax.all_gather(x, self.axis, axis=0, tiled=True)
                out = jnp.tensordot(
                    block, x_full, axes=[[1], [0]],
                    precision=MIX_PRECISION, preferred_element_type=_F32,
                )
                return out.astype(x.dtype)

            return jax.tree_util.tree_map(mix_leaf, payload)
        x_full = jax.lax.all_gather(payload, self.axis, axis=0, tiled=True)
        if op == "spread":
            cols = jax.lax.dynamic_slice_in_dim(m, i * self.nps, self.nps, axis=1)
            return jnp.einsum("ji,jk->ik", cols, x_full, precision=MIX_PRECISION)
        # spread_min: surviving-neighbourhood mask rows
        keep = self.base.adjacency > 0
        if self.failures.active:
            edge_keep, active = self._masks(key)
            keep = keep & edge_keep[self.base.edge_uid_matrix]
            keep = keep & active[:, None] & active[None, :]
        rows = jax.lax.dynamic_slice_in_dim(keep, i * self.nps, self.nps, axis=0)
        nbr = jnp.where(rows[:, :, None], x_full[None, :, :], jnp.float32(jnp.inf))
        return jnp.minimum(payload, nbr.min(axis=1))

    # ------------------------------------------------------ public operator
    def _specs_for(self, tree: PyTree) -> PyTree:
        ax = self.axis
        return jax.tree_util.tree_map(
            lambda l: P(ax, *([None] * (l.ndim - 1))), tree
        )

    def _run(self, op: str, payload: PyTree, key: jax.Array | None) -> PyTree:
        if self.failures.active and key is None:
            raise ValueError("failure model active: sharded ops need a PRNG key")
        if self.backend == "ppermute":
            return self._run_colored(op, payload, key)
        local_fn = getattr(self, f"local_{op}")
        if self.backend == "dense":
            tables: dict[str, jax.Array] = {}
        elif op == "mix":
            tables = self._mix_tables()
        else:
            layout = self.send if op == "spread" else self.recv
            tables = layout.tables()
        pay_specs = self._specs_for(payload)
        tab_specs = self._specs_for(tables)
        if key is None:
            f = jax.shard_map(
                lambda pay, t: local_fn(pay, None, t),
                mesh=self.mesh,
                in_specs=(pay_specs, tab_specs),
                out_specs=pay_specs,
            )
            return f(payload, tables)
        f = jax.shard_map(
            lambda pay, k, t: local_fn(pay, k, t),
            mesh=self.mesh,
            in_specs=(pay_specs, P(), tab_specs),
            out_specs=pay_specs,
        )
        return f(payload, key, tables)

    def mix(self, params: PyTree, key: jax.Array | None = None) -> PyTree:
        """One DecAvg aggregation of a globally shaped node-stacked pytree."""
        return self._run("mix", params, key)

    def spread(self, values: jax.Array, key: jax.Array | None = None) -> jax.Array:
        """One send-form (column-stochastic) round — ``CommPlan.spread``."""
        x = jnp.asarray(values, _F32)
        squeeze = x.ndim == 1
        if squeeze:
            x = x[:, None]
        out = self._run("spread", x, key)
        return out[:, 0] if squeeze else out

    def spread_min(self, values: jax.Array, key: jax.Array | None = None) -> jax.Array:
        """One neighbourhood min-exchange round — ``CommPlan.spread_min``."""
        x = jnp.asarray(values, _F32)
        squeeze = x.ndim == 1
        if squeeze:
            x = x[:, None]
        out = self._run("spread_min", x, key)
        return out[:, 0] if squeeze else out

    # ------------------------------------------------- ppermute (nps == 1)
    def _color_round_weights_local(
        self, key: jax.Array | None, t: dict
    ) -> tuple[jax.Array, jax.Array]:
        """Local column of ``color_round_weights`` — (n_colors, 1), (1,)."""
        if not self.failures.active:
            return t["color_w"], t["self_w"]
        edge_keep, active = self._masks(key)
        matched = t["color_uid"] >= 0
        keep = matched & edge_keep[jnp.clip(t["color_uid"], 0, None)]
        i = jax.lax.axis_index(self.axis)
        keep = keep & active[i] & jnp.take(active, t["partner"])
        num = t["color_raw_w"] * keep
        den = t["raw_self_w"] + num.sum(axis=0)
        return num / den[None, :], t["raw_self_w"] / den

    def local_colored(
        self, op: str, pay: PyTree, key: jax.Array | None, t: dict
    ) -> PyTree:
        """Colour-matching backend body: one node per device group, each
        colour class one true ``ppermute`` round (the collective rendering
        DESIGN.md §12 flagged as emulated)."""
        ax = self.axis
        base = self.base
        if op == "mix":
            cw, sw = self._color_round_weights_local(key, t)
            return mix_pytree_colored(pay, base.partners, cw, sw, axis_name=ax)
        perms = base.color_perms()
        if op == "spread":
            cw, sw = self._color_round_weights_local(key, t)
            x = pay
            acc = sw[:, None] * x
            for c in range(base.n_colors):
                if not perms[c]:
                    continue
                # the mass each node pushes along its colour-c edge lands on
                # the opposite endpoint — weights travel with the payload
                acc = acc + jax.lax.ppermute(cw[c][:, None] * x, ax, perms[c])
            return acc
        # spread_min
        keep = t["color_uid"] >= 0
        if self.failures.active:
            edge_keep, active = self._masks(key)
            keep = keep & edge_keep[jnp.clip(t["color_uid"], 0, None)]
            i = jax.lax.axis_index(ax)
            keep = keep & active[i] & jnp.take(active, t["partner"])
        x = pay
        inf = jnp.float32(jnp.inf)
        nbr = jnp.full_like(x, inf)
        for c in range(base.n_colors):
            if not perms[c]:
                continue
            cand = jax.lax.ppermute(x, ax, perms[c])
            nbr = jnp.minimum(nbr, jnp.where(keep[c][:, None], cand, inf))
        return jnp.minimum(x, nbr)

    def _colored_tables(self) -> tuple[dict, dict]:
        base = self.base
        tables = {
            "color_w": base.color_w,
            "color_raw_w": base.color_raw_w,
            "color_uid": base.color_edge_uid,
            "partner": jnp.asarray(base.partners),
            "self_w": base.self_w,
            "raw_self_w": base.raw_self_w,
        }
        ax = self.axis
        specs = {k: P(ax) if v.ndim == 1 else P(None, ax) for k, v in tables.items()}
        return tables, specs

    def mix_operands(self) -> tuple[dict, dict]:
        """(tables, in_specs) an enclosing ``shard_map`` (e.g. the sharded
        executor) passes through to ``local_mix_any`` — the per-shard mixing
        tables of this plan's backend."""
        if self.backend == "dense":
            return {}, {}
        if self.backend == "ppermute":
            return self._colored_tables()
        t = self._mix_tables()
        return t, self._specs_for(t)

    def _mix_tables(self) -> dict[str, jax.Array]:
        """Receive-layout tables, plus the sharded HYB tables on the clean
        static-topology path (where ``local_mix`` takes the slot chain)."""
        t = self.recv.tables()
        if not self.failures.active and self.hyb is not None:
            t = {**t, **self.hyb}
        return t

    def local_mix_any(self, params: PyTree, key: jax.Array | None, t: dict) -> PyTree:
        """Backend-dispatching ``local_mix`` for use inside an enclosing
        ``shard_map`` with ``mix_operands()``'s tables."""
        if self.backend == "ppermute":
            return self.local_colored("mix", params, key, t)
        return self.local_mix(params, key, t)

    def _run_colored(self, op: str, payload: PyTree, key: jax.Array | None) -> PyTree:
        tables, tab_specs = self._colored_tables()
        pay_specs = self._specs_for(payload)
        if key is None:
            f = jax.shard_map(
                lambda pay, t: self.local_colored(op, pay, None, t),
                mesh=self.mesh,
                in_specs=(pay_specs, tab_specs),
                out_specs=pay_specs,
            )
            return f(payload, tables)
        f = jax.shard_map(
            lambda pay, k, t: self.local_colored(op, pay, k, t),
            mesh=self.mesh,
            in_specs=(pay_specs, P(), tab_specs),
            out_specs=pay_specs,
        )
        return f(payload, key, tables)

    # ------------------------------------------------------------- plumbing
    def with_options(self, **kw) -> "ShardedCommPlan":
        """Recompile the base plan with some knobs replaced, re-sharded over
        the same mesh/axis."""
        return shard_plan(self.base.with_options(**kw), mesh=self.mesh, axis=self.axis)


def shard_plan(
    plan: CommPlan,
    *,
    mesh: Mesh | None = None,
    axis: str | None = None,
    n_shards: int | None = None,
) -> ShardedCommPlan:
    """Render a compiled ``CommPlan`` over a node-sharded mesh axis.

    ``mesh``/``axis`` name the node axis (e.g. ``launch.mesh.node_mesh(4)``
    with axis ``"node"``); alternatively give just ``n_shards`` and a 1-D
    mesh over the first ``n_shards`` local devices is built here.  Nodes are
    partitioned contiguously — shard s owns rows ``[s·nps, (s+1)·nps)`` —
    and ``n`` must divide evenly.  The ppermute backend additionally
    requires one node per device (``nps == 1``), where the colour matchings
    run as true per-colour collective rounds.
    """
    if mesh is None:
        if n_shards is None:
            raise ValueError("shard_plan needs a mesh or an explicit n_shards")
        devs = jax.devices()
        if n_shards > len(devs):
            raise ValueError(f"n_shards={n_shards} exceeds {len(devs)} devices")
        mesh = Mesh(np.asarray(devs[:n_shards]), (axis or "node",))
        axis = axis or "node"
    if axis is None:
        if len(mesh.axis_names) != 1:
            raise ValueError(f"mesh has axes {mesh.axis_names}; pass axis=...")
        axis = mesh.axis_names[0]
    if isinstance(axis, (tuple, list)):
        if len(axis) != 1:
            raise ValueError(f"sharded plans need a single node axis, got {axis}")
        axis = axis[0]
    shards = int(mesh.shape[axis])
    if n_shards is not None and n_shards != shards:
        raise ValueError(f"n_shards={n_shards} but mesh axis {axis!r} has {shards}")
    n = plan.n
    if n % shards:
        raise ValueError(f"n={n} nodes not divisible into {shards} shards")
    nps = n // shards

    if plan.backend == "ppermute":
        if nps != 1:
            raise ValueError(
                "the ppermute backend shards one node per device group; use the "
                f"sparse backend for nodes-per-shard {nps} > 1"
            )
        return ShardedCommPlan(base=plan, mesh=mesh, axis=axis, n_shards=shards, nps=nps)
    if plan.backend == "dense":
        return ShardedCommPlan(base=plan, mesh=mesh, axis=axis, n_shards=shards, nps=nps)

    src = np.asarray(plan.src)
    dst = np.asarray(plan.dst)
    uid = np.asarray(plan.edge_uid)
    edge_w = np.asarray(plan.edge_w)
    raw_edge_w = np.asarray(plan.raw_edge_w)
    self_w = np.asarray(plan.self_w)
    raw_self_w = np.asarray(plan.raw_self_w)
    ident = np.arange(len(src), dtype=np.int32)
    recv = _build_layout(
        n, shards, dst, src, uid, edge_w, raw_edge_w, ident, self_w, raw_self_w
    )
    order = np.lexsort((dst, src))  # src-major, dst-minor: the send layout
    send = _build_layout(
        n,
        shards,
        src[order],
        dst[order],
        uid[order],
        edge_w[order],
        raw_edge_w[order],
        ident[order],
        self_w,
        raw_self_w,
    )
    return ShardedCommPlan(
        base=plan,
        mesh=mesh,
        axis=axis,
        n_shards=shards,
        nps=nps,
        recv=recv,
        send=send,
        hyb=_build_hyb_tables(plan, recv, shards),
    )
