"""Communication-cost accounting: messages and bytes on the wire (§17).

Every trajectory reports what its mixing actually *transmitted*.  The
counts derive from the plans' static structure composed with the same
per-round masks the operators themselves apply:

* **synchronous plans** — one DecAvg round exchanges two full models per
  live undirected edge.  Clean rounds are a static count; under failures /
  membership / fault masks :func:`make_wire_fn` replays the round's failure
  draws from the *same* ``k_mix`` the mix consumes (the repo-wide
  host-replayable key discipline) and counts the surviving edges on device.
* **event-driven plans** — the executor already tracks delivered exchanges
  per wall-time bin; bytes follow as ``messages × row bytes``.
* **sharded plans** — ``ShardedCommPlan`` exposes static per-round halo
  rows / collective counts; :func:`sharded_wire_per_round` scales them by
  the payload's per-node row bytes.

Directed plans carry no event tables (a pairwise exchange has no
orientation), so they get no wire channels — the accountant returns None
and the executors skip the channel rather than guess.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.commplan import CommPlan, PlanSchedule

__all__ = [
    "make_wire_fn",
    "param_row_bytes",
    "sharded_wire_per_round",
    "static_wire_messages",
]


def param_row_bytes(
    params: Any,
    codec_bytes: Callable[[int, Any], float] | None = None,
) -> int:
    """Bytes of ONE node's model — every leaf carries a leading node axis,
    so a node's row is ``leaf.size / n`` elements per leaf.

    The per-leaf node axis is read off each leaf's own leading dim (a
    mixed-dtype pytree prices every leaf at its *own* itemsize, and a
    scalar/unstacked leaf counts in full rather than crashing the
    accountant).  ``codec_bytes(row_elems, dtype) -> float`` overrides the
    itemsize pricing with a compressed encoding's wire cost — pass
    ``Compression.leaf_row_bytes`` (``repro.core.compress``) so quantised /
    sparsified exchanges stop over-reporting at the raw dtype width.
    Fractional per-leaf costs accumulate exactly; only the total rounds.
    """
    leaves = jax.tree_util.tree_leaves(params)
    if not leaves:
        return 0
    total = 0.0
    for leaf in leaves:
        row_elems = leaf.size // leaf.shape[0] if leaf.ndim else leaf.size
        if codec_bytes is not None:
            total += float(codec_bytes(int(row_elems), leaf.dtype))
        else:
            total += row_elems * np.dtype(leaf.dtype).itemsize
    return int(round(total))


def _event_plan(plan: CommPlan | PlanSchedule) -> CommPlan | None:
    probe = plan.plans[0]
    return probe if probe.event_uv is not None else None


def static_wire_messages(plan: CommPlan | PlanSchedule, n_rounds: int) -> np.ndarray | None:
    """(n_rounds,) clean-path delivered messages per round, host-side.

    Two messages per undirected edge of the round's active plan; a
    ``PlanSchedule`` resolves its round map so churned rounds report the
    snapshot they actually mixed over.  None for directed plans.
    """
    if _event_plan(plan) is None:
        return None
    per_plan = np.array([2 * p.n_edges for p in plan.plans], dtype=np.int64)
    if len(per_plan) == 1:
        return np.full(n_rounds, per_plan[0])
    return per_plan[np.asarray(plan.plan_index(np.arange(n_rounds)))]


def make_wire_fn(
    plan: CommPlan | PlanSchedule,
) -> Callable[..., jax.Array] | None:
    """Traced per-round delivered-message accountant, or None.

    ``wire(k_mix, round_index, active=None, edge_live=None)`` returns the
    float32 count of messages this round's *effective* operator delivers:
    the static edge set masked by the Bernoulli failure draws — replayed
    through ``_round_masks_ext`` with exactly the key the mix consumes, so
    the count matches the operator bit for bit — AND the deterministic
    membership / fault masks.  An edge delivers iff its draw survives and
    both endpoints are active (the masked-mix semantics); each delivery is
    two messages.  None for directed plans (no event tables to count over).
    """
    if _event_plan(plan) is None:
        return None
    def wire(key, round_index, active=None, edge_live=None) -> jax.Array:
        view = plan.select(round_index)
        edge_keep, node_act = view._round_masks_ext(
            plan.round_key(key, round_index), active, edge_live
        )
        uv = view.event_uv
        # schedule envelopes pad event rows with exactly-zero weights (and a
        # 1-row pad on edgeless graphs) — real edges always weigh > 0
        valid = view.event_w.max(axis=1) > 0
        live = valid & edge_keep[: uv.shape[0]] & node_act[uv[:, 0]] & node_act[uv[:, 1]]
        return 2.0 * live.sum().astype(jnp.float32)

    return wire


def sharded_wire_per_round(
    plan, params: Any, codec_bytes: Callable[[int, Any], float] | None = None
) -> dict[str, int]:
    """Static per-round wire stats of a ``ShardedCommPlan`` mix.

    ``wire_bytes`` is the cross-shard halo traffic for the full parameter
    payload (the plan's static row count × the model's per-node row bytes,
    every leaf's halo exchange included); ``wire_collectives`` counts
    collective launches per round (the plan's per-leaf count × leaves).
    ``codec_bytes`` follows :func:`param_row_bytes` — compressed halo rows
    price at the codec's encoding.
    """
    row_bytes = param_row_bytes(params, codec_bytes=codec_bytes)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    return {
        "wire_bytes": int(plan.cross_shard_bytes_per_round(row_bytes, "mix")),
        "wire_rows": int(plan.cross_shard_rows_per_round("mix")),
        "wire_collectives": int(plan.collectives_per_round("mix") * n_leaves),
    }
