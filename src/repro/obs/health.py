"""Gossip-health channels (DESIGN.md §17) riding ``gossip.diagnostics``.

Three measurements, all JSON-able:

* :func:`mass_drift_trace` — per-round |Σs − Σs₀|/Σs₀ of a spread payload;
  ``spread`` is column-stochastic so any drift is pure fp32 error, and this
  curve is the canary for a broken mask/renormalisation path.
* :func:`staleness_histogram` — fixed-width linear bucketing of event-driven
  parameter staleness (the executor accumulates the buckets in-scan).
* :func:`gossip_health` — one dict bundling the convergence report's
  fitted-vs-predicted contraction with the measured mass drift.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.gossip.diagnostics import convergence_report

__all__ = [
    "gossip_health",
    "mass_drift_trace",
    "staleness_histogram",
]


def mass_drift_trace(plan, rounds: int, key=None) -> np.ndarray:
    """(rounds + 1,) relative total-mass drift of a unit payload under
    ``plan.spread`` — exactly zero in exact arithmetic (column-stochastic),
    so the curve measures fp32 conservation through the masked backends.

    ``key`` seeds per-round failure draws when the plan's failure model is
    active (round r uses ``fold_in(key, r)``, the executors' convention).
    """
    spread = jax.jit(plan.spread)
    x = jnp.ones((plan.n,), jnp.float32)
    total0 = float(plan.n)
    drift = [0.0]
    for r in range(rounds):
        k = jax.random.fold_in(key, r) if key is not None else None
        x = spread(x, k)
        drift.append(abs(float(jnp.sum(x)) - total0) / total0)
    return np.asarray(drift, dtype=np.float64)


def staleness_histogram(counts, horizon: float) -> dict:
    """In-scan staleness buckets → ``{counts, edges}`` (JSON-able lists).

    ``counts`` is the executor's fixed-width accumulator (linear buckets
    over [0, horizon], last bucket catching everything beyond); ``edges``
    are the n+1 bucket boundaries in the staleness unit (wall time).
    """
    c = np.asarray(counts, dtype=np.float64)
    edges = np.linspace(0.0, float(horizon), len(c) + 1)
    return {
        "counts": [float(v) for v in c],
        "edges": [float(e) for e in edges],
    }


def gossip_health(plan, rounds: int, key=None, *, leader: int = 0) -> dict:
    """Measured gossip health of one plan: fitted vs predicted contraction,
    rounds-to-1%, and push-sum mass conservation.  All scalars/lists."""
    rep = convergence_report(plan, rounds, key, leader=leader)
    drift = mass_drift_trace(plan, rounds, key)
    return {
        "fitted_rate": float(rep["fitted_rate"]),
        "predicted_rate": float(rep["predicted_rate"]),
        "rounds_to_1pct": int(rep["rounds_to_1pct"]),
        "max_rel_err": [float(v) for v in rep["max_rel_err"]],
        "mass_drift_max": float(drift.max()),
        "mass_drift": [float(v) for v in drift],
    }
