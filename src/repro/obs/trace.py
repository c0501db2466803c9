"""Program spans and counters of the fused executors (DESIGN.md §17).

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation``: it is
recorded exactly when a profiler runs (``launch/train.py --profile-trace``,
or any ``jax.profiler.start_trace``), on the trace's host plane and on the
same clock as the device ops, with ``attrs`` as event stats.  With no
profiler running it costs well under a microsecond.  ``count``/``counts``
are process-wide integer counters.

The device side of the same account is the round body's
``jax.named_scope``s (``SCOPES``): each device op carries its scope path in
the trace, so the spans say what the host did while the device idled and
the scopes say what the device did.  The tables name each span, scope and
counter, what it covers and what reads it.
"""
from __future__ import annotations

import collections

import jax

__all__ = ["COUNTERS", "SCOPES", "SPANS", "count", "counts", "span"]

# host spans: name -> what it covers; what reads it
SPANS = {
    "dfl.trajectory": "one executor call (attrs n_rounds, chunks); the operator's timeline",
    "dfl.chunk": "one chunk (attrs ci, r0, r1); its self time is the caller's on_chunk",
    "dfl.chunk.slice": "the chunk's slices of the schedule and eval mask; dispatch_idle_ms",
    "dfl.chunk.dispatch": "the chunk program's enqueue (first chunk: trace, compile or "
                          "cache load); dispatch_idle_ms",
    "dfl.chunk.fetch": "device-to-host copy of the chunk's metric buffers for on_chunk; "
                       "fetch_idle_ms",
    "dfl.chunk.checkpoint": "one chunk-boundary checkpoint save: the stall per save",
    "dfl.assemble": "the end-of-call fetch and concatenation of the metric buffers",
}

# device scopes (``jax.named_scope``) of the round body; what reads each
SCOPES = {
    "dfl_round": "the round's scalars: PRNG split, round counter, loss means; "
                 "bookkeeping_ms_per_round",
    "dfl_batch": "the per-node minibatch gather; bookkeeping_ms_per_round",
    "dfl_local": "the local SGD steps of every node; local_ms_per_round",
    "dfl_mix": "DecAvg with its link masks; mix_ms_per_round",
    "halo_exchange": "the sharded mix's cross-shard rows, under dfl_mix; the operator's timeline",
    "dfl_reinit": "the optimizer re-initialisation after the mix; bookkeeping_ms_per_round",
    "dfl_wire": "the replay of the round's delivered-message count; bookkeeping_ms_per_round",
    "dfl_eval": "the gated test loss; eval_ms_per_eval",
    "dfl_sigma": "the gated sigma_ap/sigma_an moments; bookkeeping_ms_per_round",
    # the model's own (repro.models.paper_models CNN and VGG forwards); under
    # a transformation the path part reads e.g. transpose(jvp(model_conv))
    "model_conv": "one convolution with its bias and ReLU; conv_ms_per_round, conv_roofline",
    "model_pool": "one 2x2 max-pool (its backward a select-and-scatter); pool_ms_per_round",
    "model_dense": "one classifier layer; the operator's timeline",
}

# counters: name -> what increments it; what reads it
COUNTERS = {
    "dfl.calls": "one per executor call; traces_per_call",
    "dfl.chunk_traces": "one per trace of a chunk program's Python body; traces_per_call",
    "commplan.auto_dense": "one per backend=\"auto\" plan or schedule resolved to dense; "
                           "which mix a run got",
    "commplan.auto_sparse": "one per backend=\"auto\" plan or schedule resolved to sparse; "
                            "which mix a run got",
}

_counts: collections.Counter = collections.Counter()


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` with ``attrs`` as its stats, for a ``with``."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def count(name: str, n: int = 1) -> None:
    _counts[name] += n


def counts() -> dict[str, int]:
    """A copy of every counter's value in this process."""
    return dict(_counts)
