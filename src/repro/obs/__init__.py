"""``repro.obs`` — typed in-scan telemetry for the fused executors (DESIGN.md §17).

One observability layer, four writers: ``run_trajectory``,
``run_event_trajectory``, ``run_elastic_trajectory`` and
``run_sharded_trajectory`` all route their per-round metric buffers through
the :class:`MetricsSpec`/:class:`Recorder` abstraction (bit-identical to the
hand-rolled outs they replace), report bytes-on-the-wire via the
:mod:`~repro.obs.wirecost` accountant, and export host-side JSONL run logs
through :mod:`~repro.obs.export`.  :mod:`~repro.obs.trace` names the
executors' host spans (``dfl.trajectory``, ``dfl.chunk`` and its slice,
dispatch, fetch and checkpoint, ``dfl.assemble``), the round body's device
scopes (``dfl_round``, ``dfl_batch``, ``dfl_local``, ``dfl_mix``,
``dfl_reinit``, ``dfl_wire``, ``dfl_eval``, ``dfl_sigma``) and the
``dfl.calls``/``dfl.chunk_traces`` and ``batch.select_onehot``/
``batch.select_gather`` counters; a profiler trace records them
on one clock.
"""

from .export import (
    SCHEMA_VERSION,
    git_rev,
    history_rows,
    profile_trace,
    read_run_log,
    run_manifest,
    validate_run_log,
    write_run_log,
)
from .health import gossip_health, mass_drift_trace, staleness_histogram
from .spec import BinChannel, BinSpec, Channel, MetricsSpec, Recorder
from .trace import COUNTERS, SCOPES, SPANS, count, counts, span
from .wirecost import (
    make_wire_fn,
    param_row_bytes,
    sharded_wire_per_round,
    static_wire_messages,
)

__all__ = [
    "COUNTERS",
    "SCHEMA_VERSION",
    "SCOPES",
    "SPANS",
    "BinChannel",
    "BinSpec",
    "Channel",
    "MetricsSpec",
    "Recorder",
    "count",
    "counts",
    "git_rev",
    "gossip_health",
    "history_rows",
    "make_wire_fn",
    "mass_drift_trace",
    "param_row_bytes",
    "profile_trace",
    "read_run_log",
    "run_manifest",
    "sharded_wire_per_round",
    "span",
    "staleness_histogram",
    "static_wire_messages",
    "validate_run_log",
    "write_run_log",
]
