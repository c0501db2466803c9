"""Host-side run-log export: JSONL records + run manifest (DESIGN.md §17).

A run log is newline-delimited JSON: the first record is the **manifest**
(``kind: "manifest"`` — config, seed, git rev, backend, schema version),
followed by one record per recorded round/bin (:func:`history_rows`) and any
trailing summary records the driver appends (final metrics, gossip health).
Everything is sanitised to strict JSON — NaN/Inf become null, numpy scalars
become Python numbers — so any downstream reader parses it.

:func:`profile_trace` is the opt-in ``jax.profiler`` capture used by
``launch/train.py --profile-trace DIR``; the executors' host spans and the
round body's device scopes (:mod:`repro.obs.trace`) show up inside it.
"""

from __future__ import annotations

import contextlib
import json
import math
import subprocess
from pathlib import Path
from typing import Any, Iterable, Iterator

import jax

__all__ = [
    "SCHEMA_VERSION",
    "git_rev",
    "history_rows",
    "profile_trace",
    "read_run_log",
    "run_manifest",
    "validate_run_log",
    "write_run_log",
]

SCHEMA_VERSION = 1

# keys every manifest must carry — the check_bench --run-log gate enforces this
MANIFEST_KEYS = ("kind", "schema", "config", "seed", "git_rev", "backend", "jax_version")


def _sanitize(obj: Any) -> Any:
    """Strict-JSON form: NaN/Inf → None, numpy/jax scalars → Python."""
    if obj is None or isinstance(obj, (bool, int, str)):
        return obj
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {str(k): _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    if hasattr(obj, "item") and not hasattr(obj, "__len__"):
        return _sanitize(obj.item())
    if hasattr(obj, "tolist"):
        return _sanitize(obj.tolist())
    return str(obj)


def git_rev(cwd: str | Path | None = None) -> str:
    """Short git revision of the working tree, or "unknown" outside a repo."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=str(cwd) if cwd else None,
            capture_output=True,
            text=True,
            timeout=10,
        )
    except (OSError, subprocess.TimeoutExpired):
        return "unknown"
    return out.stdout.strip() if out.returncode == 0 and out.stdout.strip() else "unknown"


def run_manifest(config: dict, *, seed: int, argv: list[str] | None = None) -> dict:
    """The run log's head record: everything needed to re-run or diff it."""
    return _sanitize(
        {
            "kind": "manifest",
            "schema": SCHEMA_VERSION,
            "config": config,
            "seed": int(seed),
            "argv": list(argv) if argv is not None else None,
            "git_rev": git_rev(),
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "n_devices": jax.device_count(),
        }
    )


def history_rows(hist: dict, kind: str = "round") -> list[dict]:
    """History dict → one record per recorded index.

    The index channel is ``round`` (synchronous executors) or ``bin``
    (event-driven); only keys whose list length matches the index ride
    along — scalars and mismatched extras are the driver's job to append
    as summary records.
    """
    index_key = "bin" if "bin" in hist and hist.get("bin") else "round"
    index = hist.get(index_key) or []
    n = len(index)
    if n == 0:
        return []
    keys = [k for k, v in hist.items() if isinstance(v, (list, tuple)) and len(v) == n]
    return [
        _sanitize({"kind": kind, **{k: hist[k][i] for k in keys}}) for i in range(n)
    ]


def write_run_log(path: str | Path, records: Iterable[dict]) -> int:
    """Write records as JSONL (strict JSON, one object per line); returns
    the record count.  Callers compose ``[manifest, *rows, *summaries]``."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    n = 0
    with path.open("w") as fh:
        for rec in records:
            fh.write(json.dumps(_sanitize(rec), allow_nan=False) + "\n")
            n += 1
    return n


def read_run_log(path: str | Path) -> list[dict]:
    """Parse a JSONL run log back into its records."""
    with Path(path).open() as fh:
        return [json.loads(line) for line in fh if line.strip()]


def validate_run_log(records: list[dict] | str | Path) -> list[str]:
    """Schema-gate a run log; returns human-readable problems (empty = ok).

    Checks: non-empty, manifest-first with :data:`MANIFEST_KEYS` and a
    matching schema version, every record a dict with a ``kind``, and at
    least one data (non-manifest) record.
    """
    if isinstance(records, (str, Path)):
        try:
            records = read_run_log(records)
        except (OSError, json.JSONDecodeError) as exc:
            return [f"unreadable run log: {exc}"]
    problems: list[str] = []
    if not records:
        return ["empty run log"]
    head = records[0]
    if not isinstance(head, dict) or head.get("kind") != "manifest":
        problems.append("first record is not a manifest")
    else:
        missing = [k for k in MANIFEST_KEYS if k not in head]
        if missing:
            problems.append(f"manifest missing keys: {missing}")
        if head.get("schema") != SCHEMA_VERSION:
            problems.append(
                f"manifest schema {head.get('schema')!r} != {SCHEMA_VERSION}"
            )
    for i, rec in enumerate(records[1:], start=2):
        if not isinstance(rec, dict) or "kind" not in rec:
            problems.append(f"record {i} has no 'kind'")
            break
    if sum(1 for r in records if isinstance(r, dict) and r.get("kind") != "manifest") == 0:
        problems.append("no data records after the manifest")
    return problems


@contextlib.contextmanager
def profile_trace(trace_dir: str | Path | None) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace into ``trace_dir`` (no-op if falsy).

    On the host plane, the executors' spans (``repro.obs.trace.SPANS``):
    ``dfl.trajectory`` per call, ``dfl.chunk`` per chunk with its
    ``dfl.chunk.slice``, ``dfl.chunk.dispatch``, ``dfl.chunk.fetch`` and
    ``dfl.chunk.checkpoint``, then ``dfl.assemble``.  On the device, each op
    carries the round body's scope (``repro.obs.trace.SCOPES``):
    ``dfl_round``, ``dfl_batch``, ``dfl_local``, ``dfl_mix`` (with
    ``halo_exchange`` under it when sharded), ``dfl_reinit``, ``dfl_wire``,
    ``dfl_eval``, ``dfl_sigma``.
    """
    if not trace_dir:
        yield
        return
    Path(trace_dir).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
