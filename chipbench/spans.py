"""The program's own spans and scopes in a reduced trace.

The program names its host spans (``dfl.chunk.fetch``, ``dfl.chunk.slice``,
``dfl.chunk.dispatch``, ...) and the device scopes of its round body
(``dfl_*``, ``halo_exchange`` under ``dfl_mix``); both reach the trace, the
spans on the host plane and the scopes in each device op's ``tf_op`` path.
These helpers sum a ``chipbench.trace.Summary``'s leaf ops by scope and
intersect the device's idle gaps with host spans, for the readers in
``chipbench/metrics/``.  Each returns None where the trace holds none of
what it looks for, as a program without these spans or scopes leaves it.
"""
from __future__ import annotations

from chipbench.trace import _union

# scopes of the round body outside local steps, mix and eval
BOOKKEEPING = ("dfl_round", "dfl_batch", "dfl_wire", "dfl_reinit", "dfl_sigma")


def _parts(op) -> list[str]:
    return op.scope.split("/")


def scoped(op) -> bool:
    """Whether the op lies under one of the program's scopes."""
    return any(p.startswith("dfl_") or p == "halo_exchange" for p in _parts(op))


def ops_s(summary, keep) -> float | None:
    """Device seconds of the window's leaf ops for which ``keep(op)`` holds,
    each op once, mean over devices; None where no op of the window does."""
    per, found = [], False
    for d in summary.ops:
        t = 0.0
        for o in d:
            if o.end <= summary.lo or o.start >= summary.hi or not keep(o):
                continue
            found = True
            t += min(o.end, summary.hi) - max(o.start, summary.lo)
        per.append(t)
    return sum(per) / len(per) / 1e9 if found else None


def under(*scopes: str):
    """``keep`` for ``ops_s``: the op lies under any of ``scopes``."""
    return lambda op: any(p in scopes for p in _parts(op))


def span_idle_s(summary, names) -> float | None:
    """Device idle seconds of the window that fall inside a host span named
    in ``names`` (the union of those spans), mean over devices; None where
    no such span overlaps the window."""
    spans = _union(
        (max(h.start, summary.lo), min(h.end, summary.hi))
        for h in summary.host
        if h.name in names and h.end > summary.lo and h.start < summary.hi
    )
    if not spans:
        return None
    per = []
    for d in range(len(summary.modules)):
        per.append(sum(
            max(0.0, min(ge, se) - max(gs, ss))
            for gs, ge in summary.gaps(d) for ss, se in spans
        ))
    return sum(per) / len(per) / 1e9


def boundaries(summary) -> int:
    """Chunk boundaries in the window: the harness's marks before its end."""
    return sum(1 for m in summary.marks if m < summary.hi)
