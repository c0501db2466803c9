"""Per-layer metric readers, one module per metric, found by its name in
``BENCHMARK.json``.  Each defines ``read(ctx) -> float | None``; a reader
that finds nothing to read returns None and the metric is left out of the
result line."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any


@dataclasses.dataclass
class Context:
    cfg: dict
    traffic: dict
    window: Any  # chipbench.entries.Window: the traced window
    timed: Any  # the untraced window of the same run
    trace: Any  # chipbench.trace.Summary
    peak_bytes: int
    device_kind: str
    chips: int

    def eval_rounds(self) -> int:
        """Rounds of the window whose metrics the entry evaluated."""
        return self.window.notes["eval_rounds"]


def read_all(specs: list[dict], ctx: Context) -> dict[str, float]:
    out = {}
    for m in specs:
        v = importlib.import_module(f"chipbench.metrics.{m['name']}").read(ctx)
        if v is not None:
            out[m["name"]] = v
    return out
