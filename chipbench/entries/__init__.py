"""Runners of the program's entry points, one module per entry, found by
the ``entry`` name in a traffic file.  Each defines ``Entry(cfg, traffic,
seed, inputs)`` with ``check()``, ``warmup()``, ``window_rounds(seconds)``,
``trace_rounds()``, ``prepare(rounds)``, ``window(rounds, tracer)``,
``devices()`` and ``free()``."""
from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class Window:
    """What one measured window did."""

    rounds: int  # rounds completed inside the window
    node_rounds: int
    wall_s: float  # synced to the device
    t0: float  # time.time() at the window's start and end
    t1: float
    calls: int  # entry calls inside the window
    notes: dict = dataclasses.field(default_factory=dict)


def load(name: str):
    return importlib.import_module(f"chipbench.entries.{name}").Entry
