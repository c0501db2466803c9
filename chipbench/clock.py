"""Compile time seen from the host, from JAX's monitoring events.

A copy of ``chip_smoke.CompileClock``: the wall seconds JAX spends tracing,
lowering and compiling (or loading a compiled program from the persistent
cache), and how many such spans there were, plus the persistent cache's
misses (a program XLA really compiled) and hits.  The harness counts them
inside the measured window, where there should be none.
"""
from __future__ import annotations

import time

import jax

EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Collects (event, start, end) of every trace, lowering and compile.
    Spans nest (an inner jit traces inside the outer trace), so a window's
    compile time is the length of their union."""

    def __init__(self) -> None:
        self.spans: list[tuple[str, float, float]] = []
        self.cache: list[tuple[str, float]] = []  # ("hit" | "miss", time.time())
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event: str, start: float, end: float, **_) -> None:
        if event in EVENTS:
            self.spans.append((event, start, end))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache.append(("hit", time.time()))
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache.append(("miss", time.time()))

    def cache_events(self, t0: float, t1: float) -> dict[str, int]:
        """Persistent-cache hits and misses in [t0, t1]."""
        out = {"hit": 0, "miss": 0}
        for kind, t in self.cache:
            if t0 <= t <= t1:
                out[kind] += 1
        return out

    def between(self, t0: float, t1: float) -> tuple[int, float]:
        """(number of spans, union seconds) of spans starting in [t0, t1]
        (``time.time()`` clock)."""
        inside = sorted((s, e) for _, s, e in self.spans if t0 <= s <= t1)
        total, reach = 0.0, t0
        for s, e in inside:
            s = max(s, reach)
            if e > s:
                total, reach = total + e - s, e
        return len(inside), total
