"""Profiler trace of the measured window, and its reduction to numbers.

``Tracer`` starts ``jax.profiler`` at the window's start and writes the
harness's host annotation ``CHUNK_SPAN`` at each chunk boundary.  ``Summary``
reduces the ``.xplane.pb`` it leaves (read by ``chipbench.xplane``): per
device, the programs of the "XLA Modules" line (their union is the busy
time; what lies between them in the window are the idle gaps, labelled by
the host events open at the time) and the leaf ops of the "XLA Ops" line
with their ``tf_op`` path, which carries the ``jax.named_scope`` names.
Every per-layer metric reads this one summary.
"""
from __future__ import annotations

import dataclasses
import glob
import os

CHUNK_SPAN = "chipbench.on_chunk"


class NoTracer:
    """The untraced run: every hook does nothing."""

    def start(self):
        pass

    def mark(self):
        pass

    def stop(self):
        pass


class Tracer:
    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.on = False

    def start(self):
        import jax

        if not self.on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans only: no per-call Python events
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.on = True

    def mark(self):
        import jax

        with jax.profiler.TraceAnnotation(CHUNK_SPAN):
            pass

    def stop(self):
        import jax

        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def summary(self, n_devices: int) -> "Summary":
        files = glob.glob(os.path.join(self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one trace under {self.out_dir}, found {files}")
        return Summary.from_file(files[0], n_devices)


@dataclasses.dataclass
class Op:
    start: float  # ns
    end: float
    name: str
    scope: str  # the op's ``tf_op`` path: jit name, then the named scopes
    category: str


@dataclasses.dataclass
class HostEvent:
    start: float
    end: float
    name: str


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


# ops that contain other ops on the same line; their time is their children's
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Summary:
    ops: list[list[Op]]  # per device, its leaf ops in time order
    modules: list[list[tuple[float, float]]]  # per device, program executions
    host: list[HostEvent]
    marks: list[float]  # ns of the harness's boundary annotations
    lo: float  # the window, ns
    hi: float

    @classmethod
    def from_file(cls, path: str, n_devices: int) -> "Summary":
        from chipbench import xplane

        keep = lambda plane, line: (  # noqa: E731
            line in ("XLA Ops", "XLA Modules") if plane.startswith("/device:") else True
        )
        ops, modules, host, marks = [], [], [], []
        for plane in xplane.read(path, keep):
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops.append(sorted(
                            (Op(e.start_ns, e.end_ns, e.name.split(" = ")[0],
                                str(e.meta_stats.get("tf_op", "")),
                                str(e.meta_stats.get("hlo_category", "")))
                             for e in line.events
                             if e.meta_stats.get("hlo_category") not in _CONTAINERS),
                            key=lambda o: o.start,
                        ))
                    elif line.name == "XLA Modules":
                        modules.append(sorted((e.start_ns, e.end_ns) for e in line.events))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == CHUNK_SPAN:
                            marks.append(e.start_ns)
                        host.append(HostEvent(e.start_ns, e.end_ns, e.name))
        ops, modules = ops[:n_devices], modules[:n_devices]
        if not modules or not any(modules) or not marks:
            raise RuntimeError("the trace holds no device program or no harness annotation")
        lo = min(marks)
        hi = max(max(e for _, e in m) for m in modules if m)
        return cls(ops, modules, host, sorted(marks), lo, max(hi, lo))

    # ------------------------------------------------------------ numbers
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _busy(self, d: int) -> list[tuple[float, float]]:
        return _union(
            (max(s, self.lo), min(e, self.hi)) for s, e in self.modules[d] if e > self.lo and s < self.hi
        )

    @property
    def busy_s(self) -> float:
        """Union of the intervals in which a program ran on the device,
        inside the window, mean over devices."""
        tot = [sum(e - s for s, e in self._busy(d)) for d in range(len(self.modules))]
        return sum(tot) / len(tot) / 1e9

    def scope_s(self, scope: str) -> float | None:
        """Device seconds of the window's ops under ``scope``, mean over
        devices; None where no op carries the scope."""
        per, found = [], False
        for d in self.ops:
            t = 0.0
            for o in d:
                if scope not in o.scope.split("/") or o.end <= self.lo or o.start >= self.hi:
                    continue
                found = True
                t += min(o.end, self.hi) - max(o.start, self.lo)
            per.append(t)
        return sum(per) / len(per) / 1e9 if found else None

    def gaps(self, d: int) -> list[tuple[int, int]]:
        """Idle intervals of device ``d`` inside the window."""
        out, t = [], self.lo
        for s, e in self._busy(d):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if self.hi > t:
            out.append((t, self.hi))
        return out

    def boundary_gaps_s(self) -> list[float]:
        """Per boundary annotation inside the window, the idle gap it fell in
        (0 where the device was busy), mean over devices."""
        out = []
        for m in (m for m in self.marks if m < self.hi):
            per = []
            for d in range(len(self.modules)):
                per.append(next(((e - s) for s, e in self.gaps(d) if s <= m <= e), 0))
            out.append(sum(per) / len(per) / 1e9)
        return out

    def _label(self, s: float, e: float) -> str:
        """The host events open during [s, e]: the harness's annotation,
        then the shortest host event that covers most of the gap."""
        over = [h for h in self.host if h.start < e and h.end > s]
        if not over:
            return "host: no event"
        cover = lambda h: min(h.end, e) - max(h.start, s)  # noqa: E731
        best = max(over, key=lambda h: (cover(h), -(h.end - h.start)))
        harness = [h.name for h in over if h.name == CHUNK_SPAN]
        return " / ".join(harness[:1] + [best.name])

    def breakdown(self, top: int = 10) -> dict:
        """Device ops that took most time, and the longest idle gaps by
        what the host was doing (first device)."""
        tot: dict[str, float] = {}
        for o in self.ops[0]:
            if o.end > self.lo and o.start < self.hi:
                tot[o.name] = tot.get(o.name, 0) + min(o.end, self.hi) - max(o.start, self.lo)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(0), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[self._label(s, e), (e - s) / 1e9] for s, e in gaps],
        }
