"""Roofline-term derivation from compiled dry-run artifacts (deliverable g).

Hardware constants come from ``PEAKS``, keyed by ``device.device_kind``;
a device that is not in the table is an error, never a default.

Sources: ``compiled.cost_analysis()`` (per-device FLOPs / bytes — the SPMD
module is one device's program) and the partitioned HLO text for collective
operand bytes (not in cost_analysis).

Scan correction: XLA cost analysis counts a while-loop body ONCE regardless
of trip count, and the stack scans over layer periods.  We therefore lower
two *unrolled* truncations (1 and 2 periods — the model unrolls when
n_full <= 2) and extrapolate:  total(P) = A + (P - 1)·(B - A), where A/B are
the 1-/2-period costs.  The full-depth compile still provides
memory_analysis and proves the real program lowers.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator kind."""

    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # interconnect bytes/s per link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of interchip interconnect per chip.  The per-link
# figure assumes that bandwidth is split evenly over the chip's 4 links.
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8 / 4),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind`` (``jax.devices()[0].device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[([\d,]*)\]")

__all__ = [
    "PEAKS",
    "Peaks",
    "peaks_for",
    "collective_bytes",
    "RooflineTerms",
    "terms_from_costs",
    "extrapolate_depth",
]


def _shape_bytes(text: str) -> int:
    """Sum the byte sizes of every dtype[shape] literal in ``text``."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*?)\s([a-z][\w\-]*)\(")
_OPERAND_RE = re.compile(r"%[\w.\-]+")


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind operand bytes, parsed from (partitioned) HLO text.

    HLO text elides operand shapes, so we first index every instruction's
    output shape, then sum the referenced operands' bytes for each
    collective.  ``-start`` variants are counted, ``-done`` skipped (same
    transfer).  Collectives inside while bodies appear once — consistent
    with the scan-depth extrapolation applied to all terms.
    """
    shapes: dict[str, int] = {}
    collectives: list[tuple[str, list[str]]] = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape_text, opcode = m.groups()
        shapes[name] = _shape_bytes(shape_text)
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base in _COLLECTIVES and not opcode.endswith("-done"):
            # operand list: inside the call parens, before attribute kwargs
            args = line[m.end() - 1 :]
            depth, end = 0, len(args)
            for i, ch in enumerate(args):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            collectives.append((base, _OPERAND_RE.findall(args[:end])))
    out = {k: 0 for k in _COLLECTIVES}
    for kind, operands in collectives:
        out[kind] += sum(shapes.get(o, 0) for o in operands)
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float  # per-chip
    hbm_bytes: float  # per-chip
    coll_bytes: float  # per-chip
    device_kind: str  # the chip the terms are priced on (a ``PEAKS`` key)
    coll_breakdown: dict[str, int] | None = None

    @property
    def compute_s(self) -> float:
        return self.flops / peaks_for(self.device_kind).flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / peaks_for(self.device_kind).hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / peaks_for(self.device_kind).ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s, "collective": self.collective_s}
        return max(terms, key=terms.get)

    def as_dict(self) -> dict[str, Any]:
        return {
            "flops_per_chip": self.flops,
            "hbm_bytes_per_chip": self.hbm_bytes,
            "collective_bytes_per_chip": self.coll_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "collective_breakdown": self.coll_breakdown,
        }


def terms_from_costs(cost: dict, hlo_text: str, device_kind: str) -> RooflineTerms:
    peaks_for(device_kind)  # fail here, not at the first read of a term
    cb = collective_bytes(hlo_text)
    return RooflineTerms(
        flops=float(cost.get("flops", 0.0)),
        hbm_bytes=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=float(sum(cb.values())),
        device_kind=device_kind,
        coll_breakdown=cb,
    )


def extrapolate_depth(a: RooflineTerms, b: RooflineTerms, n_periods: int) -> RooflineTerms:
    """total(P) = A + (P-1)·(B-A) from 1-period (A) and 2-period (B) costs."""
    lin = lambda x, y: x + (n_periods - 1) * (y - x)
    cb = None
    if a.coll_breakdown is not None and b.coll_breakdown is not None:
        cb = {k: int(lin(a.coll_breakdown[k], b.coll_breakdown[k])) for k in a.coll_breakdown}
    return RooflineTerms(
        flops=lin(a.flops, b.flops),
        hbm_bytes=lin(a.hbm_bytes, b.hbm_bytes),
        coll_bytes=lin(a.coll_bytes, b.coll_bytes),
        device_kind=a.device_kind,
        coll_breakdown=cb,
    )


def _nonneg_poly_extrapolate(seqs, vals, seq_target: int) -> float:
    """Evaluate a non-negative-coefficient quadratic fit at seq_target.

    Costs are non-negative combinations of {1, S, S²}; an unconstrained
    interpolation can acquire spurious curvature from alignment/padding
    wiggles that explodes when extrapolating 32× (observed: a linear
    collective term inflated 4×).  Projected least squares: fit deg-2; if
    the S² (then S) coefficient is negative, refit without it.
    """
    import numpy as np

    seqs = np.asarray(seqs, dtype=np.float64)
    vals = np.asarray(vals, dtype=np.float64)
    for cols in ([seqs**2, seqs, seqs * 0 + 1], [seqs, seqs * 0 + 1], [seqs * 0 + 1]):
        a = np.stack(cols, axis=1)
        coef, *_ = np.linalg.lstsq(a, vals, rcond=None)
        if np.all(coef[:-1] >= 0) or len(cols) == 1:
            basis = {3: [seq_target**2, seq_target, 1.0], 2: [seq_target, 1.0], 1: [1.0]}[len(cols)]
            return float(max(0.0, np.dot(coef, basis)))
    raise AssertionError


def extrapolate_depth_and_seq(
    points: dict[tuple[int, int], RooflineTerms], n_periods: int, seq_target: int
) -> RooflineTerms:
    """Fit cost(P, S) = α(S) + P·β(S) with α, β (constrained) quadratic in S.

    ``points`` maps (periods ∈ {1,2}, seq ∈ {s₁..s_k}) → measured terms from
    small *unrolled* lowerings.  Costs are polynomials of S (attention S²,
    everything else linear); k ≥ 3 points + the non-negative-coefficient fit
    keep the 8–32× extrapolation stable against padding wiggles.
    """
    import numpy as np

    seqs = sorted({s for (_, s) in points})
    assert len(seqs) >= 3, seqs

    def fit_metric(get) -> float:
        beta_pts = [get(points[(2, s)]) - get(points[(1, s)]) for s in seqs]
        alpha_pts = [get(points[(1, s)]) - b for s, b in zip(seqs, beta_pts)]
        beta = _nonneg_poly_extrapolate(seqs, beta_pts, seq_target)
        alpha = _nonneg_poly_extrapolate(seqs, alpha_pts, seq_target)
        return max(0.0, alpha + n_periods * beta)

    first = next(iter(points.values()))
    cb = {k: int(fit_metric(lambda t, k=k: t.coll_breakdown[k])) for k in first.coll_breakdown}
    return RooflineTerms(
        flops=fit_metric(lambda t: t.flops),
        hbm_bytes=fit_metric(lambda t: t.hbm_bytes),
        coll_bytes=float(sum(cb.values())),
        device_kind=first.device_kind,
        coll_breakdown=cb,
    )


def model_flops(n_active_params: int, tokens: int, kind: str) -> float:
    """Analytic MODEL_FLOPS: 6·N·D for training, 2·N·D forward-only."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active_params * tokens
