"""How ``correct`` is decided: the program's first rounds against the reference.

The warm-up call of the timed entry records, for every one of its rounds,
the mean train loss (the local SGD steps), the mean test loss and σ_ap /
σ_an of the parameters after the round (local steps, the masked and
renormalised mix and the optimizer re-initialisation all feed them) and
the messages the round delivered (the per-round link draws).  The plain
reference (``chipbench.reference``) recomputes the first ``check_rounds``
rounds from the same seed.  Each number is a relative gap
``|program − reference| / |reference|``, except ``wire``, the largest
difference in delivered messages, which is exact (limit 0), and
``dparam``, the parameters' change over those rounds taken by the worst
leaf: per leaf, the gap between the program's norm of its change and the
reference's, over the larger of the reference's norm of that leaf's change
and the median leaf's.  A leaf whose first gradient in the reference is
under a thousandth of the median leaf's moves by round-off alone and is
left out.  The limits, and the readings they were set from, are in
``chipbench/workloads/<cell>.json``.
"""
from __future__ import annotations

import math
import statistics

SERIES = (("train_loss", "train"), ("test_loss", "test"), ("sigma_ap", "sap"), ("sigma_an", "san"))


def _gap(p: float, q: float) -> float:
    if not (math.isfinite(p) and math.isfinite(q)) or q == 0:
        return math.inf
    return abs(p - q) / abs(q)


def numbers(prog: dict, ref: dict, rounds: int) -> dict[str, float]:
    """Every comparable number of the first ``rounds`` rounds."""
    out = {}
    for key, short in SERIES:
        if key not in prog or key not in ref:
            continue
        for r in range(rounds):
            out[f"{short}_r{r}"] = _gap(float(prog[key][r]), float(ref[key][r]))
    if "change" in prog and "change" in ref:
        out["dparam"] = param_gap(prog["change"], ref["change"], ref["grad0"])
    if "wire_messages" in prog:
        out["wire"] = float(
            max(abs(int(prog["wire_messages"][r]) - int(ref["wire_messages"][r])) for r in range(rounds))
        )
    return out


def param_gap(prog: dict[str, float], ref: dict[str, float], grad0: dict[str, float]) -> float:
    """Worst leaf's gap of the change's norm (see the module's docstring)."""
    g_med = statistics.median(grad0.values())
    leaves = [k for k in ref if grad0[k] >= 1e-3 * g_med]
    med = statistics.median(ref[k] for k in leaves)
    if med == 0 or set(prog) != set(ref):
        return math.inf
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def judge(values: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict, int]:
    """(correct, {name: {"value", "limit"}}, number failed) over the limited
    numbers; a number that is missing or not finite fails."""
    report, failed = {}, 0
    for name, limit in limits.items():
        v = values.get(name, math.inf)
        ok = math.isfinite(v) and v <= limit
        failed += not ok
        report[name] = {"value": v if math.isfinite(v) else str(v), "limit": limit}
    return failed == 0, report, failed
