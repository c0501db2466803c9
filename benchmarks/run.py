"""Benchmark harness entry point — one module per paper figure/table plus
kernel micro-benches and the roofline report.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--full|--quick] [fig1 fig5 ...]

Prints ``name,us_per_call,derived`` CSV rows (also collected in
benchmarks.common.ROWS).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.compile_cache import use_compile_cache

from . import (
    estimates_bench,
    fig1_scaling,
    fig2_failures,
    fig3_dynamics,
    fig4_estimates,
    fig5_vsteady,
    fig6_env,
    fig7_constant_data,
    fig8_churn,
    fig9_async,
    fig10_scaling,
    fig11_elastic,
    fig12_compress,
    fig13_serve,
    kernels_bench,
    roofline_report,
    rounds_bench,
)
from .common import emit

MODULES = {
    "fig1": fig1_scaling,
    "fig2": fig2_failures,
    "fig3": fig3_dynamics,
    "fig4": fig4_estimates,
    "fig5": fig5_vsteady,
    "fig6": fig6_env,
    "fig7": fig7_constant_data,
    "fig8": fig8_churn,
    "fig9": fig9_async,
    "fig10": fig10_scaling,
    "fig11": fig11_elastic,
    "fig12": fig12_compress,
    "fig13": fig13_serve,
    "kernels": kernels_bench,
    "roofline": roofline_report,
    "rounds": rounds_bench,
    "estimates": estimates_bench,
}


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--full", action="store_true", help="paper-scale (slow) settings")
    p.add_argument("--quick", action="store_true", help="CI-scale settings (the default)")
    p.add_argument("--only", type=str, default=None, help="comma-separated subset")
    p.add_argument("modules", nargs="*", help="module subset (same names as --only)")
    args = p.parse_args()
    if args.full and args.quick:
        p.error("--full and --quick are mutually exclusive")
    quick = args.quick or not args.full
    if args.modules and args.only:
        p.error("give modules positionally or via --only, not both")

    names = args.modules or (
        list(MODULES) if not args.only else [s.strip() for s in args.only.split(",")]
    )
    unknown = [x for x in names if x not in MODULES]
    if unknown:
        p.error(f"unknown modules {unknown}; available: {list(MODULES)}")
    use_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        t0 = time.time()
        try:
            MODULES[name].run(quick=quick)
        except Exception as e:  # noqa: BLE001 — keep the harness sweeping
            failures += 1
            emit(f"{name}.FAILED", 0.0, f"{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
        print(f"# {name} done in {time.time() - t0:.0f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
