"""A benchmark cell cut down to a size the CPU test run can hold.

Same files, same harness, same limits; only the widths and the data are
small.  The node count stays above 64, so the plan takes the sparse,
failure-masked mix as the full-size cell does.
"""
import time

from _paths import ROOT

N_NODES = 80


def full_spec(cell: str) -> dict:
    """The cell at its real size, from BENCHMARK.json."""
    from chipbench import run

    return run.load_cell(cell, ROOT)


def spec(cell: str) -> dict:
    s = full_spec(cell)
    cfg, tr = s["cfg"], s["traffic"]
    tr.update(items_per_node=32, test_items=64, chunk_rounds=3)
    tr["graph"]["n"] = N_NODES
    cfg["hidden"] = [32, 16]
    cfg["program"]["init_kwargs"]["hidden"] = [32, 16]
    return s


def run(s: dict, seed: int = 3_000_000_001, seconds: float = 0.5, trace: bool = False):
    from chipbench import run as bench_run

    return bench_run.run_cell(s, seed, seconds, trace, time.time())
