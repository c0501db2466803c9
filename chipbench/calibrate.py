"""Readings that the correctness limits of a cell are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,... --control-seeds 21,22,23 \
        [--witness-seeds 31,32]

In one process, per seed of ``--seeds``: the cell's set-up and the entry's
first call (the program's first rounds, as a benchmark run makes them),
then the plain reference; prints every comparable number
(``chipbench.check.numbers``).
Per seed of ``--control-seeds``: the control (the reference one precision
step below the configuration: local steps in bfloat16, the mix at
``high``) and each planted fault of ``chipbench.reference.FAULTS``, each
against the clean reference.  Per seed of ``--witness-seeds``: two
witnesses of how far round-off alone moves each number, against the clean
reference: the reference with its local steps at the ``default`` matmul
precision the configuration states (``ref_default``), and the program with
every contraction at ``highest`` (``program_highest``).  One JSON line per
reading; the limits in
``chipbench/workloads/<cell>.json`` are set from them by hand.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _ints(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=_ints, default=[])
    p.add_argument("--control-seeds", type=_ints, default=[])
    p.add_argument("--witness-seeds", type=_ints, default=[])
    args = p.parse_args(argv)

    import jax

    from chipbench import check, entries, program, reference, run

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    run.use_compile_cache()
    spec = run.load_cell(args.workload)
    cfg, tr = spec["cfg"], spec["traffic"]
    rounds = tr["check_rounds"]

    def emit(kind, seed, values, **extra):
        print(json.dumps({"kind": kind, "seed": seed, **extra, "numbers": values}), flush=True)

    def first_rounds(seed, inputs):
        entry = entries.load(tr["entry"])(cfg, tr, seed, inputs)
        first = entry.check()
        entry.free()
        del entry
        gc.collect()
        return first

    def series(h):
        return {k: [float(v) for v in h[k][:rounds]] for k, _ in check.SERIES if k in h}

    for seed in args.seeds:
        t = time.time()
        inputs = program.make_inputs(cfg, tr, seed, rounds)
        first = first_rounds(seed, inputs)
        t_prog = time.time() - t
        t = time.time()
        ref = reference.run(cfg, tr, seed, inputs.adj, inputs.xs, inputs.ys, inputs.test,
                            inputs.schedule, rounds)
        emit("program", seed, check.numbers(first, ref, rounds),
             program_s=t_prog, reference_s=time.time() - t,
             program_series=series(first), reference_series=series(ref),
             program_change=first["change"], reference_change=ref["change"],
             reference_grad0=ref["grad0"])
    for seed in args.control_seeds:
        inputs = program.make_inputs(cfg, tr, seed, rounds)
        ins = (cfg, tr, seed, inputs.adj, inputs.xs, inputs.ys, inputs.test, inputs.schedule, rounds)
        ref = reference.run(*ins)
        control = reference.run(*ins, setting=reference.Setting("bfloat16", "high"))
        emit("control", seed, check.numbers(control, ref, rounds), series=series(control))
        for fault in reference.FAULTS[1:]:
            bad = reference.run(*ins, setting=reference.Setting(fault=fault))
            emit("fault", seed, check.numbers(bad, ref, rounds), fault=fault)
    for seed in args.witness_seeds:
        inputs = program.make_inputs(cfg, tr, seed, rounds)
        ins = (cfg, tr, seed, inputs.adj, inputs.xs, inputs.ys, inputs.test, inputs.schedule, rounds)
        ref = reference.run(*ins)
        low = reference.run(*ins, setting=reference.Setting(local_precision="default"))
        emit("ref_default", seed, check.numbers(low, ref, rounds), series=series(low))
        with jax.default_matmul_precision("highest"):
            first = first_rounds(seed, inputs)
        emit("program_highest", seed, check.numbers(first, ref, rounds), series=series(first),
             reference_series=series(ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
