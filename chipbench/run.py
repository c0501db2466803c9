"""On-chip benchmark of the DFL trainer: one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process does the whole run: it loads the cell named in
``BENCHMARK.json`` (its configuration, traffic mix and correctness limits
from ``chipbench/``), sets up (imports, seeded data, the graph and its
communication plan, the gossip-estimated initialisation, the entry's first
call over the rounds the reference follows, one warm-up call that compiles
every shape the window uses), measures the entry for about ``--seconds``,
checks the first call's rounds against the plain reference, and prints one
JSON object as its last line.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` then
runs a short further window under the profiler and reports the per-layer
metrics, read from the trace by ``chipbench/metrics/<metric>.py`` (those
of the host clock from the untraced window).  Without a TPU as the default
device, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json``, with its files (``cell_spec``)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    return cell_spec(cells[name], bench, root)


def cell_spec(cell: dict, bench: dict, root: Path = ROOT) -> dict:
    """A cell (name, config, traffic) with its configuration, traffic,
    correctness limits and the metrics of ``bench`` it reports."""
    from chipbench import traffic

    cfg_file = next(c["file"] for c in bench["configs"] if c["name"] == cell["config"])
    reports = lambda m: cell["name"] in m.get("workloads", [cell["name"]])  # noqa: E731
    return {
        "cell": cell,
        "cfg": json.loads((root / cfg_file).read_text()),
        "traffic": traffic.load(cell["traffic"]),
        "limits": json.loads((root / "chipbench" / "workloads" / f"{cell['name']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, t_start: float) -> tuple[dict, list[str]]:
    """Set up, warm up, measure and check one run.  Returns the result
    object and the lines that report the compared numbers."""
    import jax

    from chipbench import check, entries, metrics, program, reference
    from chipbench.clock import CompileClock
    from chipbench.trace import NoTracer, Tracer

    clock = CompileClock()
    cfg, tr = spec["cfg"], spec["traffic"]
    set_rounds = tr["check_rounds"] + tr["warmup_chunks"] * tr["chunk_rounds"]
    inputs = program.make_inputs(cfg, tr, seed, set_rounds)
    entry = entries.load(tr["entry"])(cfg, tr, seed, inputs)
    first = entry.check()
    entry.warmup()
    n_rounds = entry.window_rounds(seconds)
    n_traced = entry.trace_rounds() if trace else 0
    inputs.schedule = program.schedule(tr, inputs.adj.shape[0], seed, set_rounds + n_rounds + n_traced)
    entry.prepare(n_rounds)
    if trace:
        entry.prepare(n_traced)
    setup_s = time.time() - t_start

    def say(label, w):
        n_spans, span_s = clock.between(w.t0, w.t1)
        cache = clock.cache_events(w.t0, w.t1)
        print(
            f"{label}: rounds={w.rounds} node_rounds={w.node_rounds} wall_s={w.wall_s} "
            f"calls={w.calls} trace_lower_compile_spans={n_spans} span_s={span_s} "
            f"cache_misses={cache['miss']} cache_hits={cache['hit']} setup_s={setup_s} "
            f"{json.dumps(w.notes)}",
            flush=True,
        )

    win = entry.window(n_rounds, NoTracer())
    say("window", win)
    if trace:
        with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
            tracer = Tracer(tdir)
            traced = entry.window(n_traced, tracer)
            summary = tracer.summary(len(entry.devices()))
        say("traced window", traced)
    devs = entry.devices()
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    entry.free()
    del entry
    gc.collect()

    ref = reference.run(
        cfg, tr, seed, inputs.adj, inputs.xs, inputs.ys, inputs.test,
        inputs.schedule, tr["check_rounds"],
    )
    values = check.numbers(first, ref, tr["check_rounds"])
    correct, report, failed = check.judge(values, spec["limits"]["limits"])
    lines = [f"numbers (all, limited or not): {json.dumps(values)}"]
    lines += [f"check {k}: {v['value']} (limit {v['limit']})" for k, v in report.items()]

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(peak),
    }
    if trace:
        ctx = metrics.Context(
            cfg=cfg, traffic=tr, window=traced, timed=win, trace=summary, peak_bytes=peak,
            device_kind=dev.device_kind, chips=len(devs),
        )
        values_out = metrics.read_all(spec["per_layer"], ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        values_out = {
            "node_rounds_per_s": win.node_rounds / win.wall_s,
            "setup_s": setup_s,
        }
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    result = {
        "correct": bool(correct),
        "attempted": len(report),
        "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in values_out.items()},
        "device": device,
    }
    if trace:
        result["breakdown"] = summary.breakdown()
    result["checks"] = report
    return result, lines


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache`` (the
    program's own default), for every program of any size, so that only a
    checkout's first run of a cell compiles."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: the default device is {dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 2
    spec = load_cell(args.workload)
    if len(jax.devices()) < spec["cell"]["chips"]:
        print(
            f"{args.workload} needs {spec['cell']['chips']} chips, found {len(jax.devices())}",
            file=sys.stderr,
        )
        return 2
    print(f"compile cache: {use_compile_cache()}", flush=True)
    result, lines = run_cell(spec, args.seed, args.seconds, bool(args.trace), T_START)
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
