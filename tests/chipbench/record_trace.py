"""Record a small chip trace in the layout ``chipbench/trace.py`` reduces.

    python tests/chipbench/record_trace.py OUT_DIR     # on one TPU chip

Runs a small DFL trajectory through the fused executor (16 paper MLPs on a
ring with failing links, 3 chunks of 3 rounds, eval every 2 rounds) under
the harness's ``Tracer`` exactly as ``chipbench/run.py --trace 1`` drives
it: the profiler starts at the first chunk's callback, each callback
writes the chunk-boundary annotation, and the trace stops when the call
returns.
The ``.xplane.pb`` lands under ``OUT_DIR/plugins/profile/<time>/``;
gzipped, it is ``data/chip_trace.xplane.pb.gz``, the chip-recorded fixture
of ``test_chipbench_trace.py`` beside its hand-built one.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

from chipbench import traffic  # noqa: E402
from chipbench.trace import Tracer  # noqa: E402
from repro.core.commplan import FailureModel, compile_plan  # noqa: E402
from repro.core.initialisation import InitConfig  # noqa: E402
from repro.core.topology import Graph  # noqa: E402
from repro.fed import init_fl_state, make_eval_fn, make_round_fn, run_trajectory  # noqa: E402
from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward  # noqa: E402
from repro.optim import sgd  # noqa: E402

N, ITEMS, ROUNDS, CHUNK = 16, 64, 9, 3


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    graph = Graph(traffic.make_graph({"family": "ring", "n": N}), name="ring")
    plan = compile_plan(graph, failures=FailureModel(link_p=0.9))
    x, y = traffic.make_images(N * ITEMS + 128, (28, 28, 1), 10, seed=1)
    xs, ys = x[: N * ITEMS].reshape(N, ITEMS, 28, 28, 1), y[: N * ITEMS].reshape(N, ITEMS)
    sched = traffic.batch_schedule(ITEMS, N, 16, ROUNDS * 8, seed=1)
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])  # noqa: E731
    opt = sgd(1e-3, 0.5)
    state = init_fl_state(
        jax.random.PRNGKey(1), N, lambda k: init_mlp(InitConfig("he_normal", 4.0), k), opt
    )
    kw = dict(
        n_rounds=ROUNDS, eval_every=2, eval_fn=make_eval_fn(loss_fn),
        eval_batch=(x[-128:], y[-128:]), track_sigmas=True, chunk_size=CHUNK, b_local=8,
    )
    round_fn = make_round_fn(loss_fn, opt, plan)
    run_trajectory(state, round_fn, xs, ys, sched, **kw)  # compile outside the trace

    tracer = Tracer(out_dir)

    def on_chunk(r0, r1, hist):
        tracer.start()
        tracer.mark()

    s, _ = run_trajectory(state, round_fn, xs, ys, sched, on_chunk=on_chunk, **kw)
    jax.block_until_ready(s.params)
    tracer.stop()
    print(f"trace written under {out_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
