"""Record a small chip trace of the VGG16 ensemble, with the model's scopes.

    python tests/chipbench/record_vgg_trace.py OUT_DIR     # on one TPU chip

Runs 4 VGG16-D nodes at ``width_mult`` 0.25 on a ring with clean links
through the fused executor, 2 chunks of 2 rounds, eval every 2 rounds,
under the harness's ``Tracer`` as ``chipbench/run.py --trace 1`` drives it
(see ``record_trace.py``).  The ``.xplane.pb`` lands under
``OUT_DIR/plugins/profile/<time>/``, and beside it ``OUT_DIR/reduced.xplane.pb.gz``:
the same trace with only what ``chipbench.trace.Summary`` reads (``reduce``),
which is ``data/chip_trace_vgg16.xplane.pb.gz``, the fixture on which
``test_chipbench_vgg16.py`` reads ``conv_ms_per_round``,
``pool_ms_per_round`` and ``conv_roofline``.

    python tests/chipbench/record_vgg_trace.py --reduce IN.xplane.pb OUT.xplane.pb.gz

reduces a trace recorded before.
"""
from __future__ import annotations

import glob
import gzip
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

from chipbench import traffic, xplane  # noqa: E402
from chipbench.trace import Tracer  # noqa: E402
from repro.core.commplan import compile_plan  # noqa: E402
from repro.core.initialisation import InitConfig  # noqa: E402
from repro.core.topology import Graph  # noqa: E402
from repro.fed import init_fl_state, make_eval_fn, make_round_fn, run_trajectory  # noqa: E402
from repro.models.paper_models import classifier_loss, init_vgg16, vgg16_forward  # noqa: E402
from repro.optim import sgd  # noqa: E402

N, ITEMS, ROUNDS, CHUNK, WIDTH = 4, 64, 4, 2, 0.25
KEEP_LINES = ("XLA Ops", "XLA Modules")
KEEP_STATS = ("tf_op", "hlo_category")


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _emit(f: int, wt: int, v) -> bytes:
    key = _uvarint(f << 3 | wt)
    if wt == 0:
        return key + _uvarint(v)
    if wt == 2:
        return key + _uvarint(len(v)) + v
    return key + v


def _name(msg: bytes, field: int = 2) -> str:
    return next((v.decode() for f, _, v in xplane._fields(msg) if f == field), "")


def _device_plane(b: bytes) -> bytes:
    """A device plane with its ``KEEP_LINES`` and, of its event metadata,
    the entries those lines use with only their ``KEEP_STATS``."""
    fields = list(xplane._fields(b))
    stat_ids = set()
    for f, _, v in fields:
        if f == 5:  # map<id, XStatMetadata>
            meta = next(mv for mf, _, mv in xplane._fields(v) if mf == 2)
            if _name(meta) in KEEP_STATS:
                stat_ids.add(next(x for mf, _, x in xplane._fields(meta) if mf == 1))
    lines = [v for f, _, v in fields if f == 3 and _name(v) in KEEP_LINES]
    used = {x for ln in lines for f, _, ev in xplane._fields(ln) if f == 4
            for ef, _, x in xplane._fields(ev) if ef == 1}
    out = b""
    for f, wt, v in fields:
        if f == 3 and v not in lines:
            continue
        if f == 4:  # map<id, XEventMetadata>
            meta = next(mv for mf, _, mv in xplane._fields(v) if mf == 2)
            mfields = list(xplane._fields(meta))
            if next(x for mf, _, x in mfields if mf == 1) not in used:
                continue
            kept = b"".join(
                _emit(mf, mwt, mv) for mf, mwt, mv in mfields
                if mf in (1, 2) or (mf == 5 and next(
                    x for sf, _, x in xplane._fields(mv) if sf == 1) in stat_ids)
            )
            v = b"".join(_emit(kf, kwt, kv) for kf, kwt, kv in xplane._fields(v) if kf == 1)
            v += _emit(2, 2, kept)
        out += _emit(f, wt, v)
    return out


def reduce(src: str, dst: str) -> None:
    """Write to ``dst`` (gzipped) the planes of ``src`` that
    ``chipbench.trace.Summary`` reads: each TPU plane reduced to its
    ``XLA Ops`` and ``XLA Modules`` lines, and the host's CPU plane."""
    with open(src, "rb") as fh:
        data = fh.read()
    out = b""
    for f, wt, v in xplane._fields(data):
        name = _name(v) if f == 1 else ""
        if name.startswith("/device:TPU:"):
            out += _emit(f, wt, _device_plane(v))
        elif name == "/host:CPU":
            out += _emit(f, wt, v)
    with gzip.open(dst, "wb", compresslevel=9) as fh:
        fh.write(out)


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    graph = Graph(traffic.make_graph({"family": "ring", "n": N}), name="ring")
    plan = compile_plan(graph)
    x, y = traffic.make_images(N * ITEMS + 128, (32, 32, 3), 10, seed=1)
    xs, ys = x[: N * ITEMS].reshape(N, ITEMS, 32, 32, 3), y[: N * ITEMS].reshape(N, ITEMS)
    sched = traffic.batch_schedule(ITEMS, N, 16, ROUNDS * 8, seed=1)
    loss_fn = lambda p, b: classifier_loss(vgg16_forward(p, b[0]), b[1])  # noqa: E731
    opt = sgd(1e-3, 0.5)
    state = init_fl_state(
        jax.random.PRNGKey(1), N,
        lambda k: init_vgg16(InitConfig("he_normal", 1.0), k, width_mult=WIDTH), opt,
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
    (src,) = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    reduce(src, os.path.join(out_dir, "reduced.xplane.pb.gz"))
    print(f"trace written under {out_dir}")
    return 0


if __name__ == "__main__":
    if sys.argv[1] == "--reduce":
        reduce(sys.argv[2], sys.argv[3])
        sys.exit(0)
    sys.exit(main(sys.argv[1]))
