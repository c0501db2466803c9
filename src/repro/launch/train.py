"""Training launcher (CPU-runnable end-to-end driver).

Trains the paper's MLP / CNN / reduced-VGG16 — or a reduced zoo arch on
synthetic token data — with the full DFL stack: topology, gain-corrected
uncoordinated init, DecAvg rounds, optimizer-state reinit, checkpointing.

Examples:
    python -m repro.launch.train --model mlp --nodes 16 --rounds 100
    python -m repro.launch.train --model cnn --topology ba --rounds 50
    python -m repro.launch.train --arch qwen2.5-3b --reduced --rounds 30
    # transformer-scale gossip through the fused executor, int8-compressed
    # exchanges (error-feedback mirrors ride the scan carry, DESIGN.md §18)
    python -m repro.launch.train --model transformer --nodes 8 --rounds 20 --compress int8
    python -m repro.launch.train --model mlp --compress topk --topk-frac 0.05
    python -m repro.launch.train --model mlp --no-gain-correction   # Fig.1 baseline
    # truly uncoordinated: per-node gains from on-device gossip estimation,
    # fused estimate→init→train (no host round-trip between phases)
    python -m repro.launch.train --model mlp --topology ba --uncoordinated-init --estimate-rounds 24
    # time-varying topology: train AND estimate over a Markov-churned
    # PlanSchedule (operators switch by round index inside the fused scan)
    python -m repro.launch.train --model mlp --topology kregular --topology-schedule churn \
        --plans 8 --churn-rate 0.2 --uncoordinated-init --leaderless
    # event-driven (no round barrier): per-edge Poisson clocks, pairwise
    # DecAvg exchanges scanned over the realised event stream
    python -m repro.launch.train --model mlp --topology ba --async --event-rate 1.0 \
        --event-horizon 100
    # elastic membership: 4 nodes arrive at round 50, estimate n online, and
    # initialise uncoordinated mid-run; correlated crash burst injected
    python -m repro.launch.train --model mlp --topology kregular --elastic \
        --join-nodes 4 --join-round 50 --fault-scenario crash
    # preemption-safe: checkpoint every chunk, then resume bit-identically
    python -m repro.launch.train --model mlp --rounds 100 --ckpt-dir /tmp/ck --checkpoint-every 1
    python -m repro.launch.train --model mlp --rounds 100 --ckpt-dir /tmp/ck --resume /tmp/ck
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import jax
import numpy as np

from repro.checkpoint import save_train_state
from repro.configs import get_reduced_config
from repro.core import topology as T
from repro.core.commplan import CommPlan, FailureModel, compile_plan, compile_schedule, cyclic_map
from repro.core.compress import Compression
from repro.core.faults import SCENARIOS, scenario
from repro.core.membership import membership_schedule
from repro.core.initialisation import InitConfig, gain_from_graph
from repro.data import (
    batch_index_schedule,
    cifar10_like,
    make_token_stream,
    mnist_like,
    node_batch_iterator,
    node_datasets,
    partition_iid,
    partition_zipf,
    so2sat_like,
    token_batch_iterator,
)
from repro.fed import (
    CheckpointPolicy,
    DFLState,
    init_fl_state,
    make_eval_fn,
    make_round_fn,
    run_elastic_trajectory,
    run_event_trajectory,
    run_trajectory,
    run_warmup_trajectory,
    train_loop,
)
from repro.gossip import (
    estimate_size_leaderless_events,
    gains_from_estimates,
    make_gain_estimator,
)
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as TF
from repro.obs import gossip_health, history_rows, profile_trace, run_manifest, write_run_log
from repro.models.paper_models import classifier_loss, cnn_forward, init_cnn, init_mlp, init_vgg16, mlp_forward, vgg16_forward
from repro.optim import adamw, sgd


def build_graph(kind: str, n: int, seed: int) -> T.Graph:
    return {
        "full": lambda: T.complete(n),
        "kregular": lambda: T.random_k_regular(n, min(4, n - 1 - (n % 2 == 0)), seed=seed)
        if n > 5
        else T.complete(n),
        "ba": lambda: T.barabasi_albert(n, min(8, n // 2), seed=seed),
        "er": lambda: T.erdos_renyi_gnp(n, min(1.0, 6.0 / n), seed=seed),
        "ring": lambda: T.ring(n),
        "circulant": lambda: T.circulant(n, (1, 2)),
    }[kind]()


# --model token archs: reduced zoo configs gossiped through the fused
# executor on windowed synthetic token data (the transformer-scale payloads
# the compressed-gossip codecs exist for)
TOKEN_MODELS = {
    "transformer": "qwen2.5-3b",
    "moe": "granite-moe-1b-a400m",
    "rwkv": "rwkv6-3b",
}


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    """Parse and cross-check the command line (``sys.argv[1:]`` by default)."""
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--model",
        choices=["mlp", "cnn", "vgg16", *sorted(TOKEN_MODELS)],
        default=None,
    )
    p.add_argument("--arch", type=str, default=None, help="zoo arch id (with --reduced)")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--nodes", type=int, default=16)
    p.add_argument("--rounds", type=int, default=100)
    p.add_argument("--topology", choices=["full", "kregular", "ba", "er", "ring", "circulant"], default="full")
    p.add_argument("--optimizer", choices=["sgd", "adamw"], default="sgd")
    p.add_argument("--items-per-node", type=int, default=256)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--local-batches", type=int, default=8)
    p.add_argument("--zipf", type=float, default=0.0, help="non-iid Zipf alpha (0 = iid)")
    p.add_argument("--seq-len", type=int, default=64,
                   help="window length for the token --model archs")
    p.add_argument(
        "--compress", choices=["none", "int8", "fp8", "topk", "qtopk"],
        default="none",
        help="compressed gossip (core.compress): quantised / top-k sparsified "
        "exchanges with per-node error-feedback mirrors in the scan carry; "
        "wire-byte telemetry prices the codec's actual encoding "
        "(qtopk = top-k with int8 values, 3 bytes/entry)",
    )
    p.add_argument("--compress-chunk", type=int, default=2048,
                   help="codec chunk: elements per fp32 scale (≤ 65536)")
    p.add_argument("--topk-frac", type=float, default=0.1,
                   help="fraction of each chunk the topk/qtopk codecs transmit")
    p.add_argument("--gamma", type=float, default=None,
                   help="consensus step size of the compressed mix "
                   "(default 1.0; 0.3 for topk/qtopk, which need the damping "
                   "on sparse graphs)")
    p.add_argument("--link-p", type=float, default=1.0)
    p.add_argument("--node-p", type=float, default=1.0)
    p.add_argument(
        "--topology-schedule", choices=["static", "cyclic", "churn"], default="static",
        help="time-varying topology (PlanSchedule): 'cyclic' cycles --plans "
        "independently re-sampled graphs of the chosen family, 'churn' walks "
        "a seeded Markov chain of edge up/down rewirings of the base graph "
        "(--churn-rate); both switch operators by round index inside the "
        "fused scan",
    )
    p.add_argument("--plans", type=int, default=4, help="K: plans in the schedule")
    p.add_argument("--plan-period", type=int, default=1,
                   help="rounds each plan stays active before the schedule advances")
    p.add_argument("--churn-rate", type=float, default=0.1,
                   help="per-snapshot edge resampling probability (churn schedule)")
    p.add_argument("--no-gain-correction", action="store_true")
    p.add_argument(
        "--uncoordinated-init", action="store_true",
        help="per-node gains from on-device gossip estimation (repro.gossip) "
        "instead of the perfect-knowledge gain_from_graph; estimation rides "
        "the same failure-prone links as training",
    )
    p.add_argument("--estimate-rounds", type=int, default=32,
                   help="gossip budget: power-iteration and push-sum rounds each")
    p.add_argument("--estimate-mode", choices=["vnorm", "alpha", "degree"], default="vnorm",
                   help="§4.4 knowledge regime: gossip ‖v̂‖ / size-only n̂^α / degree polling")
    p.add_argument(
        "--leaderless", action="store_true",
        help="size estimation by exponential-random-minimum sketches instead "
        "of the leader one-hot — no distinguished node",
    )
    p.add_argument(
        "--async", action="store_true", dest="async_gossip",
        help="event-driven gossip: no global round barrier — per-edge Poisson "
        "clocks realise an event stream and training/mixing happen pairwise "
        "as edges fire (fed.executor.run_event_trajectory, DESIGN.md §14)",
    )
    p.add_argument("--event-rate", type=float, default=1.0,
                   help="per-edge Poisson clock rate; 1.0 message-budget-matches "
                   "one synchronous round per unit time")
    p.add_argument("--event-horizon", type=float, default=None,
                   help="virtual-time horizon of the event stream (default: --rounds)")
    p.add_argument(
        "--legacy-loop", action="store_true",
        help="per-round dispatch via train_loop instead of the fused executor",
    )
    p.add_argument(
        "--elastic", action="store_true",
        help="elastic membership executor (fed.run_elastic_trajectory, "
        "DESIGN.md §16): nodes join/leave inside the static envelope; implied "
        "by --join-nodes / --fault-scenario",
    )
    p.add_argument("--join-nodes", type=int, default=0,
                   help="hold this many envelope slots out of the initial "
                   "membership; they arrive at --join-round, re-derive n̂ via "
                   "leaderless sketches, and initialise uncoordinated mid-run")
    p.add_argument("--join-round", type=int, default=None,
                   help="arrival round of the joining nodes (default: rounds // 2)")
    p.add_argument("--join-warmup", type=int, default=8,
                   help="estimation rounds between a node's arrival and its init")
    p.add_argument("--fault-scenario", choices=sorted(SCENARIOS), default="none",
                   help="deterministic fault injection (core.faults): correlated "
                   "crash bursts, partitions, hub outages — seeded and replayable")
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="with --ckpt-dir: snapshot full mid-scan state every N "
                   "chunks (preemption-safe; resume is bit-identical)")
    p.add_argument("--resume", type=str, default=None,
                   help="checkpoint dir or step file to resume the trajectory "
                   "from (replays bit-identical params/metrics)")
    p.add_argument("--chunk-rounds", type=int, default=0, help="executor scan chunk size (0 = auto)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ckpt-dir", type=str, default=None)
    p.add_argument("--history-out", type=str, default=None)
    p.add_argument("--telemetry", type=str, default=None,
                   help="write a JSONL run log — manifest, one record per "
                   "recorded round/bin, summary, gossip health (repro.obs, "
                   "DESIGN.md §17)")
    p.add_argument("--profile-trace", type=str, default=None,
                   help="capture a jax.profiler trace of the run into this "
                   "directory (host spans dfl.trajectory / dfl.chunk.* / "
                   "dfl.assemble; device scopes dfl_* of repro.obs.trace)")
    p.add_argument("--log-every", type=int, default=0,
                   help="stream recorded metrics every N rounds at chunk "
                   "boundaries instead of printing after the run (fused "
                   "executors; sets the chunk size unless --chunk-rounds is "
                   "given — no extra device syncs beyond the chunk transfer)")
    args = p.parse_args(argv)
    if args.join_nodes > 0 or args.fault_scenario != "none":
        args.elastic = True
    if args.uncoordinated_init and args.no_gain_correction:
        p.error("--uncoordinated-init estimates (and applies) per-node gains; "
                "it contradicts --no-gain-correction — pick one")
    if args.async_gossip:
        if args.arch or args.legacy_loop:
            p.error("--async runs through the event executor — it excludes --arch and --legacy-loop")
        if args.topology_schedule != "static":
            p.error("--async needs a static topology: realise dynamics as per-edge "
                    "clock rates (poisson_event_stream) rather than a PlanSchedule")
        if args.uncoordinated_init and args.estimate_mode == "degree":
            p.error("--async estimation is barrier-free leaderless sketching; "
                    "degree polling needs the round-based walker — drop "
                    "--estimate-mode degree or drop --async")
    if args.elastic:
        if args.async_gossip or args.arch or args.legacy_loop:
            p.error("--elastic runs through the fused elastic executor — it "
                    "excludes --async, --arch, and --legacy-loop")
        if args.uncoordinated_init:
            p.error("--elastic joiners already initialise uncoordinated from "
                    "online n̂ sketches; initial members use the graph gain — "
                    "drop --uncoordinated-init")
        if not 0 <= args.join_nodes < args.nodes:
            p.error(f"--join-nodes must leave at least one initial member "
                    f"(got {args.join_nodes} of {args.nodes})")
        if args.topology_schedule != "static" and "partition" in args.fault_scenario:
            p.error("edge-cut fault scenarios index the base graph's edge list "
                    "— they need --topology-schedule static")
    if args.resume and args.uncoordinated_init and not args.async_gossip:
        p.error("--resume is not supported through the fused warmup phase; "
                "drop --uncoordinated-init (or resume an --elastic run)")
    if args.model in TOKEN_MODELS and args.legacy_loop:
        p.error("token --model archs gather from the precomputed schedule — "
                "they run through the fused executors, not --legacy-loop "
                "(use --arch for the host-driven token path)")
    return args


def run(args: argparse.Namespace) -> tuple[DFLState, dict[str, list]]:
    """Run the training that ``args`` (from :func:`parse_args`) describes.

    Returns the final ensemble state and the recorded history.
    """
    token_model = args.model in TOKEN_MODELS
    compress_cfg = None
    if args.compress != "none":
        sparse = args.compress in ("topk", "qtopk")
        gamma = args.gamma if args.gamma is not None else (0.3 if sparse else 1.0)
        compress_cfg = Compression(
            codec=args.compress, chunk=args.compress_chunk,
            topk_frac=args.topk_frac, gamma=gamma,
        )
        print(
            f"compress: {args.compress} chunk={args.compress_chunk} "
            + (f"topk_frac={args.topk_frac} " if sparse else "")
            + f"gamma={gamma:g} "
            f"(~{4.0 / compress_cfg.leaf_row_bytes(args.compress_chunk, np.float32) * args.compress_chunk:.1f}x bytes)"
        )

    n = args.nodes
    graph = build_graph(args.topology, n, args.seed)
    sched_graphs = None
    mix_plan = graph
    if args.topology_schedule != "static":
        if args.topology_schedule == "churn":
            sched_graphs = T.churn_sequence(
                graph, args.plans, args.churn_rate, seed=args.seed + 1
            )
        else:  # cyclic: independently re-sampled graphs of the same family
            sched_graphs = [graph] + [
                build_graph(args.topology, n, args.seed + 101 * t)
                for t in range(1, args.plans)
            ]
        # failures ride in via make_round_fn's link_p/node_p override
        mix_plan = compile_schedule(sched_graphs, round_map=cyclic_map(args.plan_period))
        print(
            f"schedule: {args.topology_schedule} K={mix_plan.k} "
            f"period={args.plan_period}"
            + (f" churn_rate={args.churn_rate}" if args.topology_schedule == "churn" else "")
        )
    gain = 1.0 if args.no_gain_correction else gain_from_graph(graph)
    print(f"graph={graph.name} ‖v_steady‖⁻¹ gain={gain:.2f}" + (" (DISABLED)" if args.no_gain_correction else ""))
    opt = sgd(1e-3, 0.5) if args.optimizer == "sgd" else adamw(1e-3)

    if args.arch:
        cfg = get_reduced_config(args.arch)
        icfg = InitConfig("trunc_normal", gain)
        toks = np.stack([make_token_stream(20_000, cfg.vocab_size, seed=args.seed + i) for i in range(n)])
        it = token_batch_iterator(toks, batch_size=args.batch_size, seq_len=64, seed=args.seed)

        def loss_fn(params, batch):
            x, y = batch
            hidden, aux = TF.forward(params, cfg, x)
            return TF.lm_loss(params, cfg, hidden, y) + 0.01 * aux

        def batches():
            while True:
                bs = [next(it) for _ in range(args.local_batches)]
                yield (np.stack([b.x for b in bs], 1), np.stack([b.y for b in bs], 1))

        init_with = lambda c: (lambda k: TF.init_params(k, cfg, c))
        eval_batch = None
        eval_fn = None
    elif token_model:
        # reduced zoo arch on windowed token data: xs/ys are (n, items, seq)
        # next-token windows, so the fused executors' schedule gather (and
        # the compressed mix riding them) drive a transformer-scale payload
        cfg = get_reduced_config(TOKEN_MODELS[args.model])
        seq, items = args.seq_len, args.items_per_node
        win = (np.arange(items) * seq)[:, None] + np.arange(seq + 1)

        def windows(seed):
            t = make_token_stream(items * seq + 1, cfg.vocab_size, seed=seed)[win]
            return t[:, :-1].astype(np.int32), t[:, 1:].astype(np.int32)

        per_node = [windows(args.seed + i) for i in range(n)]
        xs = np.stack([x for x, _ in per_node])
        ys = np.stack([y for _, y in per_node])
        ex, ey = windows(args.seed + n)  # held-out stream, same window grid
        eval_batch = (ex[:64], ey[:64])
        icfg = InitConfig("trunc_normal", gain)
        init_with = lambda c: (lambda k: TF.init_params(k, cfg, c))

        def loss_fn(params, batch):
            x, y = batch
            hidden, aux = TF.forward(params, cfg, x)
            return TF.lm_loss(params, cfg, hidden, y) + 0.01 * aux

        eval_fn = make_eval_fn(loss_fn)
        d_model = sum(
            int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(TF.init_params(jax.random.PRNGKey(0), cfg, icfg))
        )
        print(f"token model {cfg.name}: {d_model / 1e6:.2f}M params/node, seq {seq}")
    else:
        model = args.model or "mlp"
        ds = {"mlp": mnist_like, "cnn": so2sat_like, "vgg16": cifar10_like}[model](
            n * args.items_per_node + 1024, seed=args.seed
        )
        if args.zipf > 0:
            parts = partition_zipf(ds.y[: n * args.items_per_node], n, alpha=args.zipf, seed=args.seed)
        else:
            parts = partition_iid(n * args.items_per_node, n, seed=args.seed)
        xs, ys = node_datasets(ds, parts)
        eval_batch = (ds.x[-1024:], ds.y[-1024:])
        icfg = InitConfig("he_normal", gain)
        if model == "mlp":
            init_with = lambda c: (lambda k: init_mlp(c, k))
            fwd = mlp_forward
        elif model == "cnn":
            init_with = lambda c: (lambda k: init_cnn(c, k, image_shape=ds.x.shape[1:], n_classes=ds.n_classes))
            fwd = cnn_forward
        else:
            init_with = lambda c: (
                lambda k: init_vgg16(c, k, image_shape=ds.x.shape[1:], n_classes=ds.n_classes, width_mult=0.25)
            )
            fwd = vgg16_forward
        loss_fn = lambda p, b: classifier_loss(fwd(p, b[0]), b[1])
        eval_fn = make_eval_fn(loss_fn)

        def batches():
            it = node_batch_iterator(xs, ys, args.batch_size, seed=args.seed)
            while True:
                bs = [next(it) for _ in range(args.local_batches)]
                yield (np.stack([b.x for b in bs], 1), np.stack([b.y for b in bs], 1))

    init_one = init_with(icfg)
    init_one_g = lambda k, gn: init_with(icfg.replace(gain=gn))(k)
    key = jax.random.PRNGKey(args.seed)
    ckpt_policy = None
    if args.ckpt_dir and args.checkpoint_every > 0:
        ckpt_policy = CheckpointPolicy(args.ckpt_dir, every=args.checkpoint_every)
    # the async branch mixes pairwise through its own plan — don't compile a
    # round function (and its O(n²) dense operator) it would never call
    round_fn = (
        None
        if args.async_gossip
        else make_round_fn(
            loss_fn, opt, mix_plan, link_p=args.link_p, node_p=args.node_p,
            compression=compress_cfg,
        )
    )
    eval_every = max(1, args.rounds // 20)
    if args.log_every > 0 and not args.chunk_rounds:
        args.chunk_rounds = args.log_every

    def stream_rows(r0, r1, h):
        # fires at chunk boundaries with the chunk's assembled history slice
        del r0, r1
        for i, r in enumerate(h["round"]):
            line = f"round {r:4d} train {h['train_loss'][i]:.4f}"
            if h.get("test_loss"):
                line += f" test {h['test_loss'][i]:.4f}"
            if h.get("n_active"):
                line += f" active {h['n_active'][i]:3d}"
            if h.get("wire_bytes"):
                line += f" wire {h['wire_bytes'][i]}B"
            print(line, flush=True)

    stream_hook = stream_rows if args.log_every > 0 else None
    profile_ctx = contextlib.ExitStack()
    profile_ctx.enter_context(profile_trace(args.profile_trace))
    estimate_fn = None
    if args.uncoordinated_init and not args.async_gossip:
        # the async branch estimates with barrier-free leaderless sketches
        # over its own event stream instead (below) — don't build (and
        # compile) a round-based estimator it would never call
        # estimation rides the same links — and the same failure model — as
        # the training rounds (unit-weight plan: Eq. 3 send operator); over a
        # topology schedule the gossip itself follows the dynamic graph
        fm = FailureModel(link_p=args.link_p, node_p=args.node_p)
        if sched_graphs is not None:
            est_plan = compile_schedule(
                sched_graphs, failures=fm, round_map=cyclic_map(args.plan_period)
            )
        else:
            est_plan = compile_plan(graph, failures=fm)
        estimate_fn = make_gain_estimator(
            est_plan, pi_rounds=args.estimate_rounds, ps_rounds=args.estimate_rounds,
            mode=args.estimate_mode, leaderless=args.leaderless,
        )
    if args.async_gossip:
        # ---- event-driven path: no round barrier, no estimation barrier ----
        horizon = args.event_horizon if args.event_horizon is not None else float(args.rounds)
        fm = FailureModel(link_p=args.link_p, node_p=args.node_p)
        plan = compile_plan(graph, failures=fm)
        stream = T.poisson_event_stream(
            graph, horizon=horizon, rate=args.event_rate, seed=args.seed + 2
        )
        print(
            f"event stream: {stream.n_events} events over horizon {horizon:g} "
            f"(rate {args.event_rate:g}, {2 * stream.n_events} messages)"
        )
        sched = batch_index_schedule(
            ys.shape[1], n, args.batch_size,
            max(int(horizon), 1) * args.local_batches, seed=args.seed,
        )
        if args.uncoordinated_init:
            # estimation is barrier-free too: leaderless sketches over their
            # own Poisson stream (--estimate-rounds units of virtual time).
            # --estimate-mode vnorm/alpha and --leaderless don't apply here:
            # the event path always sketches (no leader, no phase counter)
            # and gains are n̂^0.5 — the §4.4 size-only knowledge regime
            est_stream = T.poisson_event_stream(
                graph, horizon=float(args.estimate_rounds), rate=args.event_rate,
                seed=args.seed + 3,
            )
            k_est, key = jax.random.split(key)
            n_hat = estimate_size_leaderless_events(plan, est_stream, k_est)
            gains = np.asarray(jax.jit(gains_from_estimates)(n_hat))
            print(
                f"barrier-free leaderless gains (n̂^0.5): mean={gains.mean():.2f} "
                f"min={gains.min():.2f} max={gains.max():.2f}"
            )
            state = init_fl_state(key, n, init_one_g, opt, gains=gains)
        else:
            state = init_fl_state(key, n, init_one, opt)
        state, hist, _aux = run_event_trajectory(
            state, loss_fn, opt, plan, stream, xs, ys, sched,
            b_local=args.local_batches, n_bins=20, eval_fn=eval_fn,
            eval_batch=eval_batch, compression=compress_cfg,
        )
        for i, t in enumerate(hist["time"]):
            print(
                f"t={t:8.1f} train {hist['train_loss'][i]:.4f} "
                f"test {hist['test_loss'][i]:.4f} stale {hist['staleness'][i]:.2f} "
                f"msgs {hist['messages'][i]}", flush=True,
            )
    elif args.arch or args.legacy_loop:
        # token streams sample per-batch windows (no gather schedule yet), so
        # the arch path stays on the host-driven loop
        if estimate_fn is None:
            state = init_fl_state(key, n, init_one, opt)
        else:
            k_est, k_init = jax.random.split(key)
            gains = np.asarray(jax.jit(estimate_fn)(k_est))
            print(f"gossip gains: mean={gains.mean():.2f} min={gains.min():.2f} max={gains.max():.2f}")
            state = init_fl_state(k_init, n, init_one_g, opt, gains=gains)
        state, hist = train_loop(
            state, round_fn, batches(), n_rounds=args.rounds, eval_every=eval_every,
            eval_fn=eval_fn, eval_batch=eval_batch, track_sigmas=True, progress=True,
        )
    else:
        sched = batch_index_schedule(
            ys.shape[1], n, args.batch_size, args.rounds * args.local_batches, seed=args.seed
        )
        common = dict(
            n_rounds=args.rounds, eval_every=eval_every, eval_fn=eval_fn,
            eval_batch=eval_batch, track_sigmas=True, chunk_size=args.chunk_rounds,
            b_local=args.local_batches,
        )
        if args.elastic:
            join_round = args.join_round if args.join_round is not None else args.rounds // 2
            if args.join_nodes:
                mem = membership_schedule(
                    n, args.rounds, initial=n - args.join_nodes,
                    arrivals={join_round: list(range(n - args.join_nodes, n))},
                    join_warmup=args.join_warmup,
                )
                print(
                    f"membership: {n - args.join_nodes} initial, "
                    f"{args.join_nodes} arrive at round {join_round} "
                    f"(warmup {args.join_warmup})"
                )
            else:
                mem = membership_schedule(n, args.rounds)
            faults = (
                None if args.fault_scenario == "none"
                else scenario(args.fault_scenario, graph, args.rounds, seed=args.seed)
            )
            if faults is not None:
                print(f"fault plan: {faults.name} "
                      f"({(~faults.node_up).sum()} node-round outages, "
                      f"{(~faults.edge_up).sum()} edge-round cuts)")
            state = init_fl_state(key, n, init_one, opt)
            state, hist, aux = run_elastic_trajectory(
                state, loss_fn, opt, mix_plan, mem, xs, ys, sched,
                n_rounds=args.rounds, eval_every=eval_every, eval_fn=eval_fn,
                eval_batch=eval_batch, chunk_size=args.chunk_rounds,
                b_local=args.local_batches, init_one=init_one_g, faults=faults,
                checkpoint=ckpt_policy, resume_from=args.resume,
                on_chunk=stream_hook, compression=compress_cfg,
            )
            if stream_hook is None:
                for i, r in enumerate(hist["round"]):
                    print(
                        f"round {r:4d} train {hist['train_loss'][i]:.4f} "
                        f"test {hist['test_loss'][i]:.4f} "
                        f"active {hist['n_active'][i]:3d}", flush=True,
                    )
        elif estimate_fn is None:
            state = init_fl_state(key, n, init_one, opt)
            state, hist = run_trajectory(
                state, round_fn, xs, ys, sched,
                checkpoint=ckpt_policy, resume_from=args.resume,
                on_chunk=stream_hook, **common,
            )
        else:
            # fused warmup: estimate → per-node gain → init → train is one program
            state, hist, gains = run_warmup_trajectory(
                key, round_fn, xs, ys, sched, n_nodes=n, init_one=init_one_g,
                optimizer=opt, estimate_gains=estimate_fn, **common,
            )
            print(f"gossip gains: mean={gains.mean():.2f} min={gains.min():.2f} max={gains.max():.2f}")
        if not args.elastic and (stream_hook is None or estimate_fn is not None):
            # the fused-warmup path has no chunk hook — it prints at the end
            for i, r in enumerate(hist["round"]):
                print(f"round {r:4d} train {hist['train_loss'][i]:.4f} test {hist['test_loss'][i]:.4f}", flush=True)
    profile_ctx.close()
    if args.ckpt_dir and ckpt_policy is None:
        # legacy params-only snapshot; with --checkpoint-every the trajectory
        # checkpoints own the directory (LATEST must stay resume-compatible)
        path = save_train_state(args.ckpt_dir, int(state.round), state.params, meta={"graph": graph.name})
        print(f"checkpoint: {path}")
    if args.history_out:
        os.makedirs(os.path.dirname(args.history_out) or ".", exist_ok=True)
        with open(args.history_out, "w") as f:
            json.dump(hist, f, indent=1)
        print(f"history: {args.history_out}")
    if args.telemetry:
        records = [run_manifest(vars(args), seed=args.seed, argv=sys.argv[1:])]
        records += history_rows(hist, kind="bin" if args.async_gossip else "round")
        summary = {"kind": "summary", "rounds_run": int(state.round)}
        if hist.get("train_loss"):
            summary["final_train_loss"] = hist["train_loss"][-1]
        if hist.get("test_loss"):
            summary["final_test_loss"] = hist["test_loss"][-1]
        if hist.get("wire_messages"):
            summary["recorded_wire_messages"] = int(sum(hist["wire_messages"]))
        elif hist.get("messages"):
            summary["recorded_wire_messages"] = int(sum(hist["messages"]))
        if hist.get("wire_bytes"):
            summary["recorded_wire_bytes"] = int(sum(hist["wire_bytes"]))
        records.append(summary)
        # gossip-health fingerprint of the mixing operator actually used
        if args.async_gossip:
            health_plan = plan
        elif round_fn is not None:
            health_plan = getattr(round_fn, "plan", None)
        else:
            health_plan = None
        if isinstance(health_plan, CommPlan):
            hk = (
                jax.random.PRNGKey(args.seed + 17)
                if health_plan.failures.active else None
            )
            records.append({
                "kind": "gossip_health",
                **gossip_health(health_plan, rounds=min(64, max(16, 2 * n)), key=hk),
            })
        n_rec = write_run_log(args.telemetry, records)
        print(f"telemetry: {args.telemetry} ({n_rec} records)")
    return state, hist


def main() -> None:
    use_compile_cache()
    run(parse_args())


if __name__ == "__main__":
    main()
