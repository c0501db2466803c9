"""Fused multi-round executor: a whole DFL trajectory as one scanned program.

``fed.trainer.train_loop`` dispatches one jitted round per Python iteration,
re-assembles every node's minibatch on the host, and blocks on device→host
syncs at every eval — at the paper's scales dispatch and host overhead
dominate everything the benchmarks measure.  This module fuses the entire
trajectory (DESIGN.md §11):

* **scan over rounds** — ``n_rounds`` of local-steps → CommPlan mixing →
  opt reinit run as chunked ``lax.scan`` inside a single jitted,
  buffer-donated call; Python re-enters once per *chunk*, not per round.
* **on-device data sampling** — the per-node datasets live on device and
  each round's minibatches are drawn (``batching.draw_batch``: a one-hot
  contraction on the TPU, a gather elsewhere) from the precomputed
  ``data.pipeline.batch_index_schedule`` (bit-identical order to the host
  iterator for the same seed).
* **on-device metrics** — periodic eval / σ_an/σ_ap are computed inside the
  scan under ``lax.cond`` and written to fixed-size per-round output
  buffers; the host touches them once, after the last chunk.  The channels
  route through ``repro.obs`` (``MetricsSpec``/``Recorder``, DESIGN.md §17)
  — bit-identical to the hand-rolled outs they replaced — and every
  executor reports per-round wire cost (messages / bytes) alongside loss.
* **sweep axis** — ``run_sweep`` vmaps the whole scanned trajectory over a
  leading run axis (seeds × gains × ...), so a figure's grid of trajectories
  compiles to a handful of programs.
* **warmup phase** — ``run_warmup_trajectory`` prepends the uncoordinated-
  init estimation phase (``repro.gossip``): gossip estimates → per-node
  gains → vmapped init → first training chunk, fused as one program
  (DESIGN.md §12).

``round_fn`` is exactly the function ``make_round_fn`` builds — the executor
re-uses it unchanged, which is what makes executor-vs-legacy parity
bit-exact (same PRNG stream, same batch order, same round body).  The
node-sharded and elastic executors run the same round, ``trainer.dfl_round``,
and own only what surrounds it: carry layout, metric reductions, membership
masks, sketches.
"""
from __future__ import annotations

import dataclasses
import os
import signal
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.checkpoint.io import restore_train_state, save_train_state
from repro.core.commplan import CommPlan, PlanSchedule, compile_plan
from repro.core.compress import (
    Compression,
    compressed_mix_with,
    mask_rows,
    seed_residual,
)
from repro.core.shardplan import ShardedCommPlan
from repro.core.topology import EventStream, Graph
from repro.obs.health import staleness_histogram
from repro.obs.spec import BinChannel, BinSpec, Channel, MetricsSpec, Recorder
from repro.obs.trace import count, span
from repro.obs.wirecost import (
    make_wire_fn,
    param_row_bytes,
    sharded_wire_per_round,
    static_wire_messages,
)

from .batching import draw_batch, lane_dense
from .trainer import (
    DFLState,
    _local_steps,
    dfl_round,
    init_fl_state,
    make_round_fn,
    sigma_metrics,
)

PyTree = Any

# staleness-histogram buckets of the event executor (linear over [0, horizon])
_STALE_BUCKETS = 16

__all__ = [
    "CheckpointPolicy",
    "TrajectoryConfig",
    "run_trajectory",
    "run_sharded_trajectory",
    "run_event_trajectory",
    "run_elastic_trajectory",
    "run_warmup_trajectory",
    "run_warmup_sweep",
    "run_sweep",
    "stack_states",
    "unstack_states",
]


@dataclasses.dataclass(frozen=True)
class TrajectoryConfig:
    """Static knobs of a fused trajectory.

    ``eval_every`` matches ``train_loop``: metrics are recorded at rounds
    ``r % eval_every == 0`` plus the final round; 0 disables recording.
    ``chunk_size`` bounds rounds per jitted call (0 = auto): smaller chunks
    surface metrics earlier, larger ones amortise dispatch further.
    """

    n_rounds: int
    eval_every: int = 0
    track_sigmas: bool = False
    chunk_size: int = 0

    def eval_mask(self) -> np.ndarray:
        mask = np.zeros(self.n_rounds, dtype=bool)
        if self.eval_every:
            mask[:: self.eval_every] = True
            mask[-1] = True
        return mask

    def chunks(self) -> list[tuple[int, int]]:
        size = self.chunk_size
        if size <= 0:
            size = self.n_rounds if self.n_rounds <= 1024 else 256
        return [(r0, min(r0 + size, self.n_rounds)) for r0 in range(0, self.n_rounds, size)]


@dataclasses.dataclass(frozen=True)
class CheckpointPolicy:
    """Chunk-boundary checkpointing of a fused trajectory (DESIGN.md §16).

    After every ``every``-th chunk the executor snapshots the **full scan
    carry** (params, optimizer state, PRNG stream, data cursors, virtual
    clocks, metric accumulators) plus the realised per-chunk metric buffers
    into ``dir`` via the durable ``checkpoint.io`` layout, repointing LATEST
    and keeping the newest ``keep_last`` steps.  A later call with
    ``resume_from=dir`` replays the remaining chunks **bit-identically** —
    the chunk programs are pure functions of the restored carry.

    ``kill_after`` is the fault-injection hook (``core.faults.preemption``):
    chunk index after whose checkpoint the process SIGKILLs itself —
    uncatchable, mid-run, exactly the preemption the resume contract must
    survive.  -1 disables.
    """

    dir: str
    every: int = 1
    keep_last: int = 3
    kill_after: int = -1


def _save_chunk_ckpt(
    policy: CheckpointPolicy, chunk_idx: int, is_last: bool, carry, outs, meta: dict
) -> None:
    due = policy.every <= 1 or (chunk_idx + 1) % policy.every == 0
    if due or is_last or policy.kill_after == chunk_idx:
        with span("dfl.chunk.checkpoint", ci=chunk_idx):
            payload = {
                "carry": [np.asarray(jax.device_get(l)) for l in jax.tree_util.tree_leaves(carry)],
                "outs": [[np.asarray(c) for c in o] for o in outs],
            }
            save_train_state(
                policy.dir, chunk_idx, payload,
                meta={**meta, "chunk": chunk_idx}, keep_last=policy.keep_last,
            )
    if policy.kill_after == chunk_idx:
        os.kill(os.getpid(), signal.SIGKILL)


def _load_resume(resume_from: str, meta_id: dict):
    """(payload, start_chunk) from a checkpoint dir, or None to start fresh.
    Every identity field recorded at save time must match the caller's —
    resuming under different trajectory knobs would not be a replay."""
    restored = restore_train_state(resume_from)
    if restored is None:
        return None
    payload, meta = restored
    for k, v in meta_id.items():
        if meta.get(k) != v:
            raise ValueError(
                f"checkpoint at {resume_from!r} was written with {k}={meta.get(k)!r}, "
                f"but this run has {k}={v!r} — resume must replay the same trajectory"
            )
    return payload, int(meta["chunk"]) + 1


def _restore_carry(template, payload) -> PyTree:
    """Rebuild the scan carry from checkpointed leaves, using the live
    template's treedef (NamedTuples and custom nodes round-trip exactly)."""
    treedef = jax.tree_util.tree_structure(template)
    leaves = payload["carry"]
    if treedef.num_leaves != len(leaves):
        raise ValueError(
            f"checkpoint carries {len(leaves)} leaves, live state wants {treedef.num_leaves}"
        )
    return jax.tree_util.tree_unflatten(treedef, [jnp.asarray(l) for l in leaves])


def stack_states(states: Sequence[DFLState]) -> DFLState:
    """Stack independent DFLStates into one with a leading sweep axis."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *states)


def unstack_states(states: DFLState) -> list[DFLState]:
    """Split a swept DFLState back into its independent runs."""
    n = int(jax.tree_util.tree_leaves(states)[0].shape[0])
    return [jax.tree_util.tree_map(lambda l: l[i], states) for i in range(n)]


def _as_round_schedule(
    schedule: np.ndarray, n_rounds: int, b_local: int | None = None
) -> np.ndarray:
    """(n_rounds·b, n, bs) or (n_rounds, n, b, bs) → (n_rounds, n, b, bs).

    Pass ``b_local`` to pin the local-steps-per-round split: an oversized
    flat schedule that happens to divide n_rounds would otherwise be
    silently reinterpreted as more local steps per round.
    """
    s = np.asarray(schedule)
    if s.ndim == 4:
        if s.shape[0] != n_rounds:
            raise ValueError(f"schedule rounds {s.shape[0]} != n_rounds {n_rounds}")
        if b_local is not None and s.shape[2] != b_local:
            raise ValueError(f"schedule b_local {s.shape[2]} != b_local {b_local}")
        return s
    if s.ndim != 3 or s.shape[0] % n_rounds:
        raise ValueError(
            f"schedule shape {s.shape} incompatible with n_rounds={n_rounds}"
        )
    b = s.shape[0] // n_rounds
    if b_local is not None and b != b_local:
        raise ValueError(
            f"schedule holds {s.shape[0]} batches = {b}/round over {n_rounds} "
            f"rounds, but b_local={b_local} was requested"
        )
    return s.reshape(n_rounds, b, s.shape[1], s.shape[2]).transpose(0, 2, 1, 3)


def _build_chunk_fn(
    round_fn,
    n_nodes: int,
    eval_fn,
    track_sigmas: bool,
    *,
    sweep: bool = False,
    schedule_mapped: bool = False,
    wire_fn=None,
):
    """Compile-once chunk executor: (state, sched_chunk, mask_chunk, data) →
    (state, per-round metric buffers).

    ``data`` is ``(xs, ys, eval_batch)``: the per-node datasets and the test
    batch enter as jit arguments, never as closed-over constants, so the
    chunk compiles from their shapes alone (``tests/test_tpu_compile.py``
    compiles it for a described TPU) and one compiled program serves every
    dataset of that shape.  The chunk lays ``xs`` out lane-dense, as
    ``(n, items, F)``, once before its scan; ``batching.draw_batch`` draws
    each round's minibatches from it.

    The buffers are the :class:`repro.obs.Recorder`'s channels — the legacy
    train/eval/σ set (bit-identical to the hand-rolled outs this replaced)
    plus, when ``wire_fn`` is given, the round's delivered-message count
    traced from the same ``k_mix`` the round consumes.  Returns
    ``(jitted chunk, donate, raw chunk, recorder)``.
    """
    rec = Recorder(
        MetricsSpec.legacy(eval_fn is not None, track_sigmas, wire=wire_fn is not None)
    )

    def body(data, item_shape, state, per_round):
        xs, ys, eval_batch = data
        idx, do_eval = per_round
        with jax.named_scope("dfl_batch"):
            batch = draw_batch(xs, ys, idx, item_shape)

        def gated_metrics(params):
            vals = {}
            if eval_fn is not None:
                # Barriers keep the eval subgraph isolated from the round body
                # so it compiles like train_loop's standalone eval_fn.  XLA
                # still doesn't guarantee bit-identical lowering across
                # programs (DESIGN.md §11).  optimization_barrier has no vmap
                # batching rule, so the swept path goes without.
                barrier = (lambda x: x) if sweep else jax.lax.optimization_barrier
                with jax.named_scope("dfl_eval"):
                    per_node = barrier(eval_fn(barrier(params), eval_batch))
                with jax.named_scope("dfl_round"):
                    vals["test_loss"] = jnp.mean(per_node).astype(jnp.float32)
            if track_sigmas:
                with jax.named_scope("dfl_sigma"):
                    s = sigma_metrics(params)
                vals["sigma_ap"] = s["sigma_ap"].astype(jnp.float32)
                vals["sigma_an"] = s["sigma_an"].astype(jnp.float32)
            return vals

        values = {}
        if wire_fn is not None:
            # replay the round's k_mix split before round_fn re-derives and
            # consumes it — pure bookkeeping, no PRNG stream is advanced
            with jax.named_scope("dfl_wire"):
                _, k_mix = jax.random.split(state.rng)
                values["wire_messages"] = wire_fn(k_mix, state.round).astype(jnp.float32)
        state, metrics = round_fn(state, batch)
        values["train_loss"] = metrics["train_loss"].astype(jnp.float32)
        out = rec.step(values, gate=do_eval, gated_fn=gated_metrics, operand=state.params)
        return state, out

    def chunk_inner(state, sched_chunk, mask_chunk, data):
        count("dfl.chunk_traces")
        xs, ys, eval_batch = data
        with jax.named_scope("dfl_batch"):
            xs, item_shape = lane_dense(xs)
        return jax.lax.scan(
            partial(body, (xs, ys, eval_batch), item_shape), state, (sched_chunk, mask_chunk)
        )

    chunk = chunk_inner
    if sweep:
        chunk = jax.vmap(
            chunk_inner, in_axes=(0, 0 if schedule_mapped else None, None, None)
        )
    # Donating the carried state lets XLA reuse the ensemble's buffers across
    # chunk calls (the CPU backend does not implement donation).  _drive_chunks
    # copies the caller's state before the first call so donation never
    # invalidates it (train_loop drop-in contract).  The raw *unvmapped*
    # chunk is returned too so the fused warmups (``run_warmup_trajectory``,
    # ``run_warmup_sweep``) can inline it after their estimation/init
    # prologues — the sweep re-vmaps the whole prologue+chunk composite.
    donate = jax.default_backend() != "cpu"
    return jax.jit(chunk, donate_argnums=(0,) if donate else ()), donate, chunk_inner, rec


def _device_data(xs, ys, eval_batch) -> tuple:
    """The chunk programs' ``data`` operand, placed on the default device."""
    ev = None if eval_batch is None else jax.tree_util.tree_map(jnp.asarray, eval_batch)
    return jnp.asarray(xs), jnp.asarray(ys), ev


def _finish_wire(hist: dict, wire_static, row_bytes: int) -> dict:
    """Attach the clean-path static message counts (no device buffer ever
    existed for them) and derive bytes-on-the-wire = messages × row bytes."""
    if wire_static is not None:
        hist["wire_messages"] = [int(wire_static[r]) for r in hist["round"]]
    if "wire_messages" in hist:
        hist["wire_bytes"] = [int(m) * row_bytes for m in hist["wire_messages"]]
    return hist


def _drive_chunks(
    chunk_fn, state, sched_d, mask_np, cfg, *,
    round_axis: int = 0, donate: bool = False, skip: int = 0, head_outs=(),
    checkpoint: CheckpointPolicy | None = None, ckpt_meta: dict | None = None,
    on_chunk=None, operands: tuple = (),
):
    """Run the chunk schedule; one host sync, after the last chunk.

    ``skip``/``head_outs`` let a caller that already executed the first
    ``skip`` chunks through a different program (the fused warmup) — or a
    resumed run that restored them from a checkpoint — hand over their
    metric buffers and continue here.  ``sched_d`` may be any pytree of
    round-axis arrays (the elastic executor threads membership masks
    alongside the batch schedule).  With a ``checkpoint`` policy the carry
    and accumulated metric buffers snapshot at chunk boundaries — syncing
    the carry to host is the checkpoint's cost, paid only on saving chunks.

    ``on_chunk(ci, r0, r1, out)`` fires after every chunk call with the
    chunk's device metric buffers — the streaming/telemetry hook.  Reading
    them costs only that chunk's host transfer (the same one the final
    assembly would pay); without the hook nothing syncs until the end.

    ``operands`` trail every chunk call unchanged (the chunk programs' data).
    """
    if donate:
        # first chunk call would otherwise donate (delete) the caller's state
        state = jax.tree_util.tree_map(jnp.copy, state)
    mask_d = jnp.asarray(mask_np)
    outs = list(head_outs)
    chunks = cfg.chunks()
    for ci in range(skip, len(chunks)):
        r0, r1 = chunks[ci]
        with span("dfl.chunk", ci=ci, r0=r0, r1=r1):
            with span("dfl.chunk.slice"):
                sched_c = jax.tree_util.tree_map(
                    lambda a: jax.lax.slice_in_dim(a, r0, r1, axis=round_axis), sched_d
                )
                mask_c = mask_d[r0:r1]
            with span("dfl.chunk.dispatch"):
                state, out = chunk_fn(state, sched_c, mask_c, *operands)
            outs.append(out)
            if on_chunk is not None:
                on_chunk(ci, r0, r1, out)
            if checkpoint is not None:
                _save_chunk_ckpt(
                    checkpoint, ci, ci == len(chunks) - 1, state, outs, ckpt_meta or {}
                )
    with span("dfl.assemble"):
        cols = [
            np.concatenate([np.asarray(o[i]) for o in outs], axis=-1)
            for i in range(len(outs[0]))
        ]
    return state, cols


def run_trajectory(
    state: DFLState,
    round_fn: Callable[[DFLState, Any], tuple[DFLState, dict]],
    xs: np.ndarray,
    ys: np.ndarray,
    schedule: np.ndarray,
    *,
    n_rounds: int,
    eval_every: int = 0,
    eval_fn=None,
    eval_batch=None,
    track_sigmas: bool = False,
    chunk_size: int = 0,
    b_local: int | None = None,
    checkpoint: CheckpointPolicy | None = None,
    resume_from: str | None = None,
    plan: CommPlan | PlanSchedule | None = None,
    on_chunk=None,
) -> tuple[DFLState, dict[str, list]]:
    """Run a full trajectory fused on device.  Drop-in for ``train_loop``:
    same ``round_fn``, same history dict, bit-identical results — minus the
    per-round dispatch, host batch assembly and per-eval blocking syncs.

    ``schedule`` is ``batch_index_schedule(...)`` output covering
    ``n_rounds × b_local`` minibatches (or already round-shaped
    ``(n_rounds, n, b, bs)``); give ``b_local`` to validate the split.

    ``checkpoint`` snapshots the carry at chunk boundaries; ``resume_from``
    restores the newest snapshot in that directory and replays the remaining
    chunks — the resumed run's final params and metric history are
    **bit-identical** to the uninterrupted run's (the preemption-safety
    contract, subprocess-kill-tested), because each chunk is a pure function
    of the restored carry.  Pass the *same* initial ``state``/arguments as
    the original run; with no checkpoint on disk the run starts fresh.

    Wire cost (DESIGN.md §17): the plan the round mixes over — read from
    ``round_fn.plan`` (``make_round_fn`` attaches it) or passed as ``plan=``
    — adds ``wire_messages`` / ``wire_bytes`` history channels.  Clean plans
    cost nothing (static host-side counts); under an active failure model
    the count is traced in-scan from the same ``k_mix`` the mix consumes.
    Hand-rolled round_fns without a plan simply record no wire channels.

    ``on_chunk(r0, r1, chunk_hist)`` streams each chunk's assembled history
    slice as it lands (the ``--log-every`` hook) — the only added sync is
    the chunk's own host transfer, paid early instead of at the end.
    """
    cfg = TrajectoryConfig(n_rounds, eval_every, track_sigmas, chunk_size)
    count("dfl.calls")
    with span("dfl.trajectory", n_rounds=n_rounds, chunks=len(cfg.chunks())):
        sched_d = jnp.asarray(_as_round_schedule(schedule, n_rounds, b_local))
        data = _device_data(xs, ys, eval_batch)
        eff_plan = plan if plan is not None else getattr(round_fn, "plan", None)
        wire_fn, wire_static = None, None
        if eff_plan is not None:
            if eff_plan.failures.active:
                wire_fn = make_wire_fn(eff_plan)
            else:
                wire_static = static_wire_messages(eff_plan, n_rounds)
        # compressed round_fns (make_round_fn(compression=...)) carry their codec:
        # the mirror seeds into the carry before the scan (static structure) and
        # wire bytes price at the codec's encoding, not the raw itemsize
        comp: Compression | None = getattr(round_fn, "compression", None)
        state = seed_residual(state, comp)
        row_bytes = param_row_bytes(
            state.params, codec_bytes=comp.leaf_row_bytes if comp is not None else None
        )
        chunk_fn, donate, _, rec = _build_chunk_fn(
            round_fn, xs.shape[0], eval_fn, track_sigmas, wire_fn=wire_fn
        )
        meta_id = {
            "kind": "trajectory", "n_rounds": n_rounds, "eval_every": eval_every,
            "track_sigmas": track_sigmas, "chunk_size": cfg.chunk_size,
            "compressed": comp is not None,
        }
        mask_np = cfg.eval_mask()
        hook = None
        if on_chunk is not None:
            def hook(ci, r0, r1, out):
                del ci
                with span("dfl.chunk.fetch"):
                    h = rec.assemble(mask_np[r0:r1], [np.asarray(c) for c in out])
                    h["round"] = [r + r0 for r in h["round"]]
                    h = _finish_wire(h, wire_static, row_bytes)
                on_chunk(r0, r1, h)
        skip, head_outs = 0, ()
        if resume_from is not None:
            resumed = _load_resume(resume_from, meta_id)
            if resumed is not None:
                payload, skip = resumed
                state = _restore_carry(state, payload)
                head_outs = [tuple(np.asarray(c) for c in o) for o in payload["outs"]]
        state, cols = _drive_chunks(
            chunk_fn, state, sched_d, mask_np, cfg, donate=donate,
            skip=skip, head_outs=head_outs, checkpoint=checkpoint, ckpt_meta=meta_id,
            on_chunk=hook, operands=(data,),
        )
        hist = _finish_wire(rec.assemble(mask_np, cols), wire_static, row_bytes)
    return state, hist


def run_sharded_trajectory(
    state: DFLState,
    loss_fn,
    optimizer,
    plan: ShardedCommPlan,
    xs: np.ndarray,
    ys: np.ndarray,
    schedule: np.ndarray,
    *,
    n_rounds: int,
    eval_every: int = 0,
    eval_fn=None,
    eval_batch=None,
    track_sigmas: bool = False,
    b_local: int | None = None,
    compression: Compression | None = None,
) -> tuple[DFLState, dict[str, list]]:
    """Node-sharded fused trajectory: the whole round loop inside ONE
    ``shard_map`` over the plan's node mesh axis (DESIGN.md §15).

    The sharded sibling of ``run_trajectory``: parameter / optimizer stacks,
    the per-node dataset and the batch schedule enter as node-axis-sharded
    operands, each shard scans its ``nps`` nodes' local steps, mixing runs
    through the plan's halo-exchange collectives (``local_mix``), and every
    per-round metric reduces with ``psum`` — no (n, d) array is ever
    materialised on one device.  Each round runs the one round
    (``trainer.dfl_round``: PRNG split, local steps, mix, optimizer reinit)
    with the halo-exchange mix as its operator, so final parameters are
    bit-identical to the single-device executor for the same inputs (the
    property ``tests/test_sharded_plan.py`` pins).

    Differences from ``run_trajectory``, both metric-only: scalar metrics
    reduce as ``psum(local sum)/n`` (a different summation order than one
    global ``mean``, ~1 ulp), and with ``track_sigmas`` the σ moments are
    computed every round (collectives cannot sit under ``lax.cond``) with
    non-eval rounds masked to NaN afterwards.

    ``plan`` must be a static ``ShardedCommPlan`` (``CommPlan.shard()``);
    schedules are not supported here.  ``eval_fn``/``eval_batch`` follow
    ``run_trajectory`` (the eval batch is replicated to every shard).

    ``compression`` runs the error-feedback delta form around the halo-
    exchange ``local_mix`` — mirrors are node-sharded exactly like params
    (compression is a per-node-row transform, so it needs no collective of
    its own), and the halo payload prices at the codec's encoding.
    """
    n_nodes = xs.shape[0]
    if plan.n != n_nodes:
        raise ValueError(f"plan has {plan.n} nodes but xs carries {n_nodes}")
    cfg = TrajectoryConfig(n_rounds, eval_every, track_sigmas, 0)
    count("dfl.calls")
    with span("dfl.trajectory", n_rounds=n_rounds, chunks=1):
        sched_d = jnp.asarray(_as_round_schedule(schedule, n_rounds, b_local))
        xs_d, ys_d, eval_d = _device_data(xs, ys, eval_batch)
        mesh, ax, nps, n = plan.mesh, plan.axis, plan.nps, plan.n
        tables, tab_specs = plan.mix_operands()
        has_eval = eval_fn is not None
        mask_np = cfg.eval_mask()
        xs_d, item_shape = lane_dense(xs_d)
        comp = compression if (compression is not None and compression.active) else None

        def sharded_sigmas(params):
            # σ_ap: per-node moments are shard-local; σ_an needs cross-shard
            # per-parameter moments — two psum phases (sum, then centred sum)
            leaves = [
                l.reshape(l.shape[0], -1).astype(jnp.float32)
                for l in jax.tree_util.tree_leaves(params)
            ]
            d_total = sum(l.shape[1] for l in leaves)
            mean_n = sum(l.sum(axis=1) for l in leaves) / d_total
            var_n = sum(((l - mean_n[:, None]) ** 2).sum(axis=1) for l in leaves) / d_total
            ap = jax.lax.psum(jnp.sqrt(var_n).sum(), ax) / n
            an_sum = jnp.float32(0.0)
            for l in leaves:
                m = jax.lax.psum(l.sum(axis=0), ax) / n
                v = jax.lax.psum(((l - m[None, :]) ** 2).sum(axis=0), ax) / n
                an_sum = an_sum + jnp.sqrt(v).sum()
            return ap.astype(jnp.float32), (an_sum / d_total).astype(jnp.float32)

        def body(carry, per_round, xs_l, ys_l, t):
            params, opt_state, rng, mirror = carry  # mirror: None uncompressed
            idx, do_eval = per_round  # idx: (nps, b, bs) local slice of the schedule
            with jax.named_scope("dfl_batch"):
                batch = draw_batch(xs_l, ys_l, idx, item_shape)
            # a compressed mix keeps the mirror shard-local (a per-node-row
            # transform): only h' rides the halo exchange
            params, opt_state, rng, mirror, losses, _ = dfl_round(
                loss_fn, optimizer, lambda q, key: plan.local_mix_any(q, key, t),
                params, opt_state, rng, batch,
                failures=plan.failures, compression=comp, residual=mirror,
            )
            with jax.named_scope("dfl_round"):
                metrics = [jax.lax.psum(losses.sum(), ax).astype(jnp.float32) / n]
            if has_eval:
                # local eval sum under cond (no collective inside the branch),
                # psum unconditionally: psum(NaN) = NaN keeps skip semantics.
                # The skip branch's NaN is cast to vary over the node axis like
                # the eval branch's shard-local sum.
                with jax.named_scope("dfl_eval"):
                    local = jax.lax.cond(
                        do_eval,
                        lambda p: jnp.sum(eval_fn(p, eval_d)).astype(jnp.float32),
                        lambda p: jax.lax.pcast(jnp.float32(jnp.nan), ax, to="varying"),
                        params,
                    )
                    metrics.append(jax.lax.psum(local, ax) / n)
            if track_sigmas:
                nan = jnp.float32(jnp.nan)
                with jax.named_scope("dfl_sigma"):
                    ap, an = sharded_sigmas(params)
                    metrics += [jnp.where(do_eval, ap, nan), jnp.where(do_eval, an, nan)]
            return (params, opt_state, rng, mirror), tuple(metrics)

        def traj(carry, sched, mask, xs_l, ys_l, t):
            count("dfl.chunk_traces")

            def step(c, pr):
                return body(c, pr, xs_l, ys_l, t)

            return jax.lax.scan(step, carry, (sched, mask))

        pspecs = jax.tree_util.tree_map(
            lambda l: P(ax, *([None] * (l.ndim - 1))), state.params
        )
        ospecs = jax.tree_util.tree_map(
            lambda l: P(ax, *([None] * (l.ndim - 1))), state.opt_state
        )
        data_spec = lambda a: P(ax, *([None] * (a.ndim - 1)))  # noqa: E731
        n_metrics = 1 + int(has_eval) + 2 * int(track_sigmas)
        state = seed_residual(state, comp)
        carry0 = (
            state.params, state.opt_state, state.rng,
            state.residual if comp is not None else None,
        )
        cspecs = (pspecs, ospecs, P(), pspecs if comp is not None else P())
        f = jax.shard_map(
            traj,
            mesh=mesh,
            in_specs=(
                cspecs,
                P(None, ax, None, None),
                P(),
                data_spec(xs_d),
                data_spec(ys_d),
                tab_specs,
            ),
            out_specs=(cspecs, tuple(P() for _ in range(n_metrics))),
        )
        with span("dfl.chunk.dispatch"):
            carry, metrics = jax.jit(f)(
                carry0, sched_d, jnp.asarray(mask_np), xs_d, ys_d, tables
            )
        params, opt_state, rng, mirror = carry
        with span("dfl.assemble"):
            cols = [np.asarray(m) for m in metrics]
        # halo wire cost is a plan static (the cross-shard row set never changes
        # round to round), so the channels are host-side constants — no buffer
        rec = Recorder(MetricsSpec.legacy(has_eval, track_sigmas))
        hist = rec.assemble(
            mask_np, cols,
            constants=sharded_wire_per_round(
                plan, state.params,
                codec_bytes=comp.leaf_row_bytes if comp is not None else None,
            ),
        )
        final = DFLState(
            params=params, opt_state=opt_state,
            round=state.round + jnp.int32(n_rounds), rng=rng, residual=mirror,
        )
    return final, hist


def _make_event_step(
    loss_fn,
    optimizer,
    plan: CommPlan,
    sched_d: jax.Array,
    n_sched_rounds: int,
    xs_d: jax.Array,
    ys_d: jax.Array,
    *,
    comp: Compression | None,
    base_key: jax.Array,
):
    """One gossip event (local phase → pairwise mix → opt reinit → clocks)
    as a reusable traced step, shared by ``run_event_trajectory`` and the
    serving executor (``fed.serve.run_serve_trajectory``) so interleaving
    queries cannot change the training math.

    Returns ``step(params, opt_state, counts, clocks, mirror, i, e, t) ->
    (params, opt_state, counts, clocks, mirror, (liv, loss_mean, stale,
    delivered))``.  ``i`` is the event's ordinal in the *gossip* stream (the
    failure-key fold index), not its position in whatever envelope the
    caller scans — so the failure draws are invariant to interleaved
    non-gossip events.  ``mirror`` is the compression residual tree (pass
    ``None`` when ``comp`` is ``None``).
    """
    ep = plan.event_uv
    failures_active = plan.failures.active
    n_nodes = xs_d.shape[0]

    def step(params, opt_state, counts, clocks, mirror, i, e, t):
        liv = e >= 0
        uv = ep[jnp.maximum(e, 0)]  # (2,) endpoints (padding reads edge 0, masked below)

        # 1. local phase: both endpoints catch up by b_local minibatch steps
        cur = counts[uv] % n_sched_rounds
        idx = sched_d[cur, uv]  # (2, b, bs)
        batch = (xs_d[uv[:, None, None], idx], ys_d[uv[:, None, None], idx])
        pair_p = jax.tree_util.tree_map(lambda l: l[uv], params)
        pair_o = jax.tree_util.tree_map(lambda l: l[uv], opt_state)
        new_p, new_o, loss_pair = jax.vmap(partial(_local_steps, loss_fn, optimizer))(
            pair_p, pair_o, batch
        )
        new_p = jax.tree_util.tree_map(lambda a, old: jnp.where(liv, a, old), new_p, pair_p)
        new_o = jax.tree_util.tree_map(lambda a, old: jnp.where(liv, a, old), new_o, pair_o)
        params = jax.tree_util.tree_map(lambda l, nl: l.at[uv].set(nl), params, new_p)
        opt_state = jax.tree_util.tree_map(lambda l, nl: l.at[uv].set(nl), opt_state, new_o)

        # 2. pairwise exchange (failure draws keyed per event).  event_keep
        # here consumes the same key event_mix folds internally, so the
        # executor's bookkeeping sees exactly the draw that masked the
        # exchange: a failed exchange moves no model (and counts no
        # messages below), but the endpoints did wake and train.
        k = jax.random.fold_in(base_key, i) if failures_active else None
        delivered = (liv & plan.event_keep(k)) if failures_active else liv
        if comp is not None:
            upd = jnp.zeros(n_nodes, bool).at[uv].set(delivered)
            params, mirror = compressed_mix_with(
                lambda q: plan.event_mix(q, e, k), params, mirror, comp,
                update_mask=upd,
            )
        else:
            params = plan.event_mix(params, e, k)

        # 3. pairwise optimizer-state reinit (Algorithm 1 line 15)
        pair_after = jax.tree_util.tree_map(lambda l: l[uv], params)
        fresh = jax.vmap(optimizer.init)(pair_after)
        kept = jax.tree_util.tree_map(lambda l: l[uv], opt_state)
        fresh = jax.tree_util.tree_map(lambda a, old: jnp.where(liv, a, old), fresh, kept)
        opt_state = jax.tree_util.tree_map(lambda l, nl: l.at[uv].set(nl), opt_state, fresh)

        # 4. virtual clocks (staleness measured before the clocks move)
        stale = (t - clocks[uv]).mean()
        clocks = clocks.at[uv].set(jnp.where(liv, t, clocks[uv]))
        counts = counts.at[uv].add(jnp.where(liv, 1, 0))
        return params, opt_state, counts, clocks, mirror, (liv, loss_pair.mean(), stale, delivered)

    return step


def run_event_trajectory(
    state: DFLState,
    loss_fn,
    optimizer,
    plan: CommPlan | Graph,
    stream: EventStream,
    xs: np.ndarray,
    ys: np.ndarray,
    schedule: np.ndarray,
    *,
    b_local: int,
    n_bins: int = 20,
    eval_fn=None,
    eval_batch=None,
    chunk_events: int = 0,
    checkpoint: CheckpointPolicy | None = None,
    resume_from: str | None = None,
    on_chunk=None,
    compression: Compression | None = None,
) -> tuple[DFLState, dict[str, list], dict[str, np.ndarray]]:
    """Event-driven (asynchronous) DFL trajectory: no global round barrier.

    The coordination-free rendering of the round loop (DESIGN.md §14): the
    ``EventStream``'s per-edge Poisson clocks replace the synchronous
    barrier, and one ``lax.scan`` over the (time, edge) envelope runs, per
    event,

      1. a **local phase** — each endpoint takes ``b_local`` minibatch
         steps from its own cursor into the shared gather ``schedule``
         (wrapped modulo its length, so nodes never exhaust it);
      2. the **pairwise DecAvg exchange** ``CommPlan.event_mix`` (per-event
         failure draws keyed ``fold_in(rng, event_index)``; a failed draw
         moves no model and spends no messages, but the endpoints still
         trained — synchronous failed-link semantics);
      3. the pairwise analogue of Algorithm 1 line 15 — the two
         participants' optimizer states re-initialise.

    Per-node **virtual clocks** track each node's last participation time;
    an event's *staleness* is ``t − clock`` at its endpoints — how long the
    pair's models idled since they last moved.  Padding events (edge = -1)
    are the exact identity, so streams of different realised lengths share
    one compiled program.

    Metrics are bucketed into ``n_bins`` equal **wall-time bins** over
    ``stream.horizon`` (per-bin mean train loss / staleness / event and
    message counts; ``eval_fn`` runs once at each bin's last live event), so
    the history plots on the same axes as the synchronous fig1-style curves
    — bin b of a rate-1 stream is the budget-matched peer of synchronous
    round ``b · horizon / n_bins`` in transmitted messages.  Note the local
    phase is event-*triggered*: per unit time a node takes ``degree × b``
    local steps (vs ``b`` per synchronous round), which is why fig9 compares
    convergence per transmitted message, not per local step.

    Semantics knobs mirror ``make_round_fn``; ``plan`` may be a ``Graph``
    (compiled with the auto backend).  Returns ``(final_state, history,
    aux)`` with ``aux`` the per-node clocks/event counts.

    ``chunk_events`` bounds events per jitted call (0 = the whole envelope,
    the fully-fused default); the metric accumulators ride the scan carry,
    so chunking changes nothing numerically.  ``checkpoint``/``resume_from``
    follow ``run_trajectory``: the full carry (params, opt state, event
    counts = data cursors, virtual clocks, per-bin accumulators) snapshots
    at chunk boundaries and a resumed run — fed the *same* initial
    ``state`` — replays the remaining events bit-identically (the per-event
    failure key stream re-derives from ``state.rng``, not from the carry).

    ``compression`` compresses the *pairwise* exchange: the event's two
    endpoints transmit ``C(x − h)``, update their carried mirrors, and
    blend the mirrors — everyone else's rows (and an exchange the failure
    draw killed) stay untouched, mirrors included, because a node that
    transmitted nothing updated nobody's copy.
    """
    plan = compile_plan(plan) if isinstance(plan, Graph) else plan
    if plan.event_uv is None:
        raise ValueError("run_event_trajectory needs an undirected, statically compiled plan")
    n_nodes = xs.shape[0]
    if plan.n != n_nodes:
        raise ValueError(f"plan has {plan.n} nodes but xs carries {n_nodes}")
    s = np.asarray(schedule)
    n_sched_rounds = (s.shape[0] // b_local) if s.ndim == 3 else s.shape[0]
    sched_d = jnp.asarray(_as_round_schedule(s, n_sched_rounds, b_local))
    xs_d, ys_d, eval_d = _device_data(xs, ys, eval_batch)

    # ---- static host realisation of the stream's metric structure --------
    env = stream.envelope
    live_np = stream.edges >= 0
    bins_np = np.clip(
        (stream.times / stream.horizon * n_bins).astype(np.int64), 0, n_bins - 1
    )
    do_eval_np = np.zeros(env, dtype=bool)
    if eval_fn is not None:
        for b in range(n_bins):
            hits = np.nonzero(live_np & (bins_np == b))[0]
            if len(hits):
                do_eval_np[hits[-1]] = True

    comp = compression if (compression is not None and compression.active) else None
    rng, base_key = jax.random.split(state.rng)
    event_step = _make_event_step(
        loss_fn, optimizer, plan, sched_d, n_sched_rounds, xs_d, ys_d,
        comp=comp, base_key=base_key,
    )

    # per-bin accumulators riding the scan carry (repro.obs.BinSpec): sums /
    # counts per wall-time bin, the set-style eval slot, and a fixed-width
    # staleness histogram over [0, horizon] (last bucket catches the tail)
    bin_spec = BinSpec(
        n_bins,
        (
            BinChannel("loss_sum"),
            BinChannel("cnt"),
            BinChannel("stale_sum"),
            BinChannel("msg_cnt"),
            BinChannel("test_bin", fill=float("nan")),
            BinChannel("stale_hist", width=_STALE_BUCKETS),
        ),
    )
    horizon = float(stream.horizon)

    def body(carry, inp):
        params, opt_state, counts, clocks, acc, mirror = carry  # mirror: None uncompressed
        i, e, t, b, do_ev = inp
        params, opt_state, counts, clocks, mirror, (liv, loss_mean, stale, delivered) = (
            event_step(params, opt_state, counts, clocks, mirror, i, e, t)
        )
        livf = liv.astype(jnp.float32)

        # per-bin metric accumulation
        acc = dict(acc)
        acc["loss_sum"] = acc["loss_sum"].at[b].add(loss_mean * livf)
        acc["stale_sum"] = acc["stale_sum"].at[b].add(stale * livf)
        acc["cnt"] = acc["cnt"].at[b].add(livf)
        acc["msg_cnt"] = acc["msg_cnt"].at[b].add(2.0 * delivered.astype(jnp.float32))
        sb = jnp.clip(
            (stale / horizon * _STALE_BUCKETS).astype(jnp.int32), 0, _STALE_BUCKETS - 1
        )
        acc["stale_hist"] = acc["stale_hist"].at[sb].add(livf)
        if eval_fn is not None:
            acc["test_bin"] = jax.lax.cond(
                do_ev,
                lambda tb: tb.at[b].set(jnp.mean(eval_fn(params, eval_d)).astype(jnp.float32)),
                lambda tb: tb,
                acc["test_bin"],
            )
        return (params, opt_state, counts, clocks, acc, mirror), None

    @jax.jit
    def drive_chunk(carry, inp):
        carry, _ = jax.lax.scan(body, carry, inp)
        return carry

    state = seed_residual(state, comp)
    carry = (
        state.params,
        state.opt_state,
        jnp.zeros(n_nodes, jnp.int32),
        jnp.zeros(n_nodes, jnp.float32),
        bin_spec.init(),
        state.residual if comp is not None else None,
    )
    inp_all = (
        jnp.arange(env, dtype=jnp.int32),
        jnp.asarray(stream.edges),
        jnp.asarray(stream.times),
        jnp.asarray(bins_np, jnp.int32),
        jnp.asarray(do_eval_np),
    )
    size = env if chunk_events <= 0 else int(chunk_events)
    bounds = [(i0, min(i0 + size, env)) for i0 in range(0, env, size)]
    meta_id = {
        "kind": "event", "env": env, "n_bins": n_bins,
        "chunk_events": size, "compressed": comp is not None,
    }
    skip = 0
    if resume_from is not None:
        resumed = _load_resume(resume_from, meta_id)
        if resumed is not None:
            payload, skip = resumed
            carry = _restore_carry(carry, payload)
    for ci in range(skip, len(bounds)):
        i0, i1 = bounds[ci]
        carry = drive_chunk(carry, tuple(a[i0:i1] for a in inp_all))
        if on_chunk is not None:
            on_chunk(ci, i0, i1, carry[4])
        if checkpoint is not None:
            _save_chunk_ckpt(checkpoint, ci, ci == len(bounds) - 1, carry, [], meta_id)
    params, opt_state, counts, clocks, acc, mirror = carry
    cnt_np = np.asarray(acc["cnt"])
    safe = np.maximum(cnt_np, 1.0)
    width = stream.horizon / n_bins
    row_bytes = param_row_bytes(
        state.params, codec_bytes=comp.leaf_row_bytes if comp is not None else None
    )
    messages = [int(v) for v in np.asarray(acc["msg_cnt"])]
    hist = {
        "bin": list(range(n_bins)),
        "time": [float((b + 1) * width) for b in range(n_bins)],
        "train_loss": [float(v) for v in np.asarray(acc["loss_sum"]) / safe],
        "test_loss": [float(v) for v in np.asarray(acc["test_bin"])],
        "staleness": [float(v) for v in np.asarray(acc["stale_sum"]) / safe],
        "events": [int(v) for v in cnt_np],
        # delivered messages only: an exchange the failure draw killed moved
        # no model, so it spends none of the budget fig9 normalises by
        "messages": messages,
        "wire_bytes": [m * row_bytes for m in messages],
    }
    final = DFLState(
        params=params,
        opt_state=opt_state,
        round=state.round + jnp.int32(stream.n_events),
        rng=rng,
        residual=mirror,
    )
    aux = {
        "node_clock": np.asarray(clocks),
        "node_events": np.asarray(counts),
        "staleness_hist": staleness_histogram(acc["stale_hist"], horizon),
    }
    return final, hist, aux


def run_elastic_trajectory(
    state: DFLState,
    loss_fn,
    optimizer,
    plan: CommPlan | PlanSchedule | Graph,
    membership,
    xs: np.ndarray,
    ys: np.ndarray,
    schedule: np.ndarray,
    *,
    n_rounds: int,
    eval_every: int = 0,
    eval_fn=None,
    eval_batch=None,
    b_local: int | None = None,
    chunk_size: int = 0,
    init_one: Callable[[jax.Array, jax.Array], PyTree] | None = None,
    n_sketches: int = 32,
    faults=None,
    checkpoint: CheckpointPolicy | None = None,
    resume_from: str | None = None,
    on_chunk=None,
    compression: Compression | None = None,
) -> tuple[DFLState, dict[str, list], dict[str, np.ndarray]]:
    """Elastic-membership fused trajectory: nodes join, leave, crash — the
    static-envelope rendering of DESIGN.md §16.

    Each round runs the one round (``trainer.dfl_round``) at the full
    n-node envelope; a ``core.membership.MembershipSchedule`` lowers to
    per-round masks that (a) are its ``live`` mask, freezing non-members'
    params/optimizer (their local phase computes and is discarded — static
    shapes, no recompilation), and (b) thread ``active=`` / ``edge_live=``
    into the ``CommPlan`` operators of its mix, where the
    masked receive matrix renormalises members' rows over the live
    neighbourhood and turns non-members into identity rows.  A
    ``core.faults.FaultPlan`` ANDs its correlated outage masks into the
    same channel.

    Join protocol (§4.4 applied mid-run): an arriving node redraws Exp(1)
    sketches; every gossip-active node min-exchanges them each round
    (``spread_min`` riding the *same* per-round failure key as the training
    mix, so estimation shares training's links); after the membership's
    ``join_warmup`` rounds the joiner initialises **uncoordinated** via
    ``init_one(key, gain)`` with the size-only gain ``√n̂`` from its own
    online sketches — no leader, no barrier, nobody else pauses.

    A membership with no dynamics (``membership.trivial``) and no faults
    delegates to ``make_round_fn`` + ``run_trajectory`` — the zero-event
    path IS the static executor, bit for bit (the K = 1 contract applied to
    membership).  ``checkpoint``/``resume_from`` snapshot the full carry
    (params, opt state, PRNG, sketches) exactly like ``run_trajectory``.

    Returns ``(final_state, history, aux)``: history rows at the eval mask
    with ``n_active`` alongside the losses; ``aux`` carries the final
    per-node n̂ from the carried sketches.

    ``compression`` compresses the training mix exactly as in
    ``make_round_fn``; only the *live training* population updates its
    mirror each round (frozen / crashed nodes transmitted nothing, so
    their peers' copies — and their own — stay put until they return).
    Sketch min-exchanges stay uncompressed: they are O(n_sketches) floats,
    not model payloads.
    """
    plan = compile_plan(plan) if isinstance(plan, Graph) else plan
    n_nodes = xs.shape[0]
    if plan.n != n_nodes:
        raise ValueError(f"plan has {plan.n} nodes but xs carries {n_nodes}")
    if membership.n != n_nodes or membership.n_rounds != n_rounds:
        raise ValueError(
            f"membership is ({membership.n_rounds}, {membership.n}) but the run "
            f"wants ({n_rounds}, {n_nodes})"
        )
    trivial_faults = faults is None or faults.trivial
    if faults is not None and (faults.n != n_nodes or faults.n_rounds != n_rounds):
        raise ValueError(
            f"fault plan is ({faults.n_rounds}, {faults.n}) but the run wants "
            f"({n_rounds}, {n_nodes})"
        )
    if membership.trivial and trivial_faults:
        round_fn = make_round_fn(loss_fn, optimizer, plan, compression=compression)
        state, hist = run_trajectory(
            state, round_fn, xs, ys, schedule,
            n_rounds=n_rounds, eval_every=eval_every, eval_fn=eval_fn,
            eval_batch=eval_batch, chunk_size=chunk_size, b_local=b_local,
            checkpoint=checkpoint, resume_from=resume_from, on_chunk=on_chunk,
        )
        hist["n_active"] = [n_nodes] * len(hist["round"])
        return state, hist, {"n_hat": np.full(n_nodes, float(n_nodes))}
    if membership.inits.any() and init_one is None:
        raise ValueError("membership has joining nodes: init_one(key, gain) is required")

    cfg = TrajectoryConfig(n_rounds, eval_every, False, chunk_size)
    count("dfl.calls")
    with span("dfl.trajectory", n_rounds=n_rounds, chunks=len(cfg.chunks())):
        comp = compression if (compression is not None and compression.active) else None
        has_inits = bool(membership.inits.any())
        mask_np = cfg.eval_mask()
        sched_np = _as_round_schedule(schedule, n_rounds, b_local)
        xs_d, ys_d, eval_d = _device_data(xs, ys, eval_batch)
        xs_d, item_shape = lane_dense(xs_d)
        if trivial_faults:
            node_up = np.ones((n_rounds, n_nodes), bool)
            edge_up = np.ones((n_rounds, max(plan.n_edges, 1)), bool)
        else:
            node_up, edge_up = faults.node_up, faults.edge_up

        # aux PRNG streams fork off state.rng without consuming from it: the
        # training stream (per-round k_mix splits) stays the static executors'
        k_fresh, k_init = jax.random.split(jax.random.fold_in(state.rng, 0x5EED))
        sketches0 = jax.random.exponential(
            jax.random.fold_in(k_fresh, n_rounds), (n_nodes, n_sketches)
        )

        # wire accountant: same per-round key, membership and fault masks the
        # mix consumes, so the count is exactly the delivered-edge set (§17)
        wire_fn = make_wire_fn(plan)
        channels = [Channel("train_loss")]
        if eval_fn is not None:
            channels.append(Channel("test_loss", gated=True))
        channels.append(Channel("n_active", ints=True))
        if wire_fn is not None:
            channels.append(Channel("wire_messages", ints=True))
        rec = Recorder(MetricsSpec(tuple(channels)))

        def body(carry, per_round):
            params, opt_state, rng, sketches, mirror = carry  # mirror: None uncompressed
            idx, tr_m, gs_m, jn, ini, nup, eup, r, do_eval = per_round
            tr_eff = tr_m & nup
            gs_eff = gs_m & nup

            # 1. joiners whose warmup just completed initialise uncoordinated,
            # with the size-only gain √n̂ from their own carried sketches
            # (traced only when the schedule has inits at all — host knowledge)
            def do_init(po):
                p, o = po
                gains = jnp.sqrt(jnp.maximum((n_sketches - 1) / jnp.maximum(
                    sketches.sum(axis=1), jnp.float32(1e-30)), 1.0))
                kr = jax.random.fold_in(k_init, r)
                keys = jax.vmap(lambda i: jax.random.fold_in(kr, i))(jnp.arange(n_nodes))
                p = mask_rows(ini, jax.vmap(init_one)(keys, gains), p)
                o = mask_rows(ini, jax.vmap(optimizer.init)(p), o)
                return p, o

            if has_inits:
                with jax.named_scope("dfl_round"):
                    params, opt_state = jax.lax.cond(
                        ini.any(), do_init, lambda po: po, (params, opt_state)
                    )

            # 2. the round at the full envelope: non-members are frozen, and
            # only live trainers transmit, so only their mirrors advance
            with jax.named_scope("dfl_batch"):
                batch = draw_batch(xs_d, ys_d, idx, item_shape)

            def mix(x, key):
                return plan.select(r).mix(
                    x, plan.round_key(key, r), active=tr_eff, edge_live=eup
                )

            params, opt_state, rng, mirror, losses, key = dfl_round(
                loss_fn, optimizer, mix, params, opt_state, rng, batch,
                failures=plan.failures, compression=comp, residual=mirror, live=tr_eff,
            )

            # 3. sketch transport: arrivals redraw, the gossip-active population
            # min-exchanges over the same per-round failure draws as the mix
            with jax.named_scope("dfl_round"):
                fresh = jax.random.exponential(
                    jax.random.fold_in(k_fresh, r), (n_nodes, n_sketches)
                )
                sketches = jnp.where(jn[:, None], fresh, sketches)
                sketches = plan.select(r).spread_min(
                    sketches, plan.round_key(key, r), active=gs_eff, edge_live=eup
                )

            # 4. metrics over the live training population
            with jax.named_scope("dfl_round"):
                n_act = tr_eff.sum().astype(jnp.float32)
                safe = jnp.maximum(n_act, 1.0)
                values = {
                    "train_loss": ((losses * tr_eff).sum() / safe).astype(jnp.float32),
                    "n_active": n_act,
                }
            if wire_fn is not None:
                with jax.named_scope("dfl_wire"):
                    values["wire_messages"] = wire_fn(key, r, active=tr_eff, edge_live=eup)

            def gated_metrics(p):
                with jax.named_scope("dfl_eval"):
                    per_node = eval_fn(p, eval_d)
                with jax.named_scope("dfl_round"):
                    loss = ((per_node * tr_eff).sum() / safe).astype(jnp.float32)
                return {"test_loss": loss}

            out = rec.step(values, gate=do_eval, gated_fn=gated_metrics, operand=params)
            return (params, opt_state, rng, sketches, mirror), out

        def chunk_inner(carry, sched_chunk, mask_chunk):
            count("dfl.chunk_traces")

            def step(c, inp):
                sc, do_eval = inp
                return body(c, (*sc, do_eval))

            return jax.lax.scan(step, carry, (sched_chunk, mask_chunk))

        chunk_fn = jax.jit(chunk_inner)
        sched_tuple = (
            jnp.asarray(sched_np),
            jnp.asarray(membership.active),
            jnp.asarray(membership.gossip),
            jnp.asarray(membership.joins),
            jnp.asarray(membership.inits),
            jnp.asarray(node_up),
            jnp.asarray(edge_up),
            jnp.arange(n_rounds, dtype=jnp.int32),
        )
        state = seed_residual(state, comp)
        carry = (
            state.params, state.opt_state, state.rng, sketches0,
            state.residual if comp is not None else None,
        )
        meta_id = {
            "kind": "elastic", "n_rounds": n_rounds, "eval_every": eval_every,
            "chunk_size": cfg.chunk_size, "n_sketches": n_sketches,
            "compressed": comp is not None,
        }
        row_bytes = param_row_bytes(
            state.params, codec_bytes=comp.leaf_row_bytes if comp is not None else None
        )
        hook = None
        if on_chunk is not None:
            def hook(ci, r0, r1, out):
                del ci
                with span("dfl.chunk.fetch"):
                    h = rec.assemble(mask_np[r0:r1], [np.asarray(c) for c in out])
                    h["round"] = [r + r0 for r in h["round"]]
                    h = _finish_wire(h, None, row_bytes)
                on_chunk(r0, r1, h)
        skip, head_outs = 0, ()
        if resume_from is not None:
            resumed = _load_resume(resume_from, meta_id)
            if resumed is not None:
                payload, skip = resumed
                carry = _restore_carry(carry, payload)
                head_outs = [tuple(np.asarray(c) for c in o) for o in payload["outs"]]
        carry, cols = _drive_chunks(
            chunk_fn, carry, sched_tuple, mask_np, cfg,
            skip=skip, head_outs=head_outs, checkpoint=checkpoint, ckpt_meta=meta_id,
            on_chunk=hook,
        )
        params, opt_state, rng, sketches, mirror = carry
        hist = _finish_wire(rec.assemble(mask_np, cols), None, row_bytes)
        final = DFLState(
            params=params, opt_state=opt_state,
            round=state.round + jnp.int32(n_rounds), rng=rng,
            residual=mirror,
        )
        n_hat = (n_sketches - 1) / np.maximum(np.asarray(sketches).sum(axis=1), 1e-30)
    return final, hist, {"n_hat": n_hat}


def run_warmup_trajectory(
    key: jax.Array,
    round_fn: Callable[[DFLState, Any], tuple[DFLState, dict]],
    xs: np.ndarray,
    ys: np.ndarray,
    schedule: np.ndarray,
    *,
    n_nodes: int,
    init_one: Callable[[jax.Array, jax.Array], PyTree],
    optimizer,
    estimate_gains: Callable[[jax.Array], jax.Array],
    n_rounds: int,
    eval_every: int = 0,
    eval_fn=None,
    eval_batch=None,
    track_sigmas: bool = False,
    chunk_size: int = 0,
    b_local: int | None = None,
) -> tuple[DFLState, dict[str, list], np.ndarray]:
    """Fused **estimate → per-node gain → init → train** trajectory (§4.4).

    The uncoordinated-init warmup phase: ``estimate_gains`` (a pure-jax
    ``key → (n,) gains`` function, e.g. ``repro.gossip.make_gain_estimator``)
    runs the gossip protocols over the CommPlan backends, ``init_fl_state``
    draws every node's parameters with its own gain, and the first training
    chunk scans on — all inside ONE jitted program, so there is no host
    round-trip between the estimation and training phases and the
    estimation traffic shares the device residency of the round loop.
    Remaining chunks run through the same chunk program ``run_trajectory``
    uses.

    Key discipline: ``key`` splits once into (estimation key, init key);
    running ``estimate_gains`` + ``init_fl_state(gains=...)`` +
    ``run_trajectory`` by hand with the same split reproduces this function
    (property-tested in tests/test_gossip_engine.py).

    Returns ``(final_state, history, gains)`` with ``gains`` the realised
    (n,) per-node vector, for inspection/logging.
    """
    cfg = TrajectoryConfig(n_rounds, eval_every, track_sigmas, chunk_size)
    sched_d = jnp.asarray(_as_round_schedule(schedule, n_rounds, b_local))
    data = _device_data(xs, ys, eval_batch)
    chunk_fn, _, chunk_raw, rec = _build_chunk_fn(round_fn, n_nodes, eval_fn, track_sigmas)

    comp = getattr(round_fn, "compression", None)

    @jax.jit
    def warmup_chunk(k, sched_c, mask_c, data):
        k_est, k_init = jax.random.split(k)
        gains = estimate_gains(k_est)
        state = init_fl_state(k_init, n_nodes, init_one, optimizer, gains=gains)
        state = seed_residual(state, comp)  # static scan-carry structure
        state, out = chunk_raw(state, sched_c, mask_c, data)
        return state, out, gains

    mask_np = cfg.eval_mask()
    r0, r1 = cfg.chunks()[0]
    state, out, gains = warmup_chunk(
        key, jax.lax.slice_in_dim(sched_d, r0, r1, axis=0), jnp.asarray(mask_np[r0:r1]),
        data,
    )
    # later chunks may donate `state` — it was created inside warmup_chunk,
    # so no caller-owned buffer is ever invalidated (donate=False: no copy)
    state, cols = _drive_chunks(
        chunk_fn, state, sched_d, mask_np, cfg, skip=1, head_outs=[out],
        operands=(data,),
    )
    hist = rec.assemble(mask_np, cols)
    return state, hist, np.asarray(gains)


def run_warmup_sweep(
    keys: Sequence[jax.Array] | jax.Array,
    round_fn: Callable[[DFLState, Any], tuple[DFLState, dict]],
    xs: np.ndarray,
    ys: np.ndarray,
    schedule: np.ndarray,
    *,
    n_nodes: int,
    init_one: Callable[[jax.Array, jax.Array], PyTree],
    optimizer,
    estimate_gains: Callable[..., jax.Array],
    budgets: Sequence[int] | np.ndarray | None = None,
    n_rounds: int,
    eval_every: int = 0,
    eval_fn=None,
    eval_batch=None,
    track_sigmas: bool = False,
    chunk_size: int = 0,
    schedule_per_run: bool = False,
    b_local: int | None = None,
) -> tuple[DFLState, list[dict[str, list]], np.ndarray]:
    """Vmapped fused warmups: a (budget × seed) grid of **estimate → per-node
    gain → init → train** trajectories as one program (ROADMAP item).

    ``keys`` is one PRNG key per run (the per-run analogue of
    ``run_warmup_trajectory``'s ``key``); ``budgets``, when given, is one
    gossip budget per run, forwarded as ``estimate_gains(key, budget)`` —
    build the estimator at the grid's *max* budget and let it mask the tail
    rounds (``make_gain_estimator``'s ``budget`` argument), so every run
    shares one static program shape.  The masking keys its phase boundary
    off the *live* budget, so a budget-b cell consumes exactly the failure
    draws a standalone budget-b estimator would — failures included.
    Without ``budgets`` the estimator is called as ``estimate_gains(key)``.

    Per-run semantics match ``run_warmup_trajectory`` run for run (same key
    split, same phases) up to vmap's usual fp-reassociation slack; dataset,
    topology and — unless ``schedule_per_run`` — batch order are shared
    across the sweep like ``run_sweep``.

    Returns ``(stacked_states, histories, gains)`` with ``gains`` the
    realised (n_runs, n_nodes) per-node vectors.
    """
    keys = jnp.stack([jnp.asarray(k) for k in keys]) if isinstance(keys, (list, tuple)) else jnp.asarray(keys)
    n_runs = int(keys.shape[0])
    cfg = TrajectoryConfig(n_rounds, eval_every, track_sigmas, chunk_size)
    if schedule_per_run:
        sched = np.stack(
            [_as_round_schedule(s, n_rounds, b_local) for s in np.asarray(schedule)]
        )
    else:
        sched = _as_round_schedule(schedule, n_rounds, b_local)
    sched_d = jnp.asarray(sched)
    data = _device_data(xs, ys, eval_batch)
    chunk_fn, _, chunk_inner, rec = _build_chunk_fn(
        round_fn, n_nodes, eval_fn, track_sigmas,
        sweep=True, schedule_mapped=schedule_per_run,
    )
    has_budget = budgets is not None
    if has_budget and len(np.asarray(budgets)) != n_runs:
        raise ValueError(
            f"budgets has {len(np.asarray(budgets))} entries for {n_runs} keys"
        )
    b_arr = jnp.asarray(np.asarray(budgets if has_budget else np.zeros(n_runs)), jnp.int32)

    comp = getattr(round_fn, "compression", None)

    def one(k, b, sched_c, mask_c, data):
        k_est, k_init = jax.random.split(k)
        gains = estimate_gains(k_est, b) if has_budget else estimate_gains(k_est)
        state = init_fl_state(k_init, n_nodes, init_one, optimizer, gains=gains)
        state = seed_residual(state, comp)  # static scan-carry structure
        state, out = chunk_inner(state, sched_c, mask_c, data)
        return state, out, gains

    warmup_chunk = jax.jit(
        jax.vmap(one, in_axes=(0, 0, 0 if schedule_per_run else None, None, None))
    )
    mask_np = cfg.eval_mask()
    axis = 1 if schedule_per_run else 0
    r0, r1 = cfg.chunks()[0]
    states, out, gains = warmup_chunk(
        keys,
        b_arr,
        jax.lax.slice_in_dim(sched_d, r0, r1, axis=axis),
        jnp.asarray(mask_np[r0:r1]),
        data,
    )
    states, cols = _drive_chunks(
        chunk_fn, states, sched_d, mask_np, cfg,
        round_axis=axis, skip=1, head_outs=[out], operands=(data,),
    )
    hists = [rec.assemble(mask_np, [c[i] for c in cols]) for i in range(n_runs)]
    return states, hists, np.asarray(gains)


def run_sweep(
    states: DFLState | Sequence[DFLState],
    round_fn: Callable[[DFLState, Any], tuple[DFLState, dict]],
    xs: np.ndarray,
    ys: np.ndarray,
    schedule: np.ndarray,
    *,
    n_rounds: int,
    eval_every: int = 0,
    eval_fn=None,
    eval_batch=None,
    track_sigmas: bool = False,
    chunk_size: int = 0,
    schedule_per_run: bool = False,
    b_local: int | None = None,
) -> tuple[DFLState, list[dict[str, list]]]:
    """Vmapped sweep: many trajectories (seeds, gains, ...) in one program.

    ``states`` is a list of per-run DFLStates (or an already-stacked one with
    a leading sweep axis).  Dataset and topology are shared across the sweep;
    pass ``schedule_per_run=True`` with a leading run axis on ``schedule`` to
    give each run its own batch order.  Returns the stacked final state and
    one history dict per run.
    """
    if isinstance(states, (list, tuple)):
        states = stack_states(states)
    states = seed_residual(states, getattr(round_fn, "compression", None))
    n_runs = int(jax.tree_util.tree_leaves(states)[0].shape[0])
    cfg = TrajectoryConfig(n_rounds, eval_every, track_sigmas, chunk_size)
    if schedule_per_run:
        sched = np.stack(
            [_as_round_schedule(s, n_rounds, b_local) for s in np.asarray(schedule)]
        )
    else:
        sched = _as_round_schedule(schedule, n_rounds, b_local)
    sched_d = jnp.asarray(sched)
    chunk_fn, donate, _, rec = _build_chunk_fn(
        round_fn, xs.shape[0], eval_fn, track_sigmas,
        sweep=True, schedule_mapped=schedule_per_run,
    )
    state, cols = _drive_chunks(
        chunk_fn, states, sched_d, cfg.eval_mask(), cfg,
        round_axis=1 if schedule_per_run else 0, donate=donate,
        operands=(_device_data(xs, ys, eval_batch),),
    )
    mask = cfg.eval_mask()
    hists = [rec.assemble(mask, [c[i] for c in cols]) for i in range(n_runs)]
    return state, hists
