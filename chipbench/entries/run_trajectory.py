"""One chip: the fused executor ``repro.fed.run_trajectory``.

Set-up builds the round function once (``make_round_fn`` over
``compile_plan(graph, failures=FailureModel(link_p))``) and draws the
initial state.  Its first call runs the ``check_rounds`` rounds that the
reference follows, with the metrics recorded every round, and returns the
norm of each parameter leaf's change over them.  A warm-up call of
``warmup_chunks`` chunks follows.  Each window is one further call of whole
chunks that continues the same trajectory; its clock starts at the first
chunk's ``on_chunk`` callback, so the call's one-time re-trace and
compile-cache load fall before it, and stops when the call returns.
Node-rounds count only chunks in the window.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from chipbench import program
from chipbench.entries import Window
from chipbench.reference import leaf_norms


class Entry:
    def __init__(self, cfg: dict, tr: dict, seed: int, inputs: program.Inputs):
        from repro import fed

        self.fed, self.cfg, self.tr, self.inputs = fed, cfg, tr, inputs
        self.system = program.build(cfg, tr, inputs.adj)
        self.round_fn = fed.make_round_fn(
            self.system.loss_fn, self.system.optimizer, self.system.plan
        )
        self.eval_fn = fed.make_eval_fn(self.system.loss_fn)
        self.state = program.initial_state(self.system, cfg, seed)
        dev = jax.devices()[0]
        self.xs, self.ys = jax.device_put(inputs.xs, dev), jax.device_put(inputs.ys, dev)
        self.test = jax.device_put(inputs.test, dev)
        self.chunk = tr["chunk_rounds"]
        self.rounds_done = 0
        self.chunk_seconds = None

    def devices(self):
        return jax.devices()[:1]

    def _call(self, n_rounds: int, eval_every: int, on_chunk):
        b = self.tr["local_batches"]
        r0 = self.rounds_done
        sched = self.inputs.rounds_schedule(r0, r0 + n_rounds, b)
        self.state, hist = self.fed.run_trajectory(
            self.state, self.round_fn, self.xs, self.ys, sched,
            n_rounds=n_rounds, eval_every=eval_every, eval_fn=self.eval_fn,
            eval_batch=self.test, track_sigmas=self.cfg["track_sigmas"],
            chunk_size=self.chunk, b_local=b, on_chunk=on_chunk,
        )
        jax.block_until_ready(self.state.params)
        self.rounds_done += n_rounds
        return hist

    def check(self) -> dict:
        """The first call: the rounds the reference follows, metrics every
        round, and per leaf the norm of the parameters' change (``change``)."""
        before = self.state.params  # the executor copies the state it donates
        hist = self._call(self.tr["check_rounds"], 1, None)
        hist["change"] = leaf_norms(jax.tree_util.tree_map(jnp.subtract, self.state.params, before))
        return hist

    def warmup(self) -> None:
        """A call of ``warmup_chunks`` chunks: every program of the window,
        and the time of one chunk, the shortest between two callbacks, so
        that a host stall does not shorten the window."""
        stamps = []
        self._call(
            self.tr["warmup_chunks"] * self.chunk, self.tr["eval_every"],
            lambda *_: stamps.append(time.perf_counter()),
        )
        self.chunk_seconds = min(b - a for a, b in zip(stamps, stamps[1:]))

    def window_rounds(self, seconds: float) -> int:
        """Whole chunks for about ``seconds`` after the first, at least 3."""
        k = max(3, round(seconds / self.chunk_seconds))
        return (k + 1) * self.chunk

    def trace_rounds(self) -> int:
        """The traced window: 3 chunks after the first."""
        return 4 * self.chunk

    def prepare(self, n_rounds: int) -> None:
        """Compile what the window's call runs eagerly between chunks: the
        slices of its batch schedule and eval mask at each chunk offset."""
        b, n, bs = self.tr["local_batches"], self.system.n, self.tr["batch_size"]
        sched = jnp.zeros((n_rounds, n, b, bs), jnp.int32)
        mask = jnp.zeros((n_rounds,), bool)
        for r0 in range(0, n_rounds, self.chunk):
            r1 = min(r0 + self.chunk, n_rounds)
            jax.block_until_ready((jax.lax.slice_in_dim(sched, r0, r1, axis=0), mask[r0:r1]))

    def window(self, n_rounds: int, tracer) -> Window:
        stamps = []

        def on_chunk(r0, r1, h):
            if not stamps:
                tracer.start()
            stamps.append((time.perf_counter(), time.time()))
            tracer.mark()

        self._call(n_rounds, self.tr["eval_every"], on_chunk)
        t_end, w_end = time.perf_counter(), time.time()
        tracer.stop()
        rounds = n_rounds - self.chunk
        every = self.tr["eval_every"]
        evals = sum(1 for r in range(self.chunk, n_rounds) if r % every == 0 or r == n_rounds - 1)
        return Window(
            rounds=rounds,
            node_rounds=rounds * self.system.n,
            wall_s=t_end - stamps[0][0],
            t0=stamps[0][1],
            t1=w_end,
            calls=1,
            notes={"eval_rounds": evals},
        )

    def free(self) -> None:
        del self.state, self.xs, self.ys, self.test
