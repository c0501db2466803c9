"""Live serving of decentralised models: prefill→insert→decode engine plus
an interleaved train+serve event executor (DESIGN.md §19).

Decentralised training's end product is an *ensemble*: every node holds its
own parameters, equal only up to the consensus noise floor (§4.2).  This
module serves that ensemble two ways:

* **offline** — ``consensus_params`` collapses the ensemble into one
  deployable artifact; ``generate`` runs batched prefill (one full-sequence
  pass that also fills the decode cache — ``models.transformer.
  prefill_cache``) followed by a scanned decode loop, the whole thing one
  jitted program per (cfg, n_new, cache_len, temperature) signature;
* **live** — ``run_serve_trajectory`` merges an open-loop Poisson
  ``QueryStream`` into the gossip ``EventStream``'s sorted envelope and
  advances both through one ``lax.scan``: gossip events replay the *exact*
  training step of ``run_event_trajectory`` (shared ``_make_event_step``,
  failure keys folded on the gossip ordinal — so training is bit-identical
  to a serve-free run), and query events route to a node (``fed.router``),
  read its current parameters, and settle a queueing latency model on the
  same virtual clocks, with per-bin ``serve_latency`` / ``serve_staleness``
  channels riding the scan carry.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.commplan import CommPlan, compile_plan
from repro.core.decavg import MIX_PRECISION
from repro.core.topology import EventStream, Graph
from repro.models import transformer as tf
from repro.obs.health import staleness_histogram
from repro.obs.spec import BinChannel, BinSpec
from repro.obs.wirecost import param_row_bytes

from .executor import _STALE_BUCKETS, _as_round_schedule, _make_event_step
from .router import QueryStream, Router
from .trainer import DFLState

PyTree = Any

__all__ = [
    "consensus_params",
    "prefill",
    "decode_one",
    "generate",
    "generate_tokenwise",
    "ServeEngine",
    "run_serve_trajectory",
    "serve_summary",
]


def consensus_params(node_params: PyTree, weights: jax.Array | None = None) -> PyTree:
    """Average the node ensemble into one deployable parameter set."""

    def avg(leaf):
        lf = leaf.astype(jnp.float32)
        if weights is None:
            out = lf.mean(axis=0)
        else:
            w = weights / weights.sum()
            out = jnp.tensordot(w, lf, axes=1, precision=MIX_PRECISION)
        return out.astype(leaf.dtype)

    return jax.tree_util.tree_map(avg, node_params)


def prefill(
    params: PyTree, cfg: ArchConfig, tokens: jax.Array, frontend_embeds: jax.Array | None = None
) -> jax.Array:
    """Full-sequence forward → next-token logits for the LAST position only
    ((B, V)); full logits never materialise (vocab can be 262k)."""
    hidden, _ = tf.forward(params, cfg, tokens, frontend_embeds, remat=False)
    return tf.hidden_to_logits(params, cfg, hidden[..., -1:, :])[..., 0, :]


def decode_one(
    params: PyTree, cfg: ArchConfig, cache: PyTree, tokens: jax.Array, pos: jax.Array
) -> tuple[jax.Array, PyTree]:
    """ONE new token against a cache of ``cache_len`` — the decode_32k /
    long_500k step. tokens (B, 1), pos scalar absolute position."""
    return tf.decode_step(params, cfg, cache, tokens, pos)


# ----------------------------------------------------------------- generate
@partial(jax.jit, static_argnames=("cfg", "n_new", "cache_len", "temperature"))
def _generate_impl(
    params: PyTree,
    cfg: ArchConfig,
    prompt: jax.Array,
    n_new: int,
    cache_len: int,
    temperature: float,
    rng: jax.Array,
) -> jax.Array:
    """Batched prefill → cache insert → scanned decode, one jitted program.

    The prompt is consumed by ONE full-sequence pass whose last-position
    logits are exactly what the old token-by-token loop saw after feeding
    ``prompt[:, -1:]`` at position S-1, and whose cache insert leaves the
    slots token-wise decode would have written — so sampling continues the
    identical key chain (split once per sampled token, temperature > 0).
    """
    s = prompt.shape[-1]

    def sample(logits, key):
        if temperature > 0:
            return jax.random.categorical(key, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    logits0, cache = tf.prefill_cache(params, cfg, prompt, cache_len)
    rng, k0 = jax.random.split(rng)
    tok0 = sample(logits0, k0).astype(prompt.dtype)

    def step(carry, i):
        cache, tok, rng = carry
        logits, cache = tf.decode_step(params, cfg, cache, tok[..., None], s + i)
        rng, k = jax.random.split(rng)
        nxt = sample(logits[..., -1, :], k).astype(tok.dtype)
        return (cache, nxt, rng), nxt

    _, toks = jax.lax.scan(step, (cache, tok0, rng), jnp.arange(n_new - 1, dtype=jnp.int32))
    return jnp.concatenate([tok0[..., None], jnp.moveaxis(toks, 0, -1)], axis=-1)


def generate(
    params: PyTree,
    cfg: ArchConfig,
    prompt: jax.Array,
    n_new: int,
    cache_len: int,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Greedy/temperature sampling driver: one batched prefill + scanned
    decode, jitted once per (cfg, n_new, cache_len, temperature).

    ``generate_tokenwise`` is the old per-token reference path; the two are
    parity-tested (``tests/test_serve.py``)."""
    key = rng if rng is not None else jax.random.PRNGKey(0)
    return _generate_impl(params, cfg, prompt, int(n_new), int(cache_len), float(temperature), key)


def generate_tokenwise(
    params: PyTree,
    cfg: ArchConfig,
    prompt: jax.Array,
    n_new: int,
    cache_len: int,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Reference decode loop: prompt consumed token-by-token (the seed-era
    ``generate``), kept as the parity baseline for the prefill path."""
    b = prompt.shape[0]
    cache = tf.init_cache(cfg, (b,), cache_len)
    out = []
    step = jax.jit(tf.decode_step, static_argnums=(1,))
    pos = 0
    for t in range(prompt.shape[1] - 1):
        _, cache = step(params, cfg, cache, prompt[:, t : t + 1], jnp.asarray(pos))
        pos += 1
    tok = prompt[:, -1:]
    key = rng if rng is not None else jax.random.PRNGKey(0)
    for _ in range(n_new):
        logits, cache = step(params, cfg, cache, tok, jnp.asarray(pos))
        pos += 1
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits[:, -1] / temperature)[:, None]
        else:
            tok = logits[:, -1].argmax(-1)[:, None]
        out.append(tok)
    return jnp.concatenate(out, axis=1)


class ServeEngine:
    """Batched prefill→insert→decode engine over per-node parameter stacks.

    One jitted program per (cfg, n_new, cache_len, temperature): ``generate``
    serves a batch against ONE parameter set (e.g. the consensus), ``serve``
    answers per-query assignments against a node-stacked ensemble by
    gathering each query's node parameters and vmapping the same program.
    """

    def __init__(self, cfg: ArchConfig, cache_len: int, temperature: float = 0.0):
        self.cfg = cfg
        self.cache_len = int(cache_len)
        self.temperature = float(temperature)

    def generate(self, params: PyTree, prompt: jax.Array, n_new: int, rng=None) -> jax.Array:
        key = rng if rng is not None else jax.random.PRNGKey(0)
        return _generate_impl(
            params, self.cfg, prompt, int(n_new), self.cache_len, self.temperature, key
        )

    def serve(
        self,
        node_params: PyTree,
        assignments: jax.Array,
        prompts: jax.Array,
        n_new: int,
        rng=None,
    ) -> jax.Array:
        """prompts (B, S) answered by the nodes in ``assignments`` (B,)."""
        key = rng if rng is not None else jax.random.PRNGKey(0)
        a = jnp.asarray(assignments, jnp.int32)
        per_q = jax.tree_util.tree_map(lambda l: l[a], node_params)
        keys = jax.random.split(key, prompts.shape[0])

        def one(p, t, k):
            return _generate_impl(
                p, self.cfg, t[None], int(n_new), self.cache_len, self.temperature, k
            )[0]

        return jax.vmap(one)(per_q, prompts, keys)


# ------------------------------------------------------- interleaved serving
def run_serve_trajectory(
    state: DFLState,
    loss_fn,
    optimizer,
    plan: CommPlan | Graph,
    stream: EventStream,
    queries: QueryStream,
    router: Router,
    xs: np.ndarray,
    ys: np.ndarray,
    schedule: np.ndarray,
    *,
    b_local: int,
    n_bins: int = 20,
    eval_fn=None,
    eval_batch=None,
    service_time: float = 0.05,
    hop_latency: float = 0.02,
    serve_fn: Callable[[PyTree, jax.Array], jax.Array] | None = None,
    query_xs: np.ndarray | None = None,
    chunk_events: int = 0,
    on_chunk=None,
) -> tuple[DFLState, dict[str, list], dict[str, np.ndarray], dict[str, np.ndarray]]:
    """Interleaved train+serve: one scan over the merged gossip+query envelope.

    Gossip events replay ``run_event_trajectory``'s step exactly (shared
    ``_make_event_step``; failure keys fold on the *gossip ordinal*, routing
    keys on the *query ordinal* of a split-off key) — so the training
    trajectory is invariant to the query load, and at qps = 0 bit-identical
    to a serve-free run.  Each query event, under ``lax.cond``:

    1. routes to a node ``v = router.route(home, t - clocks, wait, key)``
       — staleness read straight off the training carry's virtual clocks
       (the flight-recorder channel), queue wait off per-node busy-until
       times;
    2. settles the open-loop latency model
       ``latency = (start - t) + service_time + hop_latency · hops(home, v)``
       with ``start = max(t, busy[v])`` and ``busy[v] ← start + service_time``
       (single serving slot per node — serving competes with itself, not
       with training, which rides virtual time);
    3. optionally answers it: ``serve_fn(params_v, query_xs[qidx])`` runs
       the query payload through the routed node's *current* parameters
       inside the scan (scalar answer, recorded per query).

    Returns ``(final_state, hist, serve, aux)``: ``hist`` is the event
    executor's per-bin history plus ``queries`` / ``serve_latency`` /
    ``serve_staleness`` channels; ``serve`` holds per-query arrays (time,
    home, node, latency, staleness, hops, answer) in arrival order; ``aux``
    the per-node clocks / event counts / staleness histogram / busy times.
    """
    plan = compile_plan(plan) if isinstance(plan, Graph) else plan
    if plan.event_uv is None:
        raise ValueError("run_serve_trajectory needs an undirected, statically compiled plan")
    n_nodes = xs.shape[0]
    if plan.n != n_nodes:
        raise ValueError(f"plan has {plan.n} nodes but xs carries {n_nodes}")
    if abs(queries.horizon - stream.horizon) > 1e-6:
        raise ValueError("query stream and event stream must share one horizon")
    s = np.asarray(schedule)
    n_sched_rounds = (s.shape[0] // b_local) if s.ndim == 3 else s.shape[0]
    sched_d = jnp.asarray(_as_round_schedule(s, n_sched_rounds, b_local))
    xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)
    eval_d = None if eval_batch is None else jax.tree_util.tree_map(jnp.asarray, eval_batch)
    qx_d = None if query_xs is None else jnp.asarray(query_xs)

    # ---- host-side merge of the two sorted envelopes ---------------------
    env_g, env_q = stream.envelope, queries.envelope
    times = np.concatenate([np.asarray(stream.times), np.asarray(queries.times)])
    edges = np.concatenate([np.asarray(stream.edges, np.int32), np.full(env_q, -1, np.int32)])
    homes = np.concatenate([np.full(env_g, -1, np.int32), np.asarray(queries.homes, np.int32)])
    gidx = np.concatenate([np.arange(env_g), np.zeros(env_q)]).astype(np.int32)
    qord = np.concatenate([np.zeros(env_g), np.arange(env_q)]).astype(np.int32)
    qidx = np.concatenate([np.zeros(env_g, np.int32), np.asarray(queries.qidx, np.int32)])
    # stable: gossip precedes queries at equal times, and at qps = 0 the
    # merged arrays are exactly the gossip arrays (identity permutation)
    order = np.argsort(times, kind="stable")
    times, edges, homes = times[order], edges[order], homes[order]
    gidx, qord, qidx = gidx[order], qord[order], qidx[order]
    env = env_g + env_q
    has_serve = env_q > 0

    live_g = edges >= 0
    bins_np = np.clip((times / stream.horizon * n_bins).astype(np.int64), 0, n_bins - 1)
    do_eval_np = np.zeros(env, dtype=bool)
    if eval_fn is not None:
        for b in range(n_bins):
            hits = np.nonzero(live_g & (bins_np == b))[0]
            if len(hits):
                do_eval_np[hits[-1]] = True

    rng, base_key = jax.random.split(state.rng)
    event_step = _make_event_step(
        loss_fn,
        optimizer,
        plan,
        sched_d,
        n_sched_rounds,
        xs_d,
        ys_d,
        comp=None,
        base_key=base_key,
    )
    # routing keys live on a split-off key so query draws can never collide
    # with the failure-key folds off base_key itself
    k_route = jax.random.split(base_key)[1]

    bin_spec = BinSpec(
        n_bins,
        (
            BinChannel("loss_sum"),
            BinChannel("cnt"),
            BinChannel("stale_sum"),
            BinChannel("msg_cnt"),
            BinChannel("test_bin", fill=float("nan")),
            BinChannel("stale_hist", width=_STALE_BUCKETS),
            BinChannel("serve_lat_sum"),
            BinChannel("serve_stale_sum"),
            BinChannel("serve_cnt"),
        ),
    )
    horizon = float(stream.horizon)
    hops_f = router.hops
    null_out = (
        jnp.int32(-1),
        jnp.float32(0.0),
        jnp.float32(0.0),
        jnp.float32(0.0),
        jnp.float32(jnp.nan),
    )

    def gossip_case(operand):
        carry, inp = operand
        params, opt_state, counts, clocks, busy, acc = carry
        g, qn, qi, e, u, t, b, do_ev = inp
        params, opt_state, counts, clocks, _, (liv, loss_mean, stale, delivered) = (
            event_step(params, opt_state, counts, clocks, None, g, e, t)
        )
        livf = liv.astype(jnp.float32)
        acc = dict(acc)
        acc["loss_sum"] = acc["loss_sum"].at[b].add(loss_mean * livf)
        acc["stale_sum"] = acc["stale_sum"].at[b].add(stale * livf)
        acc["cnt"] = acc["cnt"].at[b].add(livf)
        acc["msg_cnt"] = acc["msg_cnt"].at[b].add(2.0 * delivered.astype(jnp.float32))
        sb = jnp.clip((stale / horizon * _STALE_BUCKETS).astype(jnp.int32), 0, _STALE_BUCKETS - 1)
        acc["stale_hist"] = acc["stale_hist"].at[sb].add(livf)
        if eval_fn is not None:
            acc["test_bin"] = jax.lax.cond(
                do_ev,
                lambda tb: tb.at[b].set(jnp.mean(eval_fn(params, eval_d)).astype(jnp.float32)),
                lambda tb: tb,
                acc["test_bin"],
            )
        return (params, opt_state, counts, clocks, busy, acc), null_out

    def serve_case(operand):
        carry, inp = operand
        params, opt_state, counts, clocks, busy, acc = carry
        g, qn, qi, e, u, t, b, do_ev = inp
        live = u >= 0
        livf = live.astype(jnp.float32)
        uu = jnp.maximum(u, 0)
        stale_all = t - clocks
        wait_all = jnp.maximum(busy - t, 0.0)
        v = router.route(uu, stale_all, wait_all, jax.random.fold_in(k_route, qn))
        start = jnp.maximum(t, busy[v])
        hops = hops_f[uu, v]
        latency = (start - t) + service_time + hop_latency * hops
        stale_v = t - clocks[v]
        busy = busy.at[v].set(jnp.where(live, start + service_time, busy[v]))
        if serve_fn is not None and qx_d is not None:
            node_p = jax.tree_util.tree_map(lambda l: l[v], params)
            ans = jnp.asarray(serve_fn(node_p, qx_d[qi]), jnp.float32)
        else:
            ans = jnp.float32(jnp.nan)
        acc = dict(acc)
        acc["serve_lat_sum"] = acc["serve_lat_sum"].at[b].add(latency * livf)
        acc["serve_stale_sum"] = acc["serve_stale_sum"].at[b].add(stale_v * livf)
        acc["serve_cnt"] = acc["serve_cnt"].at[b].add(livf)
        out = (
            jnp.where(live, v, -1).astype(jnp.int32),
            latency * livf,
            stale_v * livf,
            hops * livf,
            jnp.where(live, ans, jnp.nan),
        )
        return (params, opt_state, counts, clocks, busy, acc), out

    def body(carry, inp):
        if has_serve:
            u = inp[4]
            return jax.lax.cond(u >= 0, serve_case, gossip_case, (carry, inp))
        return gossip_case((carry, inp))

    @jax.jit
    def drive_chunk(carry, inp):
        return jax.lax.scan(body, carry, inp)

    carry = (
        state.params,
        state.opt_state,
        jnp.zeros(n_nodes, jnp.int32),
        jnp.zeros(n_nodes, jnp.float32),
        jnp.zeros(n_nodes, jnp.float32),
        bin_spec.init(),
    )
    inp_all = (
        jnp.asarray(gidx),
        jnp.asarray(qord),
        jnp.asarray(qidx),
        jnp.asarray(edges),
        jnp.asarray(homes),
        jnp.asarray(times, jnp.float32),
        jnp.asarray(bins_np, jnp.int32),
        jnp.asarray(do_eval_np),
    )
    size = env if chunk_events <= 0 else int(chunk_events)
    bounds = [(i0, min(i0 + size, env)) for i0 in range(0, env, size)]
    ys_chunks = []
    for ci, (i0, i1) in enumerate(bounds):
        carry, ys_c = drive_chunk(carry, tuple(a[i0:i1] for a in inp_all))
        ys_chunks.append(ys_c)
        if on_chunk is not None:
            on_chunk(ci, i0, i1, carry[5])
    params, opt_state, counts, clocks, busy, acc = carry
    ys_all = [np.concatenate([np.asarray(c[j]) for c in ys_chunks]) for j in range(5)]

    cnt_np = np.asarray(acc["cnt"])
    safe = np.maximum(cnt_np, 1.0)
    qcnt_np = np.asarray(acc["serve_cnt"])
    qsafe = np.maximum(qcnt_np, 1.0)
    width = stream.horizon / n_bins
    row_bytes = param_row_bytes(state.params)
    messages = [int(v) for v in np.asarray(acc["msg_cnt"])]
    hist = {
        "bin": list(range(n_bins)),
        "time": [float((b + 1) * width) for b in range(n_bins)],
        "train_loss": [float(v) for v in np.asarray(acc["loss_sum"]) / safe],
        "test_loss": [float(v) for v in np.asarray(acc["test_bin"])],
        "staleness": [float(v) for v in np.asarray(acc["stale_sum"]) / safe],
        "events": [int(v) for v in cnt_np],
        "messages": messages,
        "wire_bytes": [m * row_bytes for m in messages],
        "queries": [int(v) for v in qcnt_np],
        "serve_latency": [float(v) for v in np.asarray(acc["serve_lat_sum"]) / qsafe],
        "serve_staleness": [float(v) for v in np.asarray(acc["serve_stale_sum"]) / qsafe],
    }
    qpos = np.nonzero(homes >= 0)[0]
    serve = {
        "time": times[qpos].astype(np.float64),
        "home": homes[qpos].astype(np.int64),
        "node": ys_all[0][qpos].astype(np.int64),
        "latency": ys_all[1][qpos].astype(np.float64),
        "staleness": ys_all[2][qpos].astype(np.float64),
        "hops": ys_all[3][qpos].astype(np.float64),
        "answer": ys_all[4][qpos].astype(np.float64),
    }
    final = DFLState(
        params=params,
        opt_state=opt_state,
        round=state.round + jnp.int32(stream.n_events),
        rng=rng,
        residual=None,
    )
    aux = {
        "node_clock": np.asarray(clocks),
        "node_events": np.asarray(counts),
        "node_busy": np.asarray(busy),
        "staleness_hist": staleness_histogram(acc["stale_hist"], horizon),
    }
    return final, hist, serve, aux


def serve_summary(serve: dict[str, np.ndarray]) -> dict[str, float]:
    """Headline latency/staleness stats of one ``run_serve_trajectory`` run."""
    lat = np.asarray(serve["latency"], np.float64)
    if lat.size == 0:
        return {
            "served": 0,
            "p50_latency": 0.0,
            "p95_latency": 0.0,
            "mean_latency": 0.0,
            "mean_staleness": 0.0,
            "mean_hops": 0.0,
        }
    return {
        "served": int(lat.size),
        "p50_latency": float(np.percentile(lat, 50)),
        "p95_latency": float(np.percentile(lat, 95)),
        "mean_latency": float(lat.mean()),
        "mean_staleness": float(np.asarray(serve["staleness"]).mean()),
        "mean_hops": float(np.asarray(serve["hops"]).mean()),
    }
