"""Plain reference of a DFL run, independent of the program under test.

It imports nothing of the program and takes nothing the program made.  From
the run's seed, the cell's graph and the harness's data it recomputes the
first rounds of the trajectory the program drives:

* the per-node init gains of the gossip estimator (power iteration, then
  push-sum of ``[x², 1_leader]``), with dense float64 send operators on the
  host, or gain 1 where the configuration uses the unscaled He init;
* He-normal weights per node, scaled by the node's gain, zero biases;
* per round: 8 local SGD steps with momentum per node, the DecAvg mix over
  the round's surviving links (dense, renormalised over each node's live
  neighbourhood), the optimizer's re-initialisation;
* what the program records per round: mean train loss, mean test loss,
  σ_ap and σ_an of the parameters after the round, and delivered messages;
* per parameter leaf (all nodes' stack of it): the norm of its change over
  the rounds run, and the norm of its gradient at the first local step,
  which decides the leaves whose change is compared (``chipbench.check``).

It follows the seeding the system documents, which is what makes one seed
one trajectory: the run key splits into (estimation key, init key); the
init key splits into one key per node plus the round-key stream, and each
node's key yields one sub-key per layer in order; round ``r`` splits
``(stream, k_mix)`` off the stream; a failure draw splits its key into
(link key, node key) and keeps undirected edge ``e`` (row-major, i < j)
iff ``uniform(link key)[e] < link_p``; the estimator's round ``r`` draws
with ``fold_in(first half of split(estimation key), r)``.

Precision: ``"highest"`` computes every contraction at full float32; the
control (``local_dtype=bfloat16``, ``mix_precision="high"``) is the same
computation one step below what the configuration states.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, traffic

_EPS = 1e-30
_UNREACHED = 1e-20
FAULTS = ("none", "unchanged", "half_batch", "no_mix", "one_node_altered")


def seed_key(seed: int) -> jax.Array:
    """The run key of ``--seed`` (all of its bits, not only the low 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def run_keys(seed: int) -> tuple[jax.Array, jax.Array]:
    """(estimation key, init key) of a run."""
    k_est, k_init = jax.random.split(seed_key(seed))
    return k_est, k_init


@partial(jax.jit, static_argnums=(1,))
def _edge_keep(keys: jax.Array, n_edges: int, link_p: float) -> jax.Array:
    def one(k):
        k_link, _ = jax.random.split(k)
        return jax.random.uniform(k_link, (max(n_edges, 1),)) < link_p

    return jax.vmap(one)(keys)


def edge_keep(keys: jax.Array, n_edges: int, link_p: float) -> np.ndarray:
    """(rounds, n_edges) bool survival of each undirected edge per key."""
    if link_p >= 1.0:
        return np.ones((keys.shape[0], max(n_edges, 1)), bool)
    return np.asarray(_edge_keep(keys, n_edges, link_p))


def receive_matrix(adj: np.ndarray, edges: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """Row-stochastic float64 receive operator of one round: each node
    averages itself and its neighbours over the links that survived."""
    a = adj.astype(np.float64).copy()
    dead = ~keep[: len(edges)]
    a[edges[dead, 0], edges[dead, 1]] = 0.0
    a[edges[dead, 1], edges[dead, 0]] = 0.0
    b = a + np.eye(a.shape[0])
    return b / b.sum(axis=1, keepdims=True)


def gossip_gains(adj: np.ndarray, link_p: float, k_est: jax.Array, rounds: int) -> np.ndarray:
    """Per-node gain ``1/‖v̂‖`` of ``rounds`` power-iteration rounds and
    ``rounds`` push-sum rounds from leader 0; 1.0 where the leader's mass
    never arrived."""
    edges = traffic.edge_list(adj)
    k_gossip, _ = jax.random.split(k_est)
    keys = jax.vmap(lambda r: jax.random.fold_in(k_gossip, r))(jnp.arange(2 * rounds))
    keep = edge_keep(keys, len(edges), link_p)
    n = adj.shape[0]
    x = np.ones(n)
    for r in range(rounds):
        x = receive_matrix(adj, edges, keep[r]).T @ x
    pay = np.stack([x * x, np.eye(n)[0], np.ones(n)], axis=1)
    for r in range(rounds, 2 * rounds):
        pay = receive_matrix(adj, edges, keep[r]).T @ pay
    m2, z = pay[:, 0] / pay[:, 2], pay[:, 1] / pay[:, 2]
    vnorm = np.sqrt(np.maximum(m2 * np.maximum(z, _EPS), 0.0))
    return np.where(z > _UNREACHED, 1.0 / np.maximum(vnorm, _EPS), 1.0)


def init_params(ref, cfg: dict, k_init: jax.Array, gains: np.ndarray):
    """(params, round-key stream): He-normal × gain weights, zero biases."""
    n = len(gains)
    lay = ref.layout(cfg)

    def one(key, g):
        p = {}
        for name, shape, fan_in in lay:
            key, sub = jax.random.split(key)
            std = math.sqrt(2.0 / fan_in) * g
            p[name] = {
                "w": std * jax.random.normal(sub, shape, jnp.float32),
                "b": jnp.zeros((shape[-1],), jnp.float32),
            }
        return p

    keys = jax.random.split(k_init, n + 1)
    params = jax.jit(jax.vmap(one))(keys[:n], jnp.asarray(gains, jnp.float32))
    return params, keys[n]


def _xent(logits, y):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0].mean()


@dataclasses.dataclass(frozen=True)
class Setting:
    """How the reference computes: the configuration's own precision, one
    step below it (the control), or with one fault planted."""

    local_dtype: str = "float32"
    mix_precision: str = "highest"
    fault: str = "none"
    local_precision: str = ""  # matmul precision of the local steps; "" = by dtype


def _local_phase(forward, lr, mom, setting: Setting, params, xs, ys, idx):
    """One round's local steps of every node.  idx: (n, b, bs)."""
    dt = jnp.dtype(setting.local_dtype)
    if setting.fault == "half_batch":
        idx = idx[..., : idx.shape[-1] // 2]

    def node(p, xb, yb):
        p = jax.tree_util.tree_map(lambda a: a.astype(dt), p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)

        def step(carry, batch):
            p, v = carry
            x, y = batch
            loss, g = jax.value_and_grad(lambda q: _xent(forward(q, x.astype(dt)), y))(p)
            v = jax.tree_util.tree_map(lambda m, gi: mom * m + gi, v, g)
            p = jax.tree_util.tree_map(lambda a, m: a + (-lr * m).astype(dt), p, v)
            return (p, v), loss

        (p, _), losses = jax.lax.scan(step, (p, v), (xb, yb))
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p), losses.mean()

    rows = jnp.arange(idx.shape[0])[:, None, None]
    return jax.vmap(node)(params, xs[rows, idx], ys[rows, idx])


def leaf_norms(tree) -> dict[str, float]:
    """``{leaf path: Frobenius norm of the leaf}``, every node's stack."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(jnp.linalg.norm(v.ravel())) for k, v in flat}


def _first_grads(forward, params, xs, ys, idx):
    """Every node's gradient at its first local batch.  idx: (n, b, bs)."""
    rows = jnp.arange(idx.shape[0])[:, None]
    x, y = xs[rows, idx[:, 0]], ys[rows, idx[:, 0]]
    return jax.vmap(jax.grad(lambda q, xb, yb: _xent(forward(q, xb), yb)))(params, x, y)


def _mix(params, m, precision):
    prec = {"highest": jax.lax.Precision.HIGHEST, "high": jax.lax.Precision.HIGH}[precision]

    def leaf(a):
        return jnp.dot(m, a.reshape(a.shape[0], -1), precision=prec).reshape(a.shape)

    return jax.tree_util.tree_map(leaf, params)


def _sigmas(params):
    leaves = [a.reshape(a.shape[0], -1) for a in jax.tree_util.tree_leaves(params)]
    d = sum(a.shape[1] for a in leaves)
    mean_n = sum(a.sum(axis=1) for a in leaves) / d
    var_n = sum(((a - mean_n[:, None]) ** 2).sum(axis=1) for a in leaves) / d
    an = sum(jnp.std(a, axis=0).sum() for a in leaves) / d
    return jnp.sqrt(var_n).mean(), an


def run(
    cfg: dict,
    tr: dict,
    seed: int,
    adj: np.ndarray,
    xs: np.ndarray,
    ys: np.ndarray,
    test: tuple[np.ndarray, np.ndarray],
    schedule: np.ndarray,
    rounds: int,
    setting: Setting = Setting(),
) -> dict[str, list[float]]:
    """The first ``rounds`` rounds; per-round ``train_loss``, ``test_loss``,
    ``sigma_ap``, ``sigma_an`` and ``wire_messages``, and per leaf the norm
    of its change over the rounds (``change``) and of its first gradient
    (``grad0``, at full precision).  ``schedule`` is the
    harness's (rounds·b, n, bs) batch order."""
    ref = counts.reference_module(cfg)
    opt = cfg["optimizer"]
    n = adj.shape[0]
    edges = traffic.edge_list(adj)
    k_est, k_init = run_keys(seed)
    if cfg["init"]["gains"] == "gossip_vnorm":
        gains = gossip_gains(adj, tr["link_p"], k_est, cfg["init"]["estimate_rounds"])
    else:
        gains = np.ones(n)
    params, stream = init_params(ref, cfg, k_init, gains)

    b = tr["local_batches"]
    local = jax.jit(partial(_local_phase, ref.forward, opt["learning_rate"], opt["momentum"], setting))
    mix = jax.jit(partial(_mix, precision=setting.mix_precision))
    test_loss = jax.jit(
        lambda p, x, y: jax.lax.map(lambda q: _xent(ref.forward(q, x), y), p).mean()
    )
    sigmas = jax.jit(_sigmas)
    xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)
    tx, ty = jnp.asarray(test[0]), jnp.asarray(test[1])
    out = {k: [] for k in ("train_loss", "test_loss", "sigma_ap", "sigma_an", "wire_messages")}
    params0 = params
    with jax.default_matmul_precision("highest"):
        idx0 = jnp.asarray(schedule[:b].transpose(1, 0, 2))
        out["grad0"] = leaf_norms(jax.jit(partial(_first_grads, ref.forward))(params0, xs_d, ys_d, idx0))
    local_prec = setting.local_precision or ("highest" if setting.local_dtype == "float32" else "default")
    with jax.default_matmul_precision(local_prec):
        for r in range(rounds):
            stream, k_mix = jax.random.split(stream)
            keep = edge_keep(k_mix[None], len(edges), tr["link_p"])[0]
            idx = jnp.asarray(schedule[r * b : (r + 1) * b].transpose(1, 0, 2))
            new, node_loss = local(params, xs_d, ys_d, idx)
            if setting.fault == "one_node_altered":
                new = jax.tree_util.tree_map(lambda a: a.at[0].multiply(2.0), new)
            if setting.fault != "no_mix":
                m = jnp.asarray(receive_matrix(adj, edges, keep), jnp.float32)
                new = mix(new, m)
            if setting.fault != "unchanged":
                params = new
            ap, an = sigmas(params)
            out["train_loss"].append(float(node_loss.mean()))
            out["test_loss"].append(float(test_loss(params, tx, ty)))
            out["sigma_ap"].append(float(ap))
            out["sigma_an"].append(float(an))
            out["wire_messages"].append(2 * int(keep[: len(edges)].sum()))
    out["change"] = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, params0))
    return out
