"""The system under test, as the harness builds it from a cell's files.

Everything the harness takes from the program is here: the model's init,
forward pass and loss named in the configuration file, the optimizer, the
communication plan, the gossip gain estimator and ``init_fl_state``.
Inputs (graph, data, batch order, keys) are the harness's own.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable

import jax
import numpy as np

from chipbench import reference, traffic


def resolve(path: str):
    """``"package.module:attr"`` → the attribute."""
    mod, attr = path.split(":")
    return getattr(importlib.import_module(mod), attr)


@dataclasses.dataclass
class Inputs:
    """What a run feeds the program and the reference alike."""

    adj: np.ndarray  # (n, n) adjacency from the traffic's graph_seed
    xs: np.ndarray  # (n, items, H, W, C)
    ys: np.ndarray  # (n, items)
    test: tuple[np.ndarray, np.ndarray]
    schedule: np.ndarray  # (rounds·b, n, bs) batch order

    def rounds_schedule(self, r0: int, r1: int, b: int) -> np.ndarray:
        return self.schedule[r0 * b : r1 * b]


def make_inputs(cfg: dict, tr: dict, seed: int, total_rounds: int) -> Inputs:
    """Graph from ``graph_seed``; data and batch order from ``seed``."""
    adj = traffic.make_graph(tr["graph"])
    n, items = adj.shape[0], tr["items_per_node"]
    ds = cfg["dataset"]
    x, y = traffic.make_images(
        n * items + tr["test_items"], tuple(ds["image_shape"]), ds["n_classes"], seed,
        class_sep=ds["class_sep"], n_prototypes=ds["n_prototypes"],
    )
    xs = x[: n * items].reshape((n, items) + x.shape[1:])
    ys = y[: n * items].reshape(n, items)
    return Inputs(adj, xs, ys, (x[n * items :], y[n * items :]), schedule(tr, n, seed, total_rounds))


def schedule(tr: dict, n: int, seed: int, rounds: int) -> np.ndarray:
    """The batch order of the first ``rounds`` rounds; a longer schedule
    of the same seed starts with the same batches."""
    return traffic.batch_schedule(
        tr["items_per_node"], n, tr["batch_size"], rounds * tr["local_batches"], seed
    )


@dataclasses.dataclass
class System:
    """The program's objects for one cell."""

    graph: Any
    plan: Any
    loss_fn: Callable
    optimizer: Any
    init_one: Callable  # (key) or (key, gain) → one node's params
    n: int


def build(cfg: dict, tr: dict, adj: np.ndarray) -> System:
    from repro.core.commplan import FailureModel, compile_plan
    from repro.core.initialisation import InitConfig
    from repro.core.topology import Graph
    from repro.optim import sgd

    prog = cfg["program"]
    init_model, forward, loss = (resolve(prog[k]) for k in ("init", "forward", "loss"))
    kwargs = {k: tuple(v) if isinstance(v, list) else v for k, v in prog["init_kwargs"].items()}
    dist = cfg["init"]["distribution"]

    def init_one(key, gain=1.0):
        return init_model(InitConfig(dist, gain), key, **kwargs)

    opt = cfg["optimizer"]
    if opt["kind"] != "sgd":
        raise ValueError(f"unknown optimizer {opt['kind']!r}")
    graph = Graph(adj, name=tr["graph"]["family"])
    return System(
        graph=graph,
        plan=compile_plan(graph, failures=FailureModel(link_p=tr["link_p"])),
        loss_fn=lambda p, b: loss(forward(p, b[0]), b[1]),
        optimizer=sgd(opt["learning_rate"], opt["momentum"]),
        init_one=init_one,
        n=adj.shape[0],
    )


def initial_state(system: System, cfg: dict, seed: int):
    """The paper's uncoordinated initialisation: the gossip estimator's
    per-node gains (or gain 1), then ``init_fl_state`` on the device."""
    from repro import fed
    from repro.gossip import make_gain_estimator

    k_est, k_init = reference.run_keys(seed)
    init = cfg["init"]
    if init["gains"] == "gossip_vnorm":
        r = init["estimate_rounds"]
        est = make_gain_estimator(system.plan, pi_rounds=r, ps_rounds=r, mode="vnorm")
        gains = jax.jit(est)(k_est)
        make = jax.jit(
            lambda k, g: fed.init_fl_state(k, system.n, system.init_one, system.optimizer, gains=g)
        )
        return make(k_init, gains)
    if init["gains"] != "none":
        raise ValueError(f"unknown init gains {init['gains']!r}")
    make = jax.jit(lambda k: fed.init_fl_state(k, system.n, system.init_one, system.optimizer))
    return make(k_init)
