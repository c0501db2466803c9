"""Traffic of the DFL benchmark: the graph, the data and the batch order.

One general generator for every traffic mix.  A mix is a JSON file under
``chipbench/traffic/`` (found by its name in ``BENCHMARK.json``) holding
the graph family and its parameters, the link survival probability, the
local work per round, the evaluation cadence and the entry's chunking.

The graph comes from the mix's own fixed ``graph_seed``: its shape sets the
mixing program's shapes (HYB slots, shard padding), so a graph drawn from
the run's seed would change the compiled program from run to run.  The
data, the batch order, the weights and the per-round link draws come from
the run's ``--seed``.

The generators are copies of the program's (``repro.core.topology``
``barabasi_albert``/``random_k_regular``, ``repro.data.synthetic``
``make_image_classification``, ``repro.data.pipeline``
``batch_index_schedule``), kept here so that a change to the program
cannot change what the benchmark feeds it.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    """The traffic mix ``chipbench/traffic/<name>.json``."""
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of the run's seed (any size)."""
    return np.random.default_rng([int(seed), int(stream)])


# ------------------------------------------------------------------ graphs
def _barabasi_albert(n: int, m: int, seed: int) -> np.ndarray:
    """Preferential attachment from an (m+1)-clique, m edges per new node."""
    if m < 1 or m >= n:
        raise ValueError("need 1 <= m < n")
    r = np.random.default_rng(seed)
    a = np.zeros((n, n), dtype=np.float32)
    for i in range(m + 1):
        for j in range(i + 1, m + 1):
            a[i, j] = a[j, i] = 1.0
    pool = list(np.nonzero(a)[0])
    for v in range(m + 1, n):
        chosen: set[int] = set()
        while len(chosen) < m:
            t = int(pool[r.integers(len(pool))])
            if t != v:
                chosen.add(t)
        for t in chosen:
            a[v, t] = a[t, v] = 1.0
            pool.extend([v, t])
    return a


def _connected(a: np.ndarray) -> bool:
    seen, todo = {0}, [0]
    while todo:
        for j in np.nonzero(a[todo.pop()])[0]:
            if int(j) not in seen:
                seen.add(int(j))
                todo.append(int(j))
    return len(seen) == a.shape[0]


def _random_k_regular(n: int, k: int, seed: int) -> np.ndarray:
    import networkx as nx

    for attempt in range(100):
        g = nx.random_regular_graph(k, n, seed=seed + 7919 * attempt)
        a = nx.to_numpy_array(g, dtype=np.float32)
        if _connected(a):
            return a
    raise RuntimeError(f"no connected {k}-regular graph on {n} nodes")


def make_graph(spec: dict) -> np.ndarray:
    """(n, n) float32 symmetric adjacency, zero diagonal, of ``spec``:
    ``{"family": "ba", "n", "m", "graph_seed"}``,
    ``{"family": "kregular", "n", "k", "graph_seed"}`` or
    ``{"family": "ring", "n"}``."""
    fam, n = spec["family"], int(spec["n"])
    if fam == "ba":
        return _barabasi_albert(n, int(spec["m"]), int(spec["graph_seed"]))
    if fam == "kregular":
        return _random_k_regular(n, int(spec["k"]), int(spec["graph_seed"]))
    if fam == "ring":
        a = np.zeros((n, n), np.float32)
        i = np.arange(n)
        a[i, (i + 1) % n] = a[(i + 1) % n, i] = 1.0
        return a
    raise ValueError(f"unknown graph family {fam!r}")


def edge_list(adjacency: np.ndarray) -> np.ndarray:
    """(m, 2) undirected edges, i < j, in row-major order of the adjacency:
    the order in which the failure model draws one survival per edge."""
    i, j = np.nonzero(np.triu(adjacency, 1))
    return np.stack([i, j], axis=1).astype(np.int32)


# -------------------------------------------------------------------- data
def make_images(
    n_samples: int,
    image_shape,
    n_classes: int,
    seed: int,
    class_sep: float = 2.0,
    n_prototypes: int = 4,
) -> tuple[np.ndarray, np.ndarray]:
    """Class-conditional Gaussian mixture of images, standardised.

    Each class has ``n_prototypes`` smooth prototypes (random coefficients
    on an 8×8 cosine basis); a sample is a prototype of its class plus
    white noise.  Returns ``x`` (N, H, W, C) float32 and ``y`` (N,) int32.
    """
    r = rng(seed, 0)
    h, w, c = image_shape
    nb = 8
    fy = np.cos(np.pi * np.arange(h)[:, None] * np.arange(nb)[None, :] / h)
    fx = np.cos(np.pi * np.arange(w)[:, None] * np.arange(nb)[None, :] / w)
    coef = r.standard_normal((n_classes, n_prototypes, nb, nb, c))
    protos = (np.einsum("hb,wB,kpbBc->kphwc", fy, fx, coef) / nb * class_sep).astype(np.float32)
    y = r.integers(0, n_classes, size=n_samples).astype(np.int32)
    pick = r.integers(0, n_prototypes, size=n_samples)
    x = r.standard_normal((n_samples, h, w, c), dtype=np.float32)
    x += protos[y, pick]
    x -= x.mean()
    x /= x.std() + 1e-8
    return x, y


def batch_schedule(
    per_node: int, n_nodes: int, batch_size: int, n_batches: int, seed: int
) -> np.ndarray:
    """(n_batches, n_nodes, batch_size) int32 sample indices: every epoch
    one fresh permutation per node, the remainder dropped, all nodes
    crossing epochs together."""
    if batch_size > per_node:
        raise ValueError(f"batch_size {batch_size} > per_node {per_node}")
    r = rng(seed, 1)
    bpe = per_node // batch_size
    out = []
    for _ in range(-(-n_batches // bpe)):
        order = r.permuted(np.tile(np.arange(per_node), (n_nodes, 1)), axis=1)
        out.append(order[:, : bpe * batch_size].reshape(n_nodes, bpe, batch_size).transpose(1, 0, 2))
    return np.concatenate(out)[:n_batches].astype(np.int32)
