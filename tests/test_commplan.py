"""Backend parity for the CommPlan subsystem (DESIGN.md §3).

The contract: dense, sparse and ppermute are *interchangeable executions of
the same operator* — for any topology family, any data-size weighting and
any failure draw, mixing a node-stacked pytree must give identical results
(within fp32 accumulation tolerance) on every backend.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import topology as T
from repro.core.commplan import BACKENDS, FailureModel, compile_plan
from repro.core.mixing import receive_matrix

FAMILIES = {
    "complete": lambda n, seed: T.complete(n),
    "ring": lambda n, seed: T.ring(n),
    "circulant": lambda n, seed: T.circulant(n, (1, 2)),
    "kreg": lambda n, seed: T.random_k_regular(n, 4, seed=seed),
    "er_gnp": lambda n, seed: T.erdos_renyi_gnp(n, 4.5 / n + 0.05, seed=seed),
    "er_gnm": lambda n, seed: T.erdos_renyi_gnm(n, 3 * n, seed=seed),
    "ba": lambda n, seed: T.barabasi_albert(n, 3, seed=seed),
    "heavy_tail": lambda n, seed: T.configuration_heavy_tail(n, 2.2, seed=seed),
    "torus": lambda n, seed: T.torus_lattice((4, n // 4)),
    "star": lambda n, seed: T.star(n),
}


def _params(n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "w": jax.random.normal(ks[0], (n, 6, 3)),
        "b": {"v": jax.random.normal(ks[1], (n, 5))},
        "h": jax.random.normal(ks[2], (n, 17)).astype(jnp.bfloat16),
    }


def _max_err(a, b):
    return max(
        float(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)).max())
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


# --------------------------------------------------------------- pure parity
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_backend_parity_all_families(family):
    g = FAMILIES[family](16, 0)
    params = _params(g.n)
    outs = {b: compile_plan(g, b).mix(params) for b in BACKENDS}
    assert _max_err(outs["dense"], outs["sparse"]) < 1e-2  # bf16 leaf dominates
    assert _max_err(outs["dense"], outs["ppermute"]) < 1e-2
    # fp32 leaves agree to fp32 accumulation tolerance
    for b in ("sparse", "ppermute"):
        assert float(jnp.abs(outs["dense"]["w"] - outs[b]["w"]).max()) < 1e-5


@settings(max_examples=12, deadline=None)
@given(
    family=st.sampled_from(sorted(FAMILIES)),
    n=st.sampled_from([8, 16, 24]),
    seed=st.integers(0, 10),
    weighted=st.booleans(),
)
def test_backend_parity_property(family, n, seed, weighted):
    g = FAMILIES[family](n, seed)
    params = _params(g.n, seed)
    sizes = np.linspace(1.0, 3.0, g.n) if weighted else None
    outs = {b: compile_plan(g, b, data_sizes=sizes).mix(params) for b in BACKENDS}
    for b in ("sparse", "ppermute"):
        assert float(jnp.abs(outs["dense"]["w"] - outs[b]["w"]).max()) < 1e-5, (family, b)


@settings(max_examples=12, deadline=None)
@given(
    family=st.sampled_from(sorted(FAMILIES)),
    seed=st.integers(0, 10),
    link_p=st.sampled_from([0.3, 0.7, 1.0]),
    node_p=st.sampled_from([0.6, 1.0]),
)
def test_backend_parity_under_failures(family, seed, link_p, node_p):
    """One Bernoulli per edge/node, keyed identically → identical effective
    operator on every backend, including the renormalisation."""
    if link_p == 1.0 and node_p == 1.0:
        link_p = 0.5  # ensure the failure path is exercised
    g = FAMILIES[family](16, seed)
    params = _params(g.n, seed)
    fm = FailureModel(link_p=link_p, node_p=node_p)
    key = jax.random.PRNGKey(seed * 31 + 7)
    outs = {b: compile_plan(g, b, failures=fm).mix(params, key) for b in BACKENDS}
    for b in ("sparse", "ppermute"):
        assert float(jnp.abs(outs["dense"]["w"] - outs[b]["w"]).max()) < 1e-5, (family, b)


def test_failed_isolation_keeps_own_params():
    """node_p → 0: every backend must collapse the receive row to identity."""
    g = T.random_k_regular(12, 4, seed=0)
    params = _params(g.n)
    key = jax.random.PRNGKey(0)
    for b in BACKENDS:
        plan = compile_plan(g, b, failures=FailureModel(node_p=1e-9))
        out = plan.mix(params, key)
        assert float(jnp.abs(out["w"] - params["w"]).max()) < 1e-6, b


@settings(max_examples=8, deadline=None)
@given(family=st.sampled_from(sorted(FAMILIES)), seed=st.integers(0, 5))
def test_sparse_segment_and_hyb_renderings_agree(family, seed):
    """The sparse backend's two executions — segment_sum gather-scatter and
    the HYB ELL+hub layout — are renderings of the same edge weights."""
    from repro.core.decavg import mix_pytree_hyb, mix_pytree_sparse

    g = FAMILIES[family](16, seed)
    plan = compile_plan(g, "sparse")
    params = _params(g.n, seed)
    seg = mix_pytree_sparse(params, plan.src, plan.dst, plan.edge_w, plan.self_w)
    hyb = mix_pytree_hyb(
        params, plan.slot_idx, plan.slot_w, plan.hyb_self_w, plan.hub_rows, plan.hub_m
    )
    assert float(jnp.abs(seg["w"] - hyb["w"]).max()) < 1e-5


# ------------------------------------------------------------ graph exports
@settings(max_examples=10, deadline=None)
@given(family=st.sampled_from(sorted(FAMILIES)), seed=st.integers(0, 20))
def test_edge_coloring_is_proper_and_complete(family, seed):
    g = FAMILIES[family](16, seed)
    col = g.edge_coloring()
    n = g.n
    idx = np.arange(n)
    seen = set()
    for c in range(col.n_colors):
        p = col.partners[c]
        # involution: a colour class is a matching
        assert np.array_equal(p[p], idx)
        for i in range(n):
            if p[i] != i:
                assert g.adjacency[i, p[i]] != 0
                seen.add((min(i, int(p[i])), max(i, int(p[i]))))
    # every edge appears in exactly one colour class
    edges = {(int(u), int(v)) for u, v in g.edge_list()}
    assert seen == edges
    # greedy bound
    assert col.n_colors <= max(2 * int(g.degrees.max()) - 1, 1)


def test_directed_graph_dense_sparse_parity():
    """A[i, j] = 'i receives from j' must mean the same thing on both
    backends (regression: the directed CSR export once inverted it)."""
    rng = np.random.default_rng(3)
    a = (rng.random((10, 10)) < 0.3).astype(np.float32)
    np.fill_diagonal(a, 0.0)
    g = T.from_adjacency(a, directed=True)
    params = _params(g.n)
    dense = compile_plan(g, "dense").mix(params)
    sparse = compile_plan(g, "sparse").mix(params)
    assert float(jnp.abs(dense["w"] - sparse["w"]).max()) < 1e-5


def test_csr_matches_adjacency():
    g = T.barabasi_albert(20, 3, seed=4)
    indptr, indices, uid = g.csr()
    a = np.zeros_like(g.adjacency)
    for i in range(g.n):
        a[i, indices[indptr[i] : indptr[i + 1]]] = 1.0
    assert np.array_equal(a, (g.adjacency > 0).astype(a.dtype))
    # both directions of an undirected edge share one uid
    edges = g.edge_list()
    for i in range(g.n):
        for e in range(indptr[i], indptr[i + 1]):
            u, v = edges[uid[e]]
            assert {i, int(indices[e])} == {int(u), int(v)}


# ------------------------------------------------------- block-sparse kernel
def test_bsr_kernel_matches_dense_receive_matrix():
    from repro.kernels.mix.ops import decavg_mix

    g = T.configuration_heavy_tail(40, 2.2, seed=1)
    m = jnp.asarray(receive_matrix(g), jnp.float32)
    params = _params(g.n)
    want = compile_plan(g, "dense").mix(params)
    got = decavg_mix(m, params, backend="sparse", block_n=8, interpret=True)
    assert float(jnp.abs(want["w"] - got["w"]).max()) < 1e-5


# ----------------------------------------------- collective ppermute parity
@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices (CI sets XLA_FLAGS)")
def test_ppermute_collective_matches_dense_in_process():
    """True shard_map/ppermute rendering of the colour schedule (runs in CI
    where XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.decavg import mix_pytree_colored

    n = 8
    mesh = jax.make_mesh((8,), ("data",))
    for family in ("kreg", "er_gnp", "ring", "star"):
        g = FAMILIES[family](n, 3)
        plan = compile_plan(g, "ppermute")
        params = {
            "w": jax.random.normal(jax.random.PRNGKey(0), (n, 16, 4)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (n, 5)),
        }
        dense = compile_plan(g, "dense").mix(params)
        specs = {"w": P("data", None, None), "b": P("data", None)}
        f = jax.shard_map(
            lambda p, cw, sw: mix_pytree_colored(p, plan.partners, cw, sw, axis_name="data"),
            mesh=mesh,
            in_specs=(specs, P(None, "data"), P("data")),
            out_specs=specs,
        )
        with mesh:
            out = jax.jit(f)(params, plan.color_w, plan.self_w)
        assert _max_err(dense, out) < 1e-5, family


# ----------------------------------------------------- trainer integration
def test_make_round_fn_accepts_plan_and_backends_agree():
    """One full communication round through make_round_fn must be
    backend-independent: same state, same batches → same mixed params."""
    from repro.fed import init_fl_state, make_round_fn
    from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
    from repro.core.initialisation import InitConfig
    from repro.optim import sgd

    g = T.barabasi_albert(8, 3, seed=0)
    opt = sgd(1e-2, 0.0)
    icfg = InitConfig("he_normal", 1.0)
    init_one = lambda k: init_mlp(icfg, k, in_dim=16, hidden=(8,), n_classes=3)
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 4, 16))
    y = jax.random.randint(jax.random.PRNGKey(2), (8, 2, 4), 0, 3)

    results = []
    for backend in BACKENDS:
        state = init_fl_state(jax.random.PRNGKey(0), 8, init_one, opt)
        rf = jax.jit(make_round_fn(loss_fn, opt, compile_plan(g, backend)))
        state, metrics = rf(state, (x, y))
        results.append((backend, state.params, float(metrics["train_loss"])))
    for backend, params, loss in results[1:]:
        assert np.isclose(loss, results[0][2], rtol=1e-5)
        assert _max_err(results[0][1], params) < 1e-5, backend


def test_make_round_fn_data_sizes_override_keeps_plan_failures():
    """Overriding only data_sizes must not drop the plan's failure model
    (regression: the recompile once replaced it with the inactive default)."""
    from repro.fed import init_fl_state, make_round_fn
    from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
    from repro.core.initialisation import InitConfig
    from repro.optim import sgd

    g = T.random_k_regular(8, 4, seed=0)
    opt = sgd(1e-2, 0.0)
    init_one = lambda k: init_mlp(InitConfig("he_normal", 1.0), k, in_dim=16, hidden=(8,), n_classes=3)
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 1, 4, 16))
    y = jax.random.randint(jax.random.PRNGKey(2), (8, 1, 4), 0, 3)
    sizes = np.linspace(1.0, 2.0, 8)

    # node_p -> 0 isolates every node; if the failure model survives the
    # data_sizes override, aggregation is the identity
    plan = compile_plan(g, "sparse", failures=FailureModel(node_p=1e-9))
    state0 = init_fl_state(jax.random.PRNGKey(0), 8, init_one, opt)
    rf = jax.jit(make_round_fn(loss_fn, opt, plan, data_sizes=sizes))
    state1, _ = rf(state0, (x, y))
    rf_local = jax.jit(make_round_fn(loss_fn, opt, g, aggregate=False))
    state2, _ = rf_local(state0, (x, y))
    assert _max_err(state1.params, state2.params) < 1e-6


# ------------------------------------------------------ auto backend choice
@pytest.mark.parametrize(
    "platform, graph, want",
    [
        ("tpu", lambda: T.barabasi_albert(256, 8, seed=0), "dense"),
        ("tpu", lambda: T.ring(4096), "sparse"),
        ("cpu", lambda: T.ring(65), "sparse"),
        ("cpu", lambda: T.ring(64), "dense"),
    ],
    ids=["tpu-ba256", "tpu-ring4096", "cpu-65", "cpu-64"],
)
def test_auto_backend_by_platform_n_and_edges(platform, graph, want):
    from repro.core.commplan import auto_backend

    g = graph()
    assert auto_backend(platform, g.n, len(g.csr()[1])) == want


def test_auto_dense_and_sparse_agree_on_the_cell_graph():
    """BA n=256 m=8 at link_p 0.9: the rendering "auto" picks on a TPU
    (dense) mixes, masks and counts the wire as the CPU's (sparse) does."""
    from repro.obs.wirecost import make_wire_fn

    g = T.barabasi_albert(256, 8, seed=0)
    fm = FailureModel(link_p=0.9)
    plans = {b: compile_plan(g, b, failures=fm) for b in ("dense", "sparse")}
    params = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (g.n, 6, 3)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (g.n, 5)),
    }
    for r in range(3):
        key = jax.random.PRNGKey(100 + r)
        dense, sparse = (plans[b].mix(params, key) for b in ("dense", "sparse"))
        assert _max_err(dense, sparse) < 1e-5, r
        for a, b in zip(plans["dense"].round_masks(key), plans["sparse"].round_masks(key)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        wires = [float(make_wire_fn(plans[b])(key, r)) for b in ("dense", "sparse")]
        assert wires[0] == wires[1] > 0


def test_compile_plan_auto_counts_its_choice():
    from repro.obs.trace import COUNTERS, counts

    before = counts()
    assert compile_plan(T.ring(8)).backend == "dense"  # CPU: n <= 64
    assert compile_plan(T.ring(65), backend="auto").backend == "sparse"
    compile_plan(T.ring(65), backend="sparse")  # a named backend is not counted
    after = counts()
    for name, delta in (("commplan.auto_dense", 1), ("commplan.auto_sparse", 1)):
        assert name in COUNTERS
        assert after.get(name, 0) - before.get(name, 0) == delta
