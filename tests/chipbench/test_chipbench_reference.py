"""The plain reference's pieces against the program's, at a small size.

The reference imports nothing of the program; these tests are where the
two meet: the same seed must give the same link draws, gains, weights and
mixing operator, or the reference would not follow the program's run.
"""
import jax
import numpy as np
import pytest
from _paths import ROOT  # noqa: F401

from chipbench import counts, program, reference, traffic


def _cfg(hidden=(32, 16)):
    import json

    cfg = json.loads((ROOT / "chipbench" / "configs" / "paper_mlp.json").read_text())
    cfg["hidden"] = list(hidden)
    cfg["program"]["init_kwargs"]["hidden"] = list(hidden)
    return cfg


def _tr(n=24, link_p=0.9):
    return {
        "graph": {"family": "ba", "n": n, "m": 3, "graph_seed": 0}, "link_p": link_p,
        "items_per_node": 32, "batch_size": 16, "local_batches": 8, "test_items": 64,
    }


def test_graph_matches_the_program_generator():
    from repro.core import topology as T

    a = traffic.make_graph({"family": "ba", "n": 64, "m": 8, "graph_seed": 0})
    g = T.barabasi_albert(64, 8, seed=0)
    assert np.array_equal(a, g.adjacency)
    assert np.array_equal(traffic.edge_list(a), g.edge_list())
    k = traffic.make_graph({"family": "kregular", "n": 16, "k": 4, "graph_seed": 0})
    assert np.array_equal(k, T.random_k_regular(16, 4, seed=0).adjacency)


@pytest.mark.parametrize("n,backend", [(24, "dense"), (80, "sparse")])
def test_link_draws_and_operator_match_the_plan(n, backend):
    """The program's failure-masked mix (its dense rendering at n ≤ 64, the
    sparse gather/scatter above, as the full-size cell) is the reference's
    renormalised receive operator over the surviving links."""
    tr = _tr(n)
    system = program.build(_cfg(), tr, traffic.make_graph(tr["graph"]))
    plan = system.plan
    assert plan.backend == backend
    adj = traffic.make_graph(tr["graph"])
    edges = traffic.edge_list(adj)
    key = jax.random.PRNGKey(7)
    keep_plan, _ = plan.round_masks(key)
    keep_ref = reference.edge_keep(key[None], len(edges), tr["link_p"])[0]
    assert np.array_equal(np.asarray(keep_plan), keep_ref) and not keep_ref.all()
    # the plan's mix of an identity payload is its receive operator
    eye = jax.numpy.eye(system.n)
    m_plan = np.asarray(plan.mix({"w": eye}, key=key)["w"])
    np.testing.assert_allclose(m_plan, reference.receive_matrix(adj, edges, keep_ref), atol=1e-6)


def test_gossip_gains_match_the_estimator():
    from repro.gossip import make_gain_estimator

    tr = _tr()
    adj = traffic.make_graph(tr["graph"])
    system = program.build(_cfg(), tr, adj)
    k_est, _ = reference.run_keys(3_000_000_007)
    est = make_gain_estimator(system.plan, pi_rounds=32, ps_rounds=32, mode="vnorm")
    got = np.asarray(jax.jit(est)(k_est))
    want = reference.gossip_gains(adj, tr["link_p"], k_est, 32)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got.min() > 1.0


@pytest.mark.parametrize("gains", ["gossip_vnorm", "none"])
def test_initial_weights_match_init_fl_state(gains):
    cfg = _cfg()
    cfg["init"] = {"distribution": "he_normal", "gains": gains, "estimate_rounds": 32}
    tr = _tr()
    adj = traffic.make_graph(tr["graph"])
    seed = 2**31 + 17
    state = program.initial_state(program.build(cfg, tr, adj), cfg, seed)
    k_est, k_init = reference.run_keys(seed)
    g = reference.gossip_gains(adj, tr["link_p"], k_est, 32) if gains != "none" else np.ones(24)
    params, stream = reference.init_params(counts.reference_module(cfg), cfg, k_init, g)
    for name in params:
        np.testing.assert_allclose(
            np.asarray(state.params[name]["w"]), np.asarray(params[name]["w"]), rtol=2e-5
        )
        assert not np.asarray(state.params[name]["b"]).any()
    assert np.array_equal(np.asarray(state.rng), np.asarray(stream))


def test_seed_uses_all_its_bits():
    a, b = reference.seed_key(5), reference.seed_key(5 + 2**32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
