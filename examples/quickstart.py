"""Quickstart: the paper's effect in one minute.

Trains a 16-node decentralised federated MLP on synthetic MNIST-like data
with plain He initialisation (the paper's Fig. 1 dashed baseline, which
plateaus) and with the proposed ‖v_steady‖⁻¹ gain-corrected initialisation,
and prints both test-loss trajectories.  Both runs execute as ONE fused,
vmapped program via the round executor (`repro.fed.run_sweep`): the whole
trajectory pair is a single scan-over-rounds with on-device data sampling
and on-device eval.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax

from repro.core import topology as T
from repro.core.initialisation import InitConfig, gain_from_graph
from repro.data import batch_index_schedule, mnist_like, node_datasets
from repro.fed import init_fl_state, make_eval_fn, make_round_fn, run_sweep, stack_states
from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
from repro.optim import sgd

N_NODES, PER_NODE, ROUNDS, B_LOCAL = 16, 128, 40, 4


def run(n_nodes=N_NODES, per_node=PER_NODE, rounds=ROUNDS, b_local=B_LOCAL):
    """He vs gain-corrected init as one sweep; returns {label: history}."""
    graph = T.complete(n_nodes)  # paper cfg. A: fully-connected communication
    gain = gain_from_graph(graph)
    print(f"communication network: {graph.name};  ‖v_steady‖⁻¹ gain = {gain:.2f}\n")

    ds = mnist_like(n_nodes * per_node + 512, seed=0)
    parts = [np.arange(i * per_node, (i + 1) * per_node) for i in range(n_nodes)]
    xs, ys = node_datasets(ds, parts)
    test = (ds.x[-512:], ds.y[-512:])

    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])
    opt = sgd(1e-3, momentum=0.5)
    eval_fn = make_eval_fn(loss_fn)

    variants = [("He et al. (uncorrected)", 1.0), ("proposed (gain-corrected)", gain)]
    states = stack_states([
        init_fl_state(
            jax.random.PRNGKey(0), n_nodes,
            lambda k, g=g: init_mlp(InitConfig("he_normal", g), k), opt,
        )
        for _, g in variants
    ])
    schedule = batch_index_schedule(per_node, n_nodes, 16, rounds * b_local, seed=0)
    _, hists = run_sweep(
        states, make_round_fn(loss_fn, opt, graph), xs, ys, schedule,
        n_rounds=rounds, eval_every=5, eval_fn=eval_fn, eval_batch=test,
        b_local=b_local,
    )

    for (label, _), hist in zip(variants, hists):
        traj = "  ".join(f"{v:.3f}" for v in hist["test_loss"])
        print(f"{label:28s} test loss @ rounds {hist['round']}:\n    {traj}\n")
    return {label: hist for (label, _), hist in zip(variants, hists)}


if __name__ == "__main__":
    run()
    print("note the plateau at log(10) ≈ 2.303 without the correction (paper Fig. 1).")
