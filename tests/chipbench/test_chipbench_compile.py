"""Compile-only guard for the benchmark's cells on a described TPU v5e.

Nothing here runs on a chip.  Each one-chip cell's round chunk (the
program ``run_trajectory`` calls per chunk, with its eval, σ and wire
channels) is compiled at the cell's shapes for a *described* ``v5e:2x2``
topology and must fit one chip's 16 GiB.

The topology is described inside a module fixture, never at import (one
process at a time may load the TPU library, and every test worker imports
this file), and the persistent compile cache is off around the compiles.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _paths import ROOT

from chipbench import program, traffic

HBM_BYTES = 16 * 2**30
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"] if w["chips"] == 1])
def test_one_chip_round_chunk_fits_v5e(topo, cell):
    from jax.sharding import SingleDeviceSharding

    from repro.fed import init_fl_state, make_eval_fn, make_round_fn
    from repro.fed.executor import _build_chunk_fn
    from repro.obs.wirecost import make_wire_fn

    one = SingleDeviceSharding(topo.devices[0])
    shape = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    from _tiny import full_spec

    spec = full_spec(cell)
    cfg, tr = spec["cfg"], spec["traffic"]
    system = program.build(cfg, tr, traffic.make_graph(tr["graph"]))
    n = system.n
    state = jax.eval_shape(
        lambda k: init_fl_state(k, n, system.init_one, system.optimizer), jax.random.PRNGKey(0)
    )
    state = jax.tree_util.tree_map(lambda s: shape(s.shape, s.dtype), state)
    d = sum(int(np.prod(leaf.shape[1:])) for leaf in jax.tree_util.tree_leaves(state.params))
    assert d == cfg["params_per_node"]
    round_fn = make_round_fn(system.loss_fn, system.optimizer, system.plan)
    wire = make_wire_fn(system.plan) if system.plan.failures.active else None
    chunk, _, _, _ = _build_chunk_fn(
        round_fn, n, make_eval_fn(system.loss_fn), cfg["track_sigmas"], wire_fn=wire
    )
    img = tuple(cfg["dataset"]["image_shape"])
    items, c = tr["items_per_node"], tr["chunk_rounds"]
    data = (
        shape((n, items) + img),
        shape((n, items), jnp.int32),
        (shape((tr["test_items"],) + img), shape((tr["test_items"],), jnp.int32)),
    )
    compiled = chunk.lower(
        state,
        shape((c, n, tr["local_batches"], tr["batch_size"]), jnp.int32),
        shape((c,), jnp.bool_),
        data,
    ).compile()
    # undonated on this backend: the state counts as argument and output,
    # which is what a run holds (the executor copies the state once)
    assert _footprint(compiled) <= HBM_BYTES
