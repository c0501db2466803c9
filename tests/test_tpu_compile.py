"""Compile-only guards for TPU v5e: the main path's programs at real sizes.

Nothing here runs on a chip.  Each test compiles for a *described* v5e chip
(``v5e:2x2`` topology, first device), which raises what the chip's compiler
would raise: a Pallas tiling it refuses, more VMEM than a kernel may use, or
a program that does not fit the chip's 16 GiB of HBM.  Shapes only — no
array is placed on the described device.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compile cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology as T
from repro.core.commplan import FailureModel, auto_backend, compile_plan
from repro.core.initialisation import InitConfig, gain_from_graph
from repro.core.mixing import receive_matrix
from repro.fed import batching, init_fl_state, make_eval_fn, make_round_fn
from repro.fed.executor import _build_chunk_fn
from repro.kernels.mix import bsr_from_dense, mix_bsr, quantised_mix_bsr
from repro.kernels.mix.mix import mix_matmul
from repro.models.paper_models import classifier_loss, init_mlp, mlp_forward
from repro.obs.trace import counts
from repro.optim import sgd

HBM_BYTES = 16 * 2**30  # TPU v5e, per chip
D_MLP = 567_434  # paper MLP 784-512-256-128-10, flattened
N_KERNEL = 1024  # mixing-kernel ensemble
N_TRAIN, ITEMS, BATCH, LOCAL, ROUNDS = 256, 256, 16, 8, 20  # chip_smoke phase (a)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    )


def _bsr_shapes(one_chip):
    m = receive_matrix(T.barabasi_albert(N_KERNEL, 8, seed=0)).astype(np.float32)
    bc, tiles = bsr_from_dense(m, 128)
    return _shape(one_chip, bc.shape, jnp.int32), _shape(one_chip, tiles.shape)


@pytest.mark.parametrize(
    "kernel",
    [
        "mix_matmul",
        "mix_bsr",
        "quantised_mix_bsr_int8",
    ],
)
def test_mixing_kernel_compiles_for_v5e(one_chip, kernel):
    """The mixing kernels at the paper MLP's width over 1024 nodes lower to
    a Mosaic kernel and fit one chip."""
    w = _shape(one_chip, (N_KERNEL, D_MLP))
    if kernel == "mix_matmul":
        lowered = mix_matmul.lower(_shape(one_chip, (N_KERNEL, N_KERNEL)), w)
    else:
        bc, tiles = _bsr_shapes(one_chip)
        fn = mix_bsr if kernel == "mix_bsr" else quantised_mix_bsr
        lowered = fn.lower(bc, tiles, w)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _footprint(compiled) <= HBM_BYTES


def _round_chunk(one_chip, plan, item_shape=(784,), rounds=ROUNDS):
    """chip_smoke phase (a)'s fused round chunk over ``plan``, compiled for
    the described chip; ``item_shape`` and ``rounds`` set the node datasets'
    items and the chunk's length."""
    graph = plan.graph
    loss_fn = lambda p, b: classifier_loss(mlp_forward(p, b[0]), b[1])  # noqa: E731
    opt = sgd(1e-3, 0.5)
    init_one = lambda k: init_mlp(InitConfig("he_normal", gain_from_graph(graph)), k)  # noqa: E731
    state = jax.eval_shape(
        lambda k: init_fl_state(k, N_TRAIN, init_one, opt), jax.random.PRNGKey(0)
    )
    state = jax.tree_util.tree_map(lambda s: _shape(one_chip, s.shape, s.dtype), state)
    chunk, _, _, _ = _build_chunk_fn(
        make_round_fn(loss_fn, opt, plan), N_TRAIN, make_eval_fn(loss_fn), True
    )
    data = (
        _shape(one_chip, (N_TRAIN, ITEMS) + item_shape),
        _shape(one_chip, (N_TRAIN, ITEMS), jnp.int32),
        (_shape(one_chip, (1024,) + item_shape), _shape(one_chip, (1024,), jnp.int32)),
    )
    compiled = chunk.lower(
        state,
        _shape(one_chip, (rounds, N_TRAIN, LOCAL, BATCH), jnp.int32),
        _shape(one_chip, (rounds,), jnp.bool_),
        data,
    ).compile()
    assert sum(
        np.prod(l.shape) for l in jax.tree_util.tree_leaves(state.params)
    ) == N_TRAIN * D_MLP
    return compiled


def test_round_chunk_compiles_for_v5e(one_chip):
    """The fused executor's round chunk for chip_smoke phase (a) — 256 paper
    MLPs on a Barabási–Albert graph, failure-masked sparse mix, eval and σ
    channels on — fits one chip."""
    graph = T.barabasi_albert(N_TRAIN, 8, seed=0)
    plan = compile_plan(graph, failures=FailureModel(link_p=0.9))
    assert plan.backend == "sparse"
    assert _footprint(_round_chunk(one_chip, plan)) <= HBM_BYTES


def test_round_chunk_with_tpu_auto_mix_compiles_for_v5e(one_chip):
    """The same chunk over the plan ``auto`` gives on a TPU — the dense
    masked mix — fits one chip without the sparse mix's (edges, d)
    temporaries: under 4 GiB, where the sparse chunk books about 15 GB."""
    graph = T.barabasi_albert(N_TRAIN, 8, seed=0)
    backend = auto_backend("tpu", graph.n, len(graph.csr()[1]))
    assert backend == "dense"
    plan = compile_plan(graph, backend=backend, failures=FailureModel(link_p=0.9))
    assert _footprint(_round_chunk(one_chip, plan)) <= 4 * 2**30


def test_cell_chunk_with_onehot_batches_compiles_for_v5e(one_chip, monkeypatch):
    """``mlp_ba256_linkfail``'s chunk (10 rounds, (28, 28, 1) f32 items, the
    dense masked mix) with its minibatches drawn by the one-hot contraction
    that the TPU's rule picks for it fits one chip under 4 GiB, like the
    gathering chunk."""
    graph = T.barabasi_albert(N_TRAIN, 8, seed=0)
    plan = compile_plan(graph, backend="dense", failures=FailureModel(link_p=0.9))
    assert batching.select_rendering("tpu", jnp.float32, ITEMS, LOCAL * BATCH) == "onehot"
    monkeypatch.setattr(batching, "select_rendering", lambda *a: "onehot")
    before = counts().get("batch.select_onehot", 0)
    compiled = _round_chunk(one_chip, plan, item_shape=(28, 28, 1), rounds=10)
    assert counts()["batch.select_onehot"] - before == 1
    assert _footprint(compiled) <= 4 * 2**30
