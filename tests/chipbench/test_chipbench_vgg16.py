"""The ``paper_vgg16`` configuration and its cell ``vgg16_kreg16``.

Its plain reference against the program's VGG16 (layout at full width,
forward, loss and gradients at a small one), the FLOP counts its readers
use, a whole cut-down run of the cell with faults planted, the matcher of
the model's scopes through JAX's transformation names, and the three
readers on a trace recorded on a TPU v5e by ``record_vgg_trace.py``.
"""
import gzip
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _paths import ROOT
from _tiny import full_spec, run
from test_chipbench_faults import _wrap_round_fn

from chipbench import counts, entries, metrics, scopes, spans
from chipbench.trace import Op, Summary

CELL = "vgg16_kreg16"
WIDTH = 0.125
READERS = ["conv_ms_per_round", "pool_ms_per_round", "conv_roofline"]


def _cfg(width=1.0):
    """The configuration, its program and its reference at ``width``."""
    cfg = json.loads((ROOT / "chipbench" / "configs" / "paper_vgg16.json").read_text())
    cfg["conv"] = [c if c == "M" else max(8, int(c * width)) for c in cfg["conv"]]
    cfg["fc"] = [cfg["conv"][-2]] + [max(16, int(f * width)) for f in cfg["fc"][1:-1]] + cfg["fc"][-1:]
    cfg["program"]["init_kwargs"]["width_mult"] = width
    return cfg


def _program_params(cfg, key):
    from chipbench import program, traffic

    system = program.build(cfg, full_spec(CELL)["traffic"], traffic.make_graph({"family": "ring", "n": 4}))
    return system.init_one(key)


def _tiny_spec():
    """The cell cut down for the CPU: n=16 and the graph kept, widths at
    0.125, 32 items and 32 test images per run, chunks of 3 rounds; the
    CPU's per-node (grouped) convolution is slow, so batches of 4, 2 local
    batches a round and 2 warm-up chunks."""
    s = full_spec(CELL)
    s["cfg"] = _cfg(WIDTH)
    s["traffic"].update(items_per_node=32, test_items=32, chunk_rounds=3, batch_size=4,
                        local_batches=2, warmup_chunks=2)
    return s


# ------------------------------------------------------------ reference
def test_layout_matches_init_vgg16_at_full_width():
    cfg = _cfg()
    ref = counts.reference_module(cfg)
    got = jax.eval_shape(lambda k: _program_params(cfg, k), jax.random.PRNGKey(0))
    lay = ref.layout(cfg)
    assert set(got) == {name for name, _, _ in lay}
    for name, shape, fan_in in lay:
        assert got[name]["w"].shape == shape and got[name]["b"].shape == (shape[-1],)
        assert fan_in == int(np.prod(shape[:-1]))
    assert counts.params_per_node(cfg) == cfg["params_per_node"] == 33_638_218


def test_reference_draws_the_program_initial_weights():
    """Same seed, same weights: the reference's layout is the init's draw
    order (one sub-key per layer, convolutions first)."""
    from chipbench import program, reference, traffic

    s = _tiny_spec()
    cfg, tr = s["cfg"], s["traffic"]
    adj = traffic.make_graph(tr["graph"])
    seed = 2**31 + 17
    state = program.initial_state(program.build(cfg, tr, adj), cfg, seed)
    _, k_init = reference.run_keys(seed)
    params, _ = reference.init_params(counts.reference_module(cfg), cfg, k_init, np.ones(adj.shape[0]))
    for name in params:
        np.testing.assert_allclose(
            np.asarray(state.params[name]["w"]), np.asarray(params[name]["w"]), rtol=2e-5
        )


def test_reference_forward_loss_and_gradients_match_vgg16_forward():
    """At width 0.125 on seeded random weights and biases, both at
    ``highest``.  Tolerance: the two compile their convolutions, pooling
    and dots as separate programs with their own summation order, which
    moves float32 results by a few ulps a layer over 16 layers; 1e-5 of
    the largest magnitude is a hundred times that and far below any change
    of the mathematics (a missing bias or ReLU moves logits by 1e-1)."""
    from repro.models.paper_models import classifier_loss, vgg16_forward

    from chipbench import reference

    cfg = _cfg(WIDTH)
    ref = counts.reference_module(cfg)
    kp, kb, kx, ky = jax.random.split(jax.random.PRNGKey(5), 4)
    params = _program_params(cfg, kp)
    leaves, tdef = jax.tree_util.tree_flatten(params)
    bkeys = jax.random.split(kb, len(leaves))
    params = jax.tree_util.tree_unflatten(
        tdef, [a if a.ndim > 1 else 0.1 * jax.random.normal(k, a.shape) for a, k in zip(leaves, bkeys)]
    )
    x = jax.random.normal(kx, (6, 32, 32, 3))
    y = jax.random.randint(ky, (6,), 0, 10)

    def close(a, b):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * max(np.abs(b).max(), 1e-30))

    def both(fwd, loss):
        return jax.jit(lambda p: (fwd(p, x), *jax.value_and_grad(lambda q: loss(fwd(q, x), y))(p)))

    with jax.default_matmul_precision("highest"):
        zp, lp, gp = both(vgg16_forward, classifier_loss)(params)
        zr, lr, gr = both(ref.forward, reference._xent)(params)
    close(zp, zr)
    close(lp, lr)
    jax.tree_util.tree_map(close, gp, gr)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree_util.tree_leaves(gr))


def test_flop_counts_at_full_width():
    ref = counts.reference_module(_cfg())
    assert ref.conv_forward_flops(_cfg()) == 626_393_088
    assert ref.forward_flops(_cfg()) == 626_393_088 + 37_830_656 == 664_223_744
    # 3 × the forward less the first layer's input gradient (2·32²·27·64)
    assert ref.conv_train_flops(_cfg()) == 1_875_640_320
    assert counts.forward_flops(_cfg()) == 664_223_744


# ------------------------------------------------------- whole cut-down run
def test_cut_down_cell_is_correct():
    result, lines = run(_tiny_spec())
    assert result["correct"], lines
    assert result["attempted"] == len(result["checks"]) and "wire" in result["checks"]


def _half_batch(monkeypatch):
    from repro.fed import trainer

    orig = trainer._local_steps

    def half(loss_fn, opt, params, opt_state, batches):
        batches = jax.tree_util.tree_map(lambda b: b[:, : b.shape[1] // 2], batches)
        return orig(loss_fn, opt, params, opt_state, batches)

    monkeypatch.setattr(trainer, "_local_steps", half)


def _one_node_altered(monkeypatch):
    import dataclasses

    _wrap_round_fn(
        monkeypatch,
        lambda old, new: dataclasses.replace(
            new, params=jax.tree_util.tree_map(lambda a: a.at[0].multiply(2.0), new.params)
        ),
    )


@pytest.mark.parametrize("plant", [_half_batch, _one_node_altered], ids=["half_batch", "one_node_altered"])
def test_cut_down_cell_catches_a_planted_fault(monkeypatch, plant):
    plant(monkeypatch)
    assert not run(_tiny_spec())[0]["correct"]


# ------------------------------------------------------------ scope matcher
@pytest.mark.parametrize("part,inner", [
    ("vmap(jvp(model_conv))", "model_conv"),
    ("vmap(transpose(jvp(model_conv)))", "model_conv"),
    ("transpose(jvp(model_pool))", "model_pool"),
    ("model_dense", "model_dense"),
    ("vmap()", ""),
    ("jit(eval_fn)", "jit(eval_fn)"),
    ("my_model_conv_head", "my_model_conv_head"),
    ("vmap(model_conv_head)", "model_conv_head"),
])
def test_scope_matcher_strips_transformation_wrappers(part, inner):
    assert scopes.inner(part) == inner
    op = Op(0, 1, "%f", f"jit(chunk_inner)/while/body/dfl_local/vmap()/{part}/add", "loop fusion")
    assert scopes.conv_op(op) == (inner == "model_conv")
    assert scopes.pool_op(op) == (inner == "model_pool")


@pytest.mark.parametrize("scope,category,conv,pool", [
    ("dfl_local/vmap()/jvp(model_pool)/reduce_window_max", "convolution fusion", True, False),
    ("dfl_local/vmap()/jvp(model_pool)/reduce_window_max", "output fusion", False, True),
    ("dfl_local/vmap()/transpose", "convolution fusion", True, False),
    ("dfl_local/vmap()/transpose", "loop fusion", False, False),
    ("dfl_local/vmap()/transpose(jvp(model_dense))/dot_general", "convolution fusion", False, False),
    ("cond/dfl_eval/jit(eval_fn)/vmap(model_conv)/conv_general_dilated", "convolution fusion", False, False),
])
def test_fused_ops_go_to_the_conv_reader(scope, category, conv, pool):
    """A fusion carries its root's path: a convolution fused into a pool or
    into the optimizer's update is the convolutions' work all the same."""
    op = Op(0, 1, "%fusion", "jit(chunk_inner)/while/body/" + scope, category)
    assert (scopes.conv_op(op), scopes.pool_op(op)) == (conv, pool)


# ------------------------------------------------------------ chip trace
@pytest.fixture(scope="module")
def chip():
    """A trace recorded on a TPU v5e by ``record_vgg_trace.py`` (4 VGG16
    nodes at width 0.25 on a ring, 2 chunks of 2 rounds; the window holds
    the second chunk)."""
    import os
    import tempfile

    data = gzip.decompress((ROOT / "tests" / "chipbench" / "data" / "chip_trace_vgg16.xplane.pb.gz").read_bytes())
    with tempfile.NamedTemporaryFile(suffix=".xplane.pb", delete=False) as f:
        f.write(data)
    try:
        return Summary.from_file(f.name, 1)
    finally:
        os.unlink(f.name)


def _ctx(summary):
    tr = {"graph": {"family": "ring", "n": 4}, "local_batches": 8, "batch_size": 16}
    win = entries.Window(rounds=2, node_rounds=8, wall_s=1.0, t0=0.0, t1=0.0, calls=1,
                         notes={"eval_rounds": 1})
    return metrics.Context(_cfg(0.25), tr, win, win, summary, peak_bytes=2**30,
                           device_kind="TPU v5 lite", chips=1)


def test_readers_on_the_chip_trace(chip):
    got = metrics.read_all([{"name": m} for m in READERS], _ctx(chip))
    assert set(got) == set(READERS), got
    local = chip.scope_s("dfl_local")
    conv, pool = spans.ops_s(chip, scopes.conv_op), spans.ops_s(chip, scopes.pool_op)
    assert got["conv_ms_per_round"] == pytest.approx(1e3 * conv / 2)
    assert got["pool_ms_per_round"] == pytest.approx(1e3 * pool / 2)
    assert 0 < pool < conv and conv + pool < local
    assert 0 < got["conv_roofline"] <= 100
    # the exact part match of the harness sees none of it
    assert spans.ops_s(chip, spans.under("model_conv")) is None


def test_readers_read_nothing_without_the_model_scopes(chip):
    """A program whose forward names no scopes (the MLP, or the VGG before
    its scopes) leaves the three readers silent."""
    import dataclasses

    bare = [[dataclasses.replace(o, scope=o.scope.replace("model_", "layer_")) for o in d] for d in chip.ops]
    summary = dataclasses.replace(chip, ops=bare)
    assert metrics.read_all([{"name": m} for m in READERS], _ctx(summary)) == {}


def test_no_convolution_escapes_the_conv_reader(chip):
    """Every MXU op of the local steps is the conv reader's or the
    classifier's, so ``conv_roofline`` cannot read high for want of time.
    The scope alone would miss the forward convolutions fused into a pool
    and the 13 kernels' weight gradients fused into the momentum update."""
    local = [o for d in chip.ops for o in d if "dfl_local" in scopes.names(o)]
    mxu = [o for o in local if o.category.startswith("convolution")]
    assert all(scopes.conv_op(o) != ("model_dense" in scopes.names(o)) for o in mxu)
    model = {"model_conv", "model_pool", "model_dense"}
    update = [o for o in mxu if not scopes.names(o) & model and o.name.startswith("%multiply_add_fusion")]
    assert update and len(update) % 13 == 0
    assert any("model_pool" in scopes.names(o) for o in mxu)
    sas = [o for o in local if o.category == "select-and-scatter"]
    assert sas and all(scopes.pool_op(o) for o in sas)
