"""Step functions + input specs for every (architecture × input shape).

This is the deployable SPMD layer: given an arch config, an input shape name
and a mesh, build

    * the jittable step function (fl_train_step or serve prefill/decode),
    * ShapeDtypeStruct stand-ins for every input (no allocation — the same
      abstract-lowering pattern the dry-run mandates),
    * in/out shardings.

Training = one DFL communication round on the production mesh: every FL node
(= one ``data``-axis slice) takes ``local_batches`` gradient steps, then the
ensemble aggregates through a compiled ``CommPlan`` (DESIGN.md §3):

    mixing="dense"      paper-faithful general-graph DecAvg — einsum with
                        the (n, n) receive matrix; GSPMD renders the node-axis
                        contraction as all-gather + local reduce.
    mixing="sparse"     edge-list gather + segment_sum — O(E·d) compute,
                        the large-n backend.
    mixing="ppermute"   edge-coloured collective schedule — one ppermute per
                        colour class inside shard_map, moving degree·|w|
                        instead of n·|w| bytes.  Works for ANY static
                        undirected graph; "circulant" is kept as an alias
                        (the production graph is circulant, for which the
                        colouring recovers the offset schedule).

Serving = consensus model; decode is ONE token against a cache of seq_len.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import topology
from repro.core.commplan import compile_plan
from repro.core.decavg import mix_pytree_colored
from repro.core.initialisation import InitConfig, gain_from_graph
from repro.models import transformer as tfm
from repro.optim import Optimizer, sgd
from . import shardings as shard_rules
from .mesh import n_fl_nodes, node_axis

PyTree = Any

__all__ = ["SHAPES", "ShapeSpec", "build_train_step", "build_prefill_step", "build_decode_step", "build"]


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

# circulant communication graph used for the production training rounds:
# offsets (1, 2) → random-4-regular-like degree-4 ring, paper §5's default k
# regime, and the collective_permute-friendly topology (DESIGN.md §2)
CIRCULANT_OFFSETS = (1, 2)


def _abstract_params(cfg: ArchConfig, gain: float) -> PyTree:
    icfg = InitConfig("trunc_normal", gain)
    return jax.eval_shape(lambda k: tfm.init_params(k, cfg, icfg), jax.random.PRNGKey(0))


def _token_spec(cfg: ArchConfig, batch: int, seq: int):
    """tokens (+ frontend embeds) for one sequence batch."""
    text_len = seq - cfg.n_frontend_tokens
    out = {"tokens": jax.ShapeDtypeStruct((batch, text_len), jnp.int32)}
    if cfg.frontend and cfg.n_frontend_tokens:
        out["frontend"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_frontend_tokens, cfg.frontend_embed_dim), jnp.bfloat16
        )
    return out


# ===================================================================== train
def build_train_step(
    cfg: ArchConfig,
    mesh,
    *,
    multi_pod: bool = False,
    mixing: str = "dense",
    local_batches: int = 1,
    optimizer: Optimizer | None = None,
    remat: bool = True,
    seq_len: int | None = None,
):
    """Returns (step_fn, example_args, in_shardings, out_shardings)."""
    n = n_fl_nodes(multi_pod=multi_pod)
    node_ax = node_axis(multi_pod=multi_pod)
    # degree-4 circulant at production sizes; complete graph for the tiny
    # meshes used by the integration tests (offsets would degenerate)
    graph = topology.circulant(n, CIRCULANT_OFFSETS) if n >= 5 else topology.complete(n)
    gain = gain_from_graph(graph)
    opt = optimizer or sgd(1e-3, 0.5)
    if mixing == "circulant":  # back-compat alias: colouring ≡ offset schedule
        mixing = "ppermute"
    plan = compile_plan(graph, backend=mixing)

    def loss_fn(params: PyTree, batch: dict) -> jax.Array:
        fe = batch.get("frontend")
        hidden, aux = tfm.forward(params, cfg, batch["tokens"], fe, remat=remat)
        nf = cfg.n_frontend_tokens if (cfg.frontend and fe is not None) else 0
        hidden_text = hidden[..., nf:, :] if nf else hidden
        loss = tfm.lm_loss(params, cfg, hidden_text, batch["targets"])
        return loss + 0.01 * aux

    def local_steps(params, opt_state, batches):
        def one(carry, batch):
            p, s = carry
            loss, grads = jax.value_and_grad(loss_fn)(p, batch)
            upd, s = opt.update(grads, s, p)
            p = jax.tree_util.tree_map(lambda a, u: a + u.astype(a.dtype), p, upd)
            return (p, s), loss

        (params, opt_state), losses = jax.lax.scan(one, (params, opt_state), batches)
        return params, opt_state, losses.mean()

    # ---- abstract inputs ---------------------------------------------
    params = _abstract_params(cfg, gain)
    params = jax.eval_shape(lambda p: jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), p), params)
    opt_state = jax.eval_shape(jax.vmap(opt.init), params)
    node_pspecs = shard_rules.with_node_axis(
        shard_rules.param_pspecs(params_strip_node(params), cfg, mesh), node_ax
    )

    def step(params, opt_state, batch):
        params, opt_state, loss = jax.vmap(local_steps)(params, opt_state, batch)
        if plan.backend in ("dense", "sparse"):
            # GSPMD handles both: dense = node-axis all-gather + local
            # contraction, sparse = gather/segment_sum over the node axis
            params = plan.mix(params)
        elif plan.backend == "ppermute":
            ax = node_ax if len(node_ax) > 1 else node_ax[0]
            mix_specs = shard_rules.commplan_in_specs(plan.backend, node_ax)
            mix = jax.shard_map(
                lambda p, cw, sw: mix_pytree_colored(p, plan.partners, cw, sw, axis_name=ax),
                mesh=mesh,
                in_specs=(node_pspecs, *mix_specs),
                out_specs=node_pspecs,
            )
            params = mix(params, plan.color_w, plan.self_w)
        else:
            raise ValueError(plan.backend)
        opt_state = jax.vmap(opt.init)(params)  # Algorithm 1 line 15
        return params, opt_state, loss.mean()
    per_node = SHAPES["train_4k"].global_batch // n
    seq = seq_len or SHAPES["train_4k"].seq_len
    batch = _token_spec(cfg, per_node, seq)
    batch = {
        k: jax.ShapeDtypeStruct((n, local_batches) + v.shape, v.dtype) for k, v in batch.items()
    }
    text_len = seq - cfg.n_frontend_tokens
    batch["targets"] = jax.ShapeDtypeStruct((n, local_batches, per_node, text_len), jnp.int32)

    # ---- shardings -----------------------------------------------------
    pspecs = node_pspecs
    ospecs = jax.eval_shape(opt.init, params_strip_node(params))
    ospecs = shard_rules.with_node_axis(shard_rules.param_pspecs(ospecs, cfg, mesh), node_ax)
    nax = tuple(node_ax) if len(node_ax) > 1 else node_ax[0]
    bspecs = {k: P(nax, *([None] * (len(v.shape) - 1))) for k, v in batch.items()}
    in_shardings = (
        shard_rules.shardings_for(pspecs, mesh),
        shard_rules.shardings_for(ospecs, mesh),
        shard_rules.shardings_for(bspecs, mesh),
    )
    out_shardings = (in_shardings[0], in_shardings[1], NamedSharding(mesh, P()))
    return step, (params, opt_state, batch), in_shardings, out_shardings


def params_strip_node(params: PyTree) -> PyTree:
    """Drop the leading node dim from abstract param shapes (spec helper)."""
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), params
    )


# ===================================================================== serve
def build_prefill_step(cfg: ArchConfig, mesh, *, multi_pod: bool = False, seq_len: int | None = None):
    shape = SHAPES["prefill_32k"]
    nax = ("pod", "data") if multi_pod else "data"

    def step(params, batch):
        fe = batch.get("frontend")
        hidden, _ = tfm.forward(params, cfg, batch["tokens"], fe, remat=False)
        return tfm.hidden_to_logits(params, cfg, hidden[..., -1:, :])[..., 0, :]

    params = _abstract_params(cfg, 1.0)
    batch = _token_spec(cfg, shape.global_batch, seq_len or shape.seq_len)
    pspecs = shard_rules.param_pspecs(params, cfg, mesh)
    bsize = shape.global_batch
    bdiv = bsize % _ax_size(mesh, nax) == 0
    bspecs = {k: P(nax if bdiv else None, *([None] * (len(v.shape) - 1))) for k, v in batch.items()}
    in_shardings = (shard_rules.shardings_for(pspecs, mesh), shard_rules.shardings_for(bspecs, mesh))
    vdiv = cfg.vocab_size % mesh.shape["model"] == 0
    out_shardings = NamedSharding(mesh, P(nax if bdiv else None, "model" if vdiv else None))
    return step, (params, batch), in_shardings, out_shardings


def build_decode_step(cfg: ArchConfig, mesh, *, shape_name: str = "decode_32k", multi_pod: bool = False):
    shape = SHAPES[shape_name]
    nax = ("pod", "data") if multi_pod else "data"
    b = shape.global_batch
    bdiv = b % _ax_size(mesh, nax) == 0

    def step(params, cache, tokens, pos):
        return tfm.decode_step(params, cfg, cache, tokens, pos)

    params = _abstract_params(cfg, 1.0)
    cache = jax.eval_shape(lambda: tfm.init_cache(cfg, (b,), shape.seq_len))
    tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    pspecs = shard_rules.param_pspecs(params, cfg, mesh)
    batch_axis = ("+".join(nax) if isinstance(nax, tuple) else nax) if bdiv else None
    seq_axis = None if bdiv else ("+".join(nax) if isinstance(nax, tuple) else nax)
    cspecs = shard_rules.cache_pspecs(cache, cfg, mesh, batch_axis=batch_axis, seq_axis=seq_axis)
    tok_spec = P(nax if bdiv else None, None)
    in_shardings = (
        shard_rules.shardings_for(pspecs, mesh),
        shard_rules.shardings_for(cspecs, mesh),
        NamedSharding(mesh, tok_spec),
        NamedSharding(mesh, P()),
    )
    vdiv = cfg.vocab_size % mesh.shape["model"] == 0
    out_shardings = (
        NamedSharding(mesh, P(nax if bdiv else None, None, "model" if vdiv else None)),
        shard_rules.shardings_for(cspecs, mesh),
    )
    return step, (params, cache, tokens, pos), in_shardings, out_shardings


def _ax_size(mesh, nax) -> int:
    if isinstance(nax, tuple):
        return int(np.prod([mesh.shape[a] for a in nax]))
    return mesh.shape[nax]


def build(
    cfg: ArchConfig,
    shape_name: str,
    mesh,
    *,
    multi_pod: bool = False,
    mixing: str = "dense",
    seq_len: int | None = None,
):
    """Dispatch: (arch, shape) → (step_fn, args, in_shardings, out_shardings)."""
    kind = SHAPES[shape_name].kind
    if kind == "train":
        return build_train_step(cfg, mesh, multi_pod=multi_pod, mixing=mixing, seq_len=seq_len)
    if kind == "prefill":
        return build_prefill_step(cfg, mesh, multi_pod=multi_pod, seq_len=seq_len)
    return build_decode_step(cfg, mesh, shape_name=shape_name, multi_pod=multi_pod)
