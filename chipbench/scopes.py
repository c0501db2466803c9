"""The model's layers in a reduced trace: scopes through JAX's
transformation names, and what XLA fuses across them.

A ``jax.named_scope`` that is differentiated or batched reaches an op's
``tf_op`` path wrapped in the transformation's name: the model's
``model_conv`` in the local steps reads ``jvp(model_conv)`` in the forward
pass, ``transpose(jvp(model_conv))`` in the backward pass and
``vmap(model_conv)`` in the evaluation.  ``chipbench.spans.under`` matches
whole path parts and misses every such op; ``inner`` strips the wrappers.

A fusion carries the path of its root alone.  On the TPU a forward
convolution fused into the max-pool after it reads ``jvp(model_pool)``, and
a weight gradient fused into the optimizer's momentum update reads the
update's ``transpose``, under no model scope.  So ``conv_op`` takes every
MXU fusion of the local steps (category ``convolution ...``) but the
classifier's, beside the ops under ``model_conv``: all the convolutions'
work, with whatever XLA fused into it.  The loss's softmax reduction, a
convolution fusion too, comes with it (under 0.1 % of the convolutions'
time in the recorded VGG16 trace).
"""
from __future__ import annotations

from chipbench import spans

WRAPPERS = ("vmap(", "jvp(", "transpose(")


def inner(part: str) -> str:
    """``part`` with the transformation wrappers stripped:
    ``vmap(transpose(jvp(model_conv)))`` → ``model_conv``."""
    while part.endswith(")"):
        w = next((w for w in WRAPPERS if part.startswith(w)), None)
        if w is None:
            break
        part = part[len(w) : -1]
    return part


def names(op) -> set[str]:
    """The scope names of ``op``'s path, each with its wrappers stripped."""
    return {inner(p) for p in op.scope.split("/")}


def conv_op(op) -> bool:
    """The op is the local steps' convolution work (module docstring)."""
    n = names(op)
    if "dfl_local" not in n:
        return False
    return "model_conv" in n or (op.category.startswith("convolution") and "model_dense" not in n)


def conv_s(summary) -> float | None:
    """Device seconds of the window's ``conv_op`` ops, mean over devices;
    None where no op carries ``model_conv`` (a program without the model's
    scopes, whose every MXU fusion ``conv_op`` would take)."""
    if spans.ops_s(summary, lambda op: "model_conv" in names(op)) is None:
        return None
    return spans.ops_s(summary, conv_op)


def pool_op(op) -> bool:
    """The op is the local steps' max-pooling (with its ``select-and-scatter``
    backward) and no convolution fused into it."""
    return "dfl_local" in names(op) and "model_pool" in names(op) and not conv_op(op)
