"""Plain reference of ``paper_mlp``: the paper's Appendix A MLP in jax.numpy.

Weights are stored (in, out) and drawn in layer order; biases start at 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layout(cfg: dict) -> list[tuple[str, tuple[int, ...], int]]:
    """``(layer, weight shape, fan_in)`` in the order the init draws them."""
    dims = [cfg["in_dim"], *cfg["hidden"], cfg["n_classes"]]
    return [(f"fc{i}", (dims[i], dims[i + 1]), dims[i]) for i in range(len(dims) - 1)]


def forward(params: dict, x: jax.Array) -> jax.Array:
    """(B, 28, 28, 1) images → (B, 10) logits."""
    x = x.reshape(x.shape[0], -1)
    n = len(params)
    for i in range(n):
        x = jnp.dot(x, params[f"fc{i}"]["w"]) + params[f"fc{i}"]["b"]
        if i < n - 1:
            x = jnp.maximum(x, 0)
    return x


def forward_flops(cfg: dict) -> int:
    """Multiply-add FLOPs of one sample's forward pass (2 per weight)."""
    return sum(2 * s[0] * s[1] for _, s, _ in layout(cfg))
