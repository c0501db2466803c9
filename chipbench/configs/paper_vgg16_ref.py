"""Plain reference of ``paper_vgg16``: VGG16 configuration D in jax.numpy.

Thirteen 3×3 SAME convolutions (NHWC images, HWIO kernels) with bias and
ReLU, a 2×2 max-pool after each of the five blocks, written as a reshape
and a max, then the classifier on the flattened map.  Weights are drawn in
layer order, convolutions first; biases start at 0.  The widths are the
configuration's own copy (``conv``, ``fc``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# VGG16-D pools after the last convolution of each block: blocks of 2, 2, 3, 3, 3
POOL_AFTER = (1, 3, 6, 9, 12)


def _convs(cfg: dict) -> list[tuple[int, int, int]]:
    """``(spatial size, cin, cout)`` of each convolution, in order."""
    h, _, cin = cfg["dataset"]["image_shape"]
    out, pools = [], []
    for c in cfg["conv"]:
        if c == "M":
            pools.append(len(out) - 1)
            h //= 2
            continue
        out.append((h, cin, c))
        cin = c
    if tuple(pools) != POOL_AFTER:
        raise ValueError(f"not a VGG16-D layer list: pools after {pools}")
    return out


def layout(cfg: dict) -> list[tuple[str, tuple[int, ...], int]]:
    """``(layer, weight shape, fan_in)`` in the order the init draws them."""
    convs = [(f"conv{i}", (3, 3, cin, cout), 9 * cin) for i, (_, cin, cout) in enumerate(_convs(cfg))]
    fc = cfg["fc"]
    return convs + [(f"fc{i}", (fc[i], fc[i + 1]), fc[i]) for i in range(len(fc) - 1)]


def _pool(x: jax.Array) -> jax.Array:
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(params: dict, x: jax.Array) -> jax.Array:
    """(B, 32, 32, 3) images → (B, 10) logits."""
    i = 0
    while f"conv{i}" in params:
        p = params[f"conv{i}"]
        x = jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )
        x = jnp.maximum(x + p["b"], 0)
        if i in POOL_AFTER:
            x = _pool(x)
        i += 1
    x = x.reshape(x.shape[0], -1)
    n = sum(1 for k in params if k.startswith("fc"))
    for i in range(n):
        x = jnp.dot(x, params[f"fc{i}"]["w"]) + params[f"fc{i}"]["b"]
        if i < n - 1:
            x = jnp.maximum(x, 0)
    return x


def conv_forward_flops(cfg: dict) -> int:
    """Multiply-add FLOPs of one sample's convolutions (2 per weight per
    output pixel)."""
    return sum(2 * h * h * 9 * cin * cout for h, cin, cout in _convs(cfg))


def forward_flops(cfg: dict) -> int:
    """Multiply-add FLOPs of one sample's forward pass: convolutions and
    classifier."""
    fc = cfg["fc"]
    return conv_forward_flops(cfg) + sum(2 * fc[i] * fc[i + 1] for i in range(len(fc) - 1))


def conv_train_flops(cfg: dict) -> int:
    """FLOPs of one sample's convolutions in a training step: forward,
    input gradient and weight gradient of every layer (each as many as its
    forward), less the first layer's input gradient, which is never
    computed."""
    h, cin, cout = _convs(cfg)[0]
    return 3 * conv_forward_flops(cfg) - 2 * h * h * 9 * cin * cout
