"""The paper's own architectures (Appendix A):

* MLP    — 4 fully-connected layers (512, 256, 128 hidden; 10 out), ReLU.
* CNN    — 3 conv layers (32/64/64 ch, 3×3, pad 1) + FC 128, 64, out.
* VGG16  — Simonyan & Zisserman cfg-D, with a width multiplier for
           CPU-tractable validation runs (published widths at 1.0).

All weights go through the gain-corrected He initialiser — these are the
models Figures 1–4, 6, 7 are made with.  The CNN and VGG forwards name
their device scopes (``repro.obs.trace.SCOPES``): ``model_conv`` (one
convolution with its bias and ReLU), ``model_pool`` (one 2×2 max-pool) and
``model_dense`` (one classifier layer); the MLP's forward has none.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core.initialisation import InitConfig, scaled_init
from .common import KeyGen

PyTree = Any

__all__ = ["init_mlp", "mlp_forward", "init_cnn", "cnn_forward", "init_vgg16", "vgg16_forward", "classifier_loss", "accuracy"]


# ----------------------------------------------------------------- MLP
def init_mlp(
    init_cfg: InitConfig,
    key: jax.Array,
    in_dim: int = 784,
    hidden: Sequence[int] = (512, 256, 128),
    n_classes: int = 10,
) -> PyTree:
    kg = KeyGen(key)
    dims = [in_dim, *hidden, n_classes]
    return {
        f"fc{i}": {
            "w": scaled_init(init_cfg, kg(), (dims[i], dims[i + 1]), jnp.float32),
            "b": jnp.zeros((dims[i + 1],), jnp.float32),
        }
        for i in range(len(dims) - 1)
    }


def mlp_forward(params: PyTree, x: jax.Array) -> jax.Array:
    """x (..., H, W, C) or (..., D) → logits (..., n_classes)."""
    n_layers = len(params)
    d_in = params["fc0"]["w"].shape[0]
    # merge however many trailing axes multiply to d_in (image → flat vector)
    if x.shape[-1] != d_in:
        k, prod = x.ndim, 1
        while prod < d_in and k > 0:
            k -= 1
            prod *= x.shape[k]
        if prod != d_in:
            raise ValueError(f"cannot flatten {x.shape} to feature dim {d_in}")
        x = x.reshape(x.shape[:k] + (d_in,))
    for i in range(n_layers):
        p = params[f"fc{i}"]
        x = x @ p["w"] + p["b"]
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    return x


# ----------------------------------------------------------------- CNN
def _conv_init(init_cfg: InitConfig, key: jax.Array, kh: int, kw: int, cin: int, cout: int) -> PyTree:
    return {
        "w": scaled_init(init_cfg, key, (kh, kw, cin, cout), jnp.float32),
        "b": jnp.zeros((cout,), jnp.float32),
    }


def _conv(p: PyTree, x: jax.Array) -> jax.Array:
    """NHWC 3×3 same conv."""
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    return y + p["b"]


def _conv_relu(p: PyTree, x: jax.Array) -> jax.Array:
    with jax.named_scope("model_conv"):
        return jax.nn.relu(_conv(p, x))


def _maxpool2(x: jax.Array) -> jax.Array:
    with jax.named_scope("model_pool"):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _dense(p: PyTree, x: jax.Array, relu: bool) -> jax.Array:
    with jax.named_scope("model_dense"):
        x = x @ p["w"] + p["b"]
        return jax.nn.relu(x) if relu else x


def init_cnn(
    init_cfg: InitConfig,
    key: jax.Array,
    image_shape: tuple[int, int, int] = (32, 32, 10),
    channels: Sequence[int] = (32, 64, 64),
    fc_hidden: Sequence[int] = (128, 64),
    n_classes: int = 17,
) -> PyTree:
    kg = KeyGen(key)
    h, w, cin = image_shape
    params: PyTree = {}
    c_prev = cin
    for i, c in enumerate(channels):
        params[f"conv{i}"] = _conv_init(init_cfg, kg(), 3, 3, c_prev, c)
        c_prev = c
        h, w = h // 2, w // 2  # one maxpool per conv
    dims = [h * w * c_prev, *fc_hidden, n_classes]
    for i in range(len(dims) - 1):
        params[f"fc{i}"] = {
            "w": scaled_init(init_cfg, kg(), (dims[i], dims[i + 1]), jnp.float32),
            "b": jnp.zeros((dims[i + 1],), jnp.float32),
        }
    return params


def cnn_forward(params: PyTree, x: jax.Array) -> jax.Array:
    """x (B, H, W, C) → logits."""
    i = 0
    while f"conv{i}" in params:
        x = _maxpool2(_conv_relu(params[f"conv{i}"], x))
        i += 1
    x = x.reshape(x.shape[0], -1)
    i = 0
    while f"fc{i}" in params:
        x = _dense(params[f"fc{i}"], x, relu=f"fc{i+1}" in params)
        i += 1
    return x


# ----------------------------------------------------------------- VGG16
_VGG_D = (64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512, "M", 512, 512, 512, "M")


def init_vgg16(
    init_cfg: InitConfig,
    key: jax.Array,
    image_shape: tuple[int, int, int] = (32, 32, 3),
    n_classes: int = 10,
    width_mult: float = 1.0,
    fc_dim: int = 4096,
) -> PyTree:
    kg = KeyGen(key)
    h, w, cin = image_shape
    params: PyTree = {}
    c_prev = cin
    conv_i = 0
    for entry in _VGG_D:
        if entry == "M":
            h, w = h // 2, w // 2
            continue
        c = max(8, int(entry * width_mult))
        params[f"conv{conv_i}"] = _conv_init(init_cfg, kg(), 3, 3, c_prev, c)
        c_prev = c
        conv_i += 1
    fdim = max(16, int(fc_dim * width_mult))
    dims = [h * w * c_prev, fdim, fdim, n_classes]
    for i in range(3):
        params[f"fc{i}"] = {
            "w": scaled_init(init_cfg, kg(), (dims[i], dims[i + 1]), jnp.float32),
            "b": jnp.zeros((dims[i + 1],), jnp.float32),
        }
    return params


def vgg16_forward(params: PyTree, x: jax.Array) -> jax.Array:
    conv_i = 0
    for entry in _VGG_D:
        if entry == "M":
            x = _maxpool2(x)
            continue
        x = _conv_relu(params[f"conv{conv_i}"], x)
        conv_i += 1
    x = x.reshape(x.shape[0], -1)
    for i in range(3):
        x = _dense(params[f"fc{i}"], x, relu=i < 2)
    return x


# ----------------------------------------------------------------- losses
def classifier_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy (the paper's test metric is exactly this)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return (logits.argmax(-1) == labels).mean()
