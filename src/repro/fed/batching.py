"""Each round's per-node minibatches, drawn on the device (DESIGN.md §11).

The fused executors keep every node's items on the device and draw a
round's minibatches by index, from the precomputed batch schedule.
``draw_batch`` is the one place that does it, in one of two renderings that
``select_rendering`` picks from what is known when the program is traced:

* ``"gather"``: ``xs[node, idx]``, which the TPU paces by the items it
  copies, not by their bytes;
* ``"onehot"``: per node, the (drawn, items) one-hot selection matrix
  contracted on the MXU with the node's items laid out lane-dense as
  (items, F), F = prod(item_shape).  Its cost grows with items × drawn × F,
  so it wins while a node holds few items per item drawn.

The contraction returns ``xs[node, idx]`` as the gather does: the one-hot
is exact in bf16, an f32 item is the sum of the bf16 parts that
``HIGHEST`` multiplies, and every other term is 0 · x = 0.  On a TPU v5e
no bit differed on image data at unit scale and scaled over 40 binades;
what does not come back whole is ``-0.0`` (returned as ``+0.0``),
magnitudes below ≈ 1e-33 (whose low bf16 part underflows) and subnormals
(flushed to zero) (DESIGN.md §11).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.trace import count

__all__ = ["draw_batch", "lane_dense", "select_rendering"]

# On a TPU the one-hot contraction costs ~ items·drawn·F MXU work per node
# and the gather ~ drawn items copied: the contraction stays cheaper while a
# node holds at most this many items per item drawn in a round.  A sweep of
# both renderings alone on a TPU v5e (PERF.md §6) puts the crossover at 2
# for F = 3072; for F = 784 the contraction stayed faster up to 256.
_TPU_ONEHOT_KAPPA = 2

# dtypes the contraction renders: bf16 in one pass, f32 at HIGHEST
_ONEHOT_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def select_rendering(platform: str, dtype, items: int, drawn: int) -> str:
    """The rendering ``draw_batch`` uses for items of ``dtype`` when each
    node holds ``items`` and draws ``drawn`` a round, on ``platform``
    (``jax.default_backend()``): the one-hot contraction on a TPU while
    ``items ≤ κ·drawn`` for f32 and bf16 items, the gather otherwise (integer
    items such as token ids, many items per node, and every other
    platform)."""
    if (
        platform == "tpu"
        and jnp.dtype(dtype) in _ONEHOT_DTYPES
        and items <= _TPU_ONEHOT_KAPPA * drawn
    ):
        return "onehot"
    return "gather"


def lane_dense(xs: jax.Array) -> tuple[jax.Array, tuple]:
    """The node datasets ``xs`` (n, items, *item_shape) as ``draw_batch``
    reads them, (n, items, F) with F = prod(item_shape), and item_shape.
    Call it once, outside the round loop: on a TPU it is a relayout copy."""
    return xs.reshape(xs.shape[0], xs.shape[1], -1), tuple(xs.shape[2:])


def _onehot_select(xs: jax.Array, flat: jax.Array) -> jax.Array:
    """(n, items, F) items and (n, drawn) indices → (n, drawn, F), as a
    batched one-hot contraction."""
    sel = (flat[:, :, None] == jnp.arange(xs.shape[1], dtype=flat.dtype)).astype(xs.dtype)
    precision = (
        jax.lax.Precision.DEFAULT if xs.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    )
    return jnp.einsum(
        "ndk,nkf->ndf", sel, xs, precision=precision, preferred_element_type=xs.dtype
    )


def draw_batch(xs: jax.Array, ys: jax.Array, idx: jax.Array, item_shape: tuple):
    """A round's minibatches: ``idx`` (n, b, bs) item indices into ``xs``
    (n, items, F), the node datasets laid out by ``lane_dense``, and ``ys``
    (n, items, ...) →
    ``((n, b, bs, *item_shape), (n, b, bs, ...))``, equal to
    ``(xs[node, idx], ys[node, idx])`` (see the module's note for the values
    the contraction does not return bit for bit on a TPU).

    The rendering is resolved once per trace (``select_rendering`` on the
    default platform) and counted in ``repro.obs.trace`` as
    ``batch.select_onehot`` or ``batch.select_gather``.  The labels are
    always gathered.  Indices must lie in ``[0, items)``.

    With the one-hot contraction a non-finite item spoils every row its node
    draws in that round (0 · inf = NaN), where the gather spoils only the
    rows that draw it.
    """
    n, items = xs.shape[:2]
    flat = idx.reshape(n, -1)
    rendering = select_rendering(jax.default_backend(), xs.dtype, items, flat.shape[1])
    count(f"batch.select_{rendering}")
    node = jnp.arange(n)[:, None]
    bx = _onehot_select(xs, flat) if rendering == "onehot" else xs[node, flat]
    return (
        bx.reshape(idx.shape + tuple(item_shape)),
        ys[node, flat].reshape(idx.shape + ys.shape[2:]),
    )
