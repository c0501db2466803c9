"""Payload compression for DecAvg gossip (DESIGN.md §18).

At transformer-scale parameter counts the mixing step is wire-bound, not
compute-bound: one DecAvg round moves ``degree × d_total × itemsize`` bytes
per node.  This module makes bytes-on-the-wire an *optimisable* axis for
every CommPlan backend without touching their operator algebra:

* **chunked per-leaf gossip** — every leaf is processed as fixed-size
  chunks of its per-node row (``chunk`` elements).  Chunks are the codec's
  scale granularity, and with ``stream=True`` the mix itself runs chunk by
  chunk under ``lax.map`` so no temporary larger than (n, chunk) exists per
  leaf — an n-node mix never materialises a second (n, d_total) stack.
* **int8 / fp8 quantised exchanges** — per-chunk absmax scales; what a
  node transmits is the *dequantised* value its peers would decode, so the
  operators stay linear and backend-agnostic.
* **top-k sparsification** — per chunk, only the k = ``ceil(topk_frac·c)``
  largest-|·| entries are transmitted.  ``"qtopk"`` additionally int8-
  quantises the kept values against the chunk absmax (3 bytes/entry
  instead of 6): at the same kept fraction it halves the sparse wire cost,
  which is what lets a quality-preserving fraction still clear a 4×
  reduction (the fig12 acceptance configuration is qtopk at frac 0.3).
* **error feedback** — each node carries a *mirror* ``h`` (a params-shaped
  fp32 pytree in the scan state): the copy of itself its peers hold, built
  from everything it ever transmitted.  The residual ``x − h`` is the
  accumulated untransmitted mass.  One compressed round is

      q  = C(x − h)            # the wire payload
      h' = h + q               # peers decode the same update
      x' = x + γ (M h' − h')   # delta-form gossip on the shared mirrors

  — the difference-compression scheme of CHOCO-style compressed gossip
  (PAPERS.md heterogeneity line): quantisation error scales with the
  *residual*, not the weights, so it vanishes as consensus approaches, and
  every dropped top-k coordinate is retransmitted once its residual grows.
  With an exact codec and ``gamma=1`` the update collapses to ``x' = M x``.
  ``gamma`` (the consensus step size) trades contraction speed for
  stability: quantisers run at 1.0; aggressive sparsifiers (small
  ``topk_frac``) need γ < 1 on poorly-connected graphs — the classic
  compressed-gossip trade-off, measured in tests/test_compress.py.

The **uncompressed path is bit-identical to the raw operators**: codec
``"none"`` routes straight to ``plan.mix`` / ``plan.spread`` with no delta
arithmetic, so a ``Compression()`` default changes nothing (the PR 8
parity contract).  Wire accounting lives in ``Compression.leaf_row_bytes``
— ``repro.obs.wirecost.param_row_bytes`` takes it as its ``codec_bytes=``
hook, replacing the dtype itemsize with the codec's encoding:

======  =========================================================
codec   bytes per row of a d-element leaf (C = ceil(d/chunk))
======  =========================================================
none    d · itemsize
int8    d · 1 + C · 4                  (fp32 scale per chunk)
fp8     d · 1 + C · 4                  (e4m3 payload, fp32 scale)
topk    Σ_chunks k_c · (4 + 2)         (fp32 value + uint16 idx)
qtopk   Σ_chunks k_c · (1 + 2) + C · 4 (int8 value + uint16 idx)
======  =========================================================
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

__all__ = [
    "Compression",
    "compressed_mix",
    "compressed_mix_with",
    "compressed_spread",
    "encode_decode",
    "init_residuals",
    "seed_residual",
]

CODECS = ("none", "int8", "fp8", "topk", "qtopk")
_FP8_MAX = 448.0  # float8_e4m3fn finite max
_SCALE_BYTES = 4  # fp32 scale per chunk on the wire
_TOPK_IDX_BYTES = 2  # uint16 in-chunk index (chunk <= 65536)


@dataclasses.dataclass(frozen=True)
class Compression:
    """Static codec configuration threaded through ``CommPlan.mix/spread``.

    ``chunk`` is the per-node-row chunk size in elements — the codec's
    scale granularity and, with ``stream=True``, the mix's streaming unit.
    ``topk_frac`` is the kept fraction per chunk (codec ``"topk"``).
    ``gamma`` is the consensus step size of the delta-form update.
    ``error_feedback=False`` drops the mirror update (every round
    compresses the raw weights with no memory) — for ablations only;
    memory-less compressed DecAvg stalls at the codec's noise floor.
    """

    codec: str = "none"
    chunk: int = 2048
    topk_frac: float = 0.1
    gamma: float = 1.0
    error_feedback: bool = True
    stream: bool = False

    def __post_init__(self):
        if self.codec not in CODECS:
            raise ValueError(f"unknown codec {self.codec!r}, want one of {CODECS}")
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        if self.chunk > 65536:
            # the documented wire format carries uint16 in-chunk indices
            raise ValueError(f"chunk must be <= 65536, got {self.chunk}")
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(f"topk_frac must be in (0, 1], got {self.topk_frac}")
        if not 0.0 < self.gamma <= 1.0:
            raise ValueError(f"gamma must be in (0, 1], got {self.gamma}")

    @property
    def active(self) -> bool:
        return self.codec != "none"

    # ------------------------------------------------------------ wire cost
    def topk_count(self, chunk_elems: int) -> int:
        """Entries kept in one chunk of ``chunk_elems`` elements."""
        return max(1, min(chunk_elems, math.ceil(self.topk_frac * chunk_elems)))

    def leaf_row_bytes(self, n_elems: int, dtype) -> float:
        """Wire bytes for ONE node's row of one leaf (``codec_bytes=`` hook
        of ``obs.wirecost.param_row_bytes``).  Uncompressed leaves cost
        their dtype itemsize; compressed ones cost the codec encoding plus
        per-chunk scale overhead (see the module table)."""
        if n_elems == 0:
            return 0.0
        if not self.active:
            return float(n_elems * np.dtype(dtype).itemsize)
        full, rem = divmod(n_elems, self.chunk)
        n_chunks = full + (1 if rem else 0)
        if self.codec in ("int8", "fp8"):
            return float(n_elems + n_chunks * _SCALE_BYTES)
        entries = full * self.topk_count(self.chunk)
        if rem:
            entries += self.topk_count(rem)
        if self.codec == "qtopk":
            return float(entries * (1 + _TOPK_IDX_BYTES) + n_chunks * _SCALE_BYTES)
        return float(entries * (4 + _TOPK_IDX_BYTES))


# --------------------------------------------------------------- chunk codecs
def _to_chunks(x2: jax.Array, chunk: int) -> tuple[jax.Array, int]:
    """(n, d) → (n, C, c) zero-padded; returns the padded array and d."""
    n, d = x2.shape
    c = min(chunk, d)
    pad = -d % c
    if pad:
        x2 = jnp.pad(x2, ((0, 0), (0, pad)))
    return x2.reshape(n, (d + pad) // c, c), d


def _from_chunks(q3: jax.Array, d: int) -> jax.Array:
    return q3.reshape(q3.shape[0], -1)[:, :d]


def _absmax_scale(t3: jax.Array, qmax: float) -> jax.Array:
    amax = jnp.max(jnp.abs(t3), axis=-1, keepdims=True)
    return jnp.maximum(amax, jnp.float32(1e-30)) / jnp.float32(qmax)


def _codec_int8(t3: jax.Array) -> jax.Array:
    scale = _absmax_scale(t3, 127.0)
    q = jnp.clip(jnp.round(t3 / scale), -127.0, 127.0)
    return q * scale


def _codec_fp8(t3: jax.Array) -> jax.Array:
    # normalise the chunk absmax to the e4m3 finite range, cast through the
    # real fp8 dtype (round-to-nearest-even in hardware), scale back
    scale = _absmax_scale(t3, _FP8_MAX)
    return (t3 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _codec_topk(t3: jax.Array, k: int, quantise: bool = False) -> jax.Array:
    vals, idx = jax.lax.top_k(jnp.abs(t3), k)  # (n, C, k)
    del vals
    kept = jnp.take_along_axis(t3, idx, axis=-1)
    if quantise:
        # "qtopk": int8-quantise the kept values against the chunk absmax
        # (the top-1 |value| of the full chunk), 3 wire bytes per entry
        scale = _absmax_scale(t3, 127.0)
        kept = jnp.clip(jnp.round(kept / scale), -127.0, 127.0) * scale
    n, n_chunks, _ = t3.shape
    i0 = jnp.arange(n)[:, None, None]
    i1 = jnp.arange(n_chunks)[None, :, None]
    return jnp.zeros_like(t3).at[i0, i1, idx].set(kept)


def _encode_decode_2d(x2: jax.Array, comp: Compression) -> jax.Array:
    """decode(encode(x)) of one (n, d) leaf — what the peers receive."""
    t3, d = _to_chunks(x2.astype(jnp.float32), comp.chunk)
    if comp.codec == "int8":
        q3 = _codec_int8(t3)
    elif comp.codec == "fp8":
        q3 = _codec_fp8(t3)
    elif comp.codec in ("topk", "qtopk"):
        q3 = _codec_topk(
            t3, comp.topk_count(t3.shape[-1]), quantise=comp.codec == "qtopk"
        )
    else:
        q3 = t3
    return _from_chunks(q3, d)


def encode_decode(params: PyTree, comp: Compression) -> PyTree:
    """Per-leaf decode(encode(·)) of a node-stacked pytree (fp32 out)."""
    if not comp.active:
        return params

    def one(leaf):
        q = _encode_decode_2d(leaf.reshape(leaf.shape[0], -1), comp)
        return q.reshape(leaf.shape)

    return jax.tree_util.tree_map(one, params)


# ----------------------------------------------------------- residual carry
def init_residuals(params: PyTree) -> PyTree:
    """Zero compression carry: params-shaped, fp32.  The carry holds each
    node's transmitted *mirror* h; starting from h = 0 the first round
    transmits C(x) in full (modulo the codec)."""
    return jax.tree_util.tree_map(lambda l: jnp.zeros(l.shape, jnp.float32), params)


def seed_residual(state, compression: Compression | None):
    """Attach a zero compression carry to a ``DFLState`` when the codec
    needs one (executors call this before the scan so the carry structure
    is static)."""
    if compression is None or not compression.active or state.residual is not None:
        return state
    return dataclasses.replace(state, residual=init_residuals(state.params))


def mask_rows(mask: jax.Array, new: PyTree, old: PyTree) -> PyTree:
    """Per node row: ``new`` where the (n,) bool ``mask`` holds, else ``old``."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(mask.reshape((-1,) + (1,) * (a.ndim - 1)), a, b),
        new,
        old,
    )


# ------------------------------------------------------------- mixing forms
def compressed_mix_with(
    mix_fn: Callable[[PyTree], PyTree],
    params: PyTree,
    residual: PyTree,
    comp: Compression,
    *,
    update_mask: jax.Array | None = None,
) -> tuple[PyTree, PyTree]:
    """Error-feedback delta-form gossip around ANY linear node-mixing
    operator ``mix_fn`` (CommPlan.mix, a sharded local_mix, an event_mix):

        q = C(x − h);  h' = h + q;  x' = x + γ (mix(h') − h');

    returning ``(x', h')`` — ``residual`` is the carried mirror ``h``.
    Rows where ``mix_fn`` is the identity (masked-out members, event
    non-participants) satisfy ``mix(h')_i = h'_i`` and therefore come back
    unchanged; pass ``update_mask`` ((n,) bool) to also freeze their
    mirrors — a node that transmitted nothing updated nobody's copy.

    With ``comp.stream`` the whole pipeline runs per chunk under
    ``lax.map`` — compress chunk, mix chunk, delta, mirror update — so the
    largest per-leaf temporary is (n, chunk).  ``mix_fn`` re-derives its
    failure draws from the same key for every chunk, so all chunks of a
    round ride one effective operator, identical to the unstreamed path.

    Codec ``"none"`` returns ``(mix_fn(params), residual)`` verbatim — the
    bit-identity contract of the uncompressed path.
    """
    if not comp.active:
        return mix_fn(params), residual
    if comp.stream:
        return _streamed_mix(mix_fn, params, residual, comp, update_mask)
    if comp.error_feedback:
        delta = jax.tree_util.tree_map(
            lambda x, h: x.astype(jnp.float32) - h, params, residual
        )
        h_new = jax.tree_util.tree_map(
            lambda h, qq: h + qq, residual, encode_decode(delta, comp)
        )
    else:
        # memory-less ablation: every round transmits C(x) from scratch —
        # the quantisation error never leaves, so consensus floors out
        h_new = encode_decode(
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params), comp
        )
    if update_mask is not None:
        h_new = mask_rows(update_mask, h_new, residual)
    mixed = mix_fn(h_new)
    g = jnp.float32(comp.gamma)
    out = jax.tree_util.tree_map(
        lambda x, mh, hh: (x.astype(jnp.float32) + g * (mh - hh)).astype(x.dtype),
        params,
        mixed,
        h_new,
    )
    return out, h_new


def _streamed_mix(mix_fn, params, residual, comp, update_mask):
    def one_leaf(x, h):
        shape = x.shape
        x3, d = _to_chunks(x.reshape(shape[0], -1).astype(jnp.float32), comp.chunk)
        h3, _ = _to_chunks(h.reshape(shape[0], -1), comp.chunk)
        flat = dataclasses.replace(comp, chunk=x3.shape[-1], stream=False)

        def step(xh):
            xc, hc = xh  # (n, c) one chunk of every node's row
            return compressed_mix_with(mix_fn, xc, hc, flat, update_mask=update_mask)

        out3, nh3 = jax.lax.map(step, (x3.transpose(1, 0, 2), h3.transpose(1, 0, 2)))
        out = _from_chunks(out3.transpose(1, 0, 2), d).astype(x.dtype)
        new_h = _from_chunks(nh3.transpose(1, 0, 2), d)
        return out.reshape(shape), new_h.reshape(shape)

    pairs = jax.tree_util.tree_map(one_leaf, params, residual)
    is_pair = lambda t: isinstance(t, tuple)  # noqa: E731
    out = jax.tree_util.tree_map(lambda p: p[0], pairs, is_leaf=is_pair)
    new_h = jax.tree_util.tree_map(lambda p: p[1], pairs, is_leaf=is_pair)
    return out, new_h


def compressed_mix(
    plan,
    params: PyTree,
    residual: PyTree,
    key: jax.Array | None = None,
    *,
    compression: Compression,
    round_index=None,
    active: jax.Array | None = None,
    edge_live: jax.Array | None = None,
    update_mask: jax.Array | None = None,
) -> tuple[PyTree, PyTree]:
    """One compressed DecAvg round over a CommPlan / PlanSchedule: the
    plan-aware form of :func:`compressed_mix_with`, mixing with the plan
    active at ``round_index`` (ignored by a CommPlan)."""
    return compressed_mix_with(
        lambda p: plan.select(round_index).mix(
            p, plan.round_key(key, round_index), active=active, edge_live=edge_live
        ),
        params, residual, compression, update_mask=update_mask,
    )


def compressed_spread(
    plan,
    values: jax.Array,
    residual: jax.Array,
    key: jax.Array | None = None,
    *,
    compression: Compression,
    round_index=None,
    active: jax.Array | None = None,
    edge_live: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One compressed send-form (push) round: ``v' = v + γ (Mᵀ h' − h')``.

    Because the masked ``Mᵀ`` is column-stochastic, ``sum(Mᵀ h') =
    sum(h')`` and the delta form conserves total mass *exactly* for any
    codec — the invariant push-sum estimation needs survives compression
    untouched.
    """
    spread = lambda v: plan.select(round_index).spread(  # noqa: E731
        v, plan.round_key(key, round_index), active=active, edge_live=edge_live
    )
    if not compression.active:
        return spread(values), residual
    v = jnp.asarray(values, jnp.float32)
    delta = v - residual if compression.error_feedback else v
    q = _encode_decode_2d(delta.reshape(delta.shape[0], -1), compression).reshape(
        delta.shape
    )
    h_new = (residual + q) if compression.error_feedback else q
    out = v + jnp.float32(compression.gamma) * (spread(h_new) - h_new)
    return out, h_new
