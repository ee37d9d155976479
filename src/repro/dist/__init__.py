"""Distributed substrate: sharding specs (dist/sharding.py) and the update
compression codecs used on the FL uplink.

Compression is applied to client deltas before upload (Eq. 8's on-demand
volume composes with these): int8 symmetric quantization (per-leaf scale)
and top-k sparsification with error feedback (the dropped mass is carried
to the next round, so the compressed stream is unbiased in the limit).
``compressed_size_bytes`` is the accounting used by the comm simulator.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp


def quantize_int8(tree: Any) -> tuple[Any, Any]:
    """Per-leaf symmetric int8: scale = max|x|/127, q = round(x/scale)."""
    pairs = jax.tree.map(lambda x: _quantize_leaf(x.astype(jnp.float32)),
                         tree)
    qt = jax.tree.map(lambda p: p[0], pairs,
                      is_leaf=lambda x: isinstance(x, tuple))
    sc = jax.tree.map(lambda p: p[1], pairs,
                      is_leaf=lambda x: isinstance(x, tuple))
    return qt, sc


def dequantize_int8(qtree: Any, scales: Any) -> Any:
    return jax.tree.map(lambda q, s: q.astype(jnp.float32) * s, qtree, scales)


def _quantize_leaf(x32):
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_int8_ef(tree: Any, error: Any | None = None
                     ) -> tuple[Any, Any, Any]:
    """Int8 quantization with error feedback (client-side state).

    Quantizes ``tree + error`` and returns ``(qtree, scales, residual)``
    where residual = (tree + error) - dequant(qtree) is the next round's
    ``error``. Summed over rounds, the dequantized uploads telescope to the
    uncompressed stream minus the final residual, so the compressed uplink
    is unbiased in the limit (same contract as ``topk_sparsify``).
    """
    if error is None:
        error = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tree)

    def q(x, e):
        x32 = x.astype(jnp.float32) + e
        qv, scale = _quantize_leaf(x32)
        return qv, scale, x32 - qv.astype(jnp.float32) * scale

    triples = jax.tree.map(q, tree, error)
    is_t = lambda x: isinstance(x, tuple)  # noqa: E731
    return tuple(jax.tree.map(lambda p, i=i: p[i], triples, is_leaf=is_t)
                 for i in range(3))


def quantize_int8_stacked(tree: Any, error: Any | None = None
                          ) -> tuple[Any, Any, Any]:
    """Per-client int8 for client-stacked trees ([K, ...] leaves).

    One symmetric scale per (client, leaf) — scales leaves are [K] — so a
    whole dispatch batch quantizes in one vectorized shot; this is the
    uplink layout ``aggregation.cohort_reduce(..., scales=)`` ingests
    natively.
    ``error`` ([K, ...] residual stack) carries per-client error feedback.
    Returns ``(qtree, scales, residual)`` like ``quantize_int8_ef``.
    """
    def q(x, e):
        x32 = x.astype(jnp.float32) + (0.0 if e is None else e)
        red = tuple(range(1, x32.ndim))
        scale = jnp.maximum(jnp.max(jnp.abs(x32), axis=red), 1e-12) / 127.0
        sb = scale.reshape((-1,) + (1,) * (x32.ndim - 1))
        qv = jnp.clip(jnp.round(x32 / sb), -127, 127).astype(jnp.int8)
        return qv, scale, x32 - qv.astype(jnp.float32) * sb

    if error is None:
        triples = jax.tree.map(lambda x: q(x, None), tree)
    else:
        triples = jax.tree.map(q, tree, error)
    is_t = lambda x: isinstance(x, tuple)  # noqa: E731
    return tuple(jax.tree.map(lambda p, i=i: p[i], triples, is_leaf=is_t)
                 for i in range(3))


def dequantize_int8_stacked(qtree: Any, scales: Any) -> Any:
    """Inverse of ``quantize_int8_stacked`` ([K] scale leaves broadcast)."""
    return jax.tree.map(
        lambda q, s: q.astype(jnp.float32)
        * s.reshape((-1,) + (1,) * (q.ndim - 1)), qtree, scales)


def topk_sparsify(tree: Any, frac: float, error: Any | None = None
                  ) -> tuple[Any, Any]:
    """Magnitude top-k with error feedback.

    Keeps ceil(frac * size) entries per leaf of ``tree + error``; the
    residual (dropped mass) is returned as the next round's ``error``.
    """
    if error is None:
        error = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tree)

    def sp(x, e):
        x32 = x.astype(jnp.float32) + e
        flat = x32.reshape(-1)
        k = max(1, int(math.ceil(frac * flat.size)))
        thresh = jnp.sort(jnp.abs(flat))[-k]
        mask = (jnp.abs(flat) >= thresh).astype(jnp.float32)
        # break ties deterministically: keep at most k (first-come in sort)
        sparse = (flat * mask).reshape(x32.shape)
        return sparse, x32 - sparse

    pairs = jax.tree.map(sp, tree, error)
    sparse = jax.tree.map(lambda p: p[0], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
    err = jax.tree.map(lambda p: p[1], pairs,
                       is_leaf=lambda x: isinstance(x, tuple))
    return sparse, err


def compressed_size_bytes(tree: Any, mode: str, frac: float | None = None
                          ) -> int:
    """Uplink bytes for one update under a codec.

    none: 4B/param. int8: 1B/param + 4B scale per leaf. topk: kept values
    as (4B value + 4B index) pairs.
    """
    leaves = jax.tree.leaves(tree)
    if mode == "none":
        return sum(4 * x.size for x in leaves)
    if mode == "int8":
        return sum(x.size + 4 for x in leaves)
    if mode == "topk":
        assert frac is not None
        return sum(8 * max(1, int(math.ceil(frac * x.size))) for x in leaves)
    raise ValueError(mode)
