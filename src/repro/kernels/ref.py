"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_l2_ref(x: jnp.ndarray, y: jnp.ndarray, squared: bool = False) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    sq = (
        jnp.sum(x * x, axis=1)[:, None]
        + jnp.sum(y * y, axis=1)[None, :]
        - 2.0 * jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    )
    sq = jnp.maximum(sq, 0.0)
    return sq if squared else jnp.sqrt(sq)


def masked_pairwise_l2_ref(
    x: jnp.ndarray, y: jnp.ndarray, tile_mask: jnp.ndarray, bm: int, bn: int,
    squared: bool = False,
) -> jnp.ndarray:
    d = pairwise_l2_ref(x, y, squared=squared)
    mrep = jnp.repeat(jnp.repeat(tile_mask != 0, bm, axis=0), bn, axis=1)
    mrep = mrep[: d.shape[0], : d.shape[1]]
    return jnp.where(mrep, d, jnp.inf)


def planar_lower_bound_ref(
    d1: jnp.ndarray, d2: jnp.ndarray, deltas: jnp.ndarray, boxes: jnp.ndarray
) -> jnp.ndarray:
    from repro.core.constants import DEGENERATE_DELTA, MIN_DELTA

    d1 = d1.astype(jnp.float32)
    d2 = d2.astype(jnp.float32)
    raw = deltas.astype(jnp.float32)[None, :]
    delta = jnp.maximum(raw, MIN_DELTA)
    qx = jnp.where(
        raw < DEGENERATE_DELTA, 0.0, (d1 * d1 - d2 * d2) / (2.0 * delta)
    )
    qy = jnp.sqrt(jnp.maximum(d1 * d1 - (qx + delta / 2.0) ** 2, 0.0))
    # boxes are planes-major (4, M, B): per-plane bound (Q, M, B)
    qxe = qx[:, :, None]
    qye = qy[:, :, None]
    dx = jnp.maximum(jnp.maximum(boxes[0] - qxe, qxe - boxes[1]), 0.0)
    dy = jnp.maximum(jnp.maximum(boxes[2] - qye, qye - boxes[3]), 0.0)
    return jnp.max(jnp.sqrt(dx * dx + dy * dy), axis=1)


def pairwise_jsd_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    _EPS = 1e-12

    def xlogx(v):
        return jnp.where(v > _EPS, v * jnp.log(jnp.maximum(v, _EPS)), 0.0)

    x = x.astype(jnp.float32)[:, None, :]
    y = y.astype(jnp.float32)[None, :, :]
    m = 0.5 * (x + y)
    js = jnp.sum(0.5 * xlogx(x) + 0.5 * xlogx(y) - xlogx(m), axis=-1)
    return jnp.sqrt(jnp.maximum(js, 0.0) / jnp.log(2.0))


def pairwise_tri_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    _EPS = 1e-12
    x = x.astype(jnp.float32)[:, None, :]
    y = y.astype(jnp.float32)[None, :, :]
    num = (x - y) ** 2
    den = jnp.maximum(x + y, _EPS)
    return jnp.sqrt(jnp.maximum(0.5 * jnp.sum(num / den, axis=-1), 0.0))


def masked_pairwise_metric_ref(
    dense: jnp.ndarray, tile_mask: jnp.ndarray, bm: int, bn: int
) -> jnp.ndarray:
    """Apply the tile mask to a dense (m, n) distance matrix from any of the
    ``*_ref`` pairwise oracles — the reference for the masked family."""
    mrep = jnp.repeat(jnp.repeat(tile_mask != 0, bm, axis=0), bn, axis=1)
    mrep = mrep[: dense.shape[0], : dense.shape[1]]
    return jnp.where(mrep, dense, jnp.inf)
