"""Pallas TPU kernel: fused tetrahedral projection + block lower-bounding.

Computes, for a tile of queries and a tile of BSS blocks, the planar
lower-bound matrix

    lb[q, b] = max_m  dist2d( proj_m(q), box[:, m, b] )

fusing (i) apex projection of the query onto every pivot-pair plane
(paper §3, Eq. in Fig. 4), (ii) point-to-rectangle distance, (iii) the
max-reduction over planes — one HBM read of the query-pivot distances and
the box table, one write of the bound.  Pure VPU work (no MXU).

Layout: ``boxes`` is planes-major, ``(4, M, B)`` = (x_lo, x_hi, y_lo, y_hi)
x plane x block, so a block tile is lane-dense over blocks and each plane's
box edges are one ``(1, bb)`` row.  The kernel loops statically over the M
planes with a running ``(bq, bb)`` max, so every intermediate stays 2-D: a
``(bq, bb, M)`` broadcast would pad M=24 to 128 lanes (8 MiB per fp32
temporary), and reading a ``(bb, M, 4)`` block's last axis is a 3-D gather
the TPU compiler refuses.

VMEM @ bq=bb=128, M=24: d1/d2 2*12 KiB + boxes 4*24*128*4 = 48 KiB + a few
(128, 128) fp32 temporaries (64 KiB each).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.constants import DEGENERATE_DELTA, MIN_DELTA
from repro.kernels.tiles import TILE_BLOCK, TILE_BQ

__all__ = ["planar_lower_bound_kernel_call", "EMPTY_BOX"]

# overridable via REPRO_TILE_BQ / REPRO_TILE_BLOCK (repro.kernels.tiles)
DEFAULT_BQ = TILE_BQ
DEFAULT_BB = TILE_BLOCK

# (x_lo, x_hi, y_lo, y_hi) of a box no point projects into: its planar
# distance overflows to +inf in float32, so the block is excluded at any
# finite radius (padding blocks, fully invalid blocks)
EMPTY_BOX = (3.4e38, -3.4e38, 3.4e38, -3.4e38)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _lb_tile_kernel(d1_ref, d2_ref, delta_ref, boxes_ref, o_ref):
    d1 = d1_ref[...].astype(jnp.float32)  # (bq, M)
    d2 = d2_ref[...].astype(jnp.float32)  # (bq, M)
    raw = delta_ref[...].astype(jnp.float32)  # (1, M)
    delta = jnp.maximum(raw, MIN_DELTA)

    # apex projection (fused; never leaves VMEM); degenerate planes use the
    # ring bound x=0 — must match projection.project / ref exactly
    qx = jnp.where(
        raw < DEGENERATE_DELTA, 0.0, (d1 * d1 - d2 * d2) / (2.0 * delta)
    )  # (bq, M)
    qy = jnp.sqrt(jnp.maximum(d1 * d1 - (qx + delta / 2.0) ** 2, 0.0))

    lb = None
    for m in range(d1.shape[1]):  # static M => unrolled at trace time
        qxm = qx[:, m : m + 1]  # (bq, 1)
        qym = qy[:, m : m + 1]
        x_lo = boxes_ref[0, m : m + 1, :]  # (1, bb)
        x_hi = boxes_ref[1, m : m + 1, :]
        y_lo = boxes_ref[2, m : m + 1, :]
        y_hi = boxes_ref[3, m : m + 1, :]
        dx = jnp.maximum(jnp.maximum(x_lo - qxm, qxm - x_hi), 0.0)
        dy = jnp.maximum(jnp.maximum(y_lo - qym, qym - y_hi), 0.0)
        plane = jnp.sqrt(dx * dx + dy * dy)  # (bq, bb)
        lb = plane if lb is None else jnp.maximum(lb, plane)
    o_ref[...] = lb


def _pad_to(a: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    rem = a.shape[axis] % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(a, pad)


@functools.partial(jax.jit, static_argnames=("bq", "bb", "interpret"))
def planar_lower_bound_kernel_call(
    d1: jnp.ndarray,
    d2: jnp.ndarray,
    deltas: jnp.ndarray,
    boxes: jnp.ndarray,
    *,
    bq: int = DEFAULT_BQ,
    bb: int = DEFAULT_BB,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """d1, d2: (Q, M) query distances to each plane's two pivots;
    deltas: (M,); boxes: (4, M, B) planes-major.  Returns (Q, B) lower
    bounds.

    Padding blocks get ``EMPTY_BOX`` (bound +inf), so padded cells never
    survive.
    """
    if interpret is None:
        interpret = _interpret_default()
    q, m = d1.shape
    b = boxes.shape[2]
    d1p = _pad_to(d1, bq, 0)
    d2p = _pad_to(d2, bq, 0)
    if b % bb:
        fill = jnp.broadcast_to(
            jnp.asarray(EMPTY_BOX, jnp.float32)[:, None, None],
            (4, m, bb - b % bb),
        )
        boxesp = jnp.concatenate([boxes, fill], axis=2)
    else:
        boxesp = boxes
    qp, bp = d1p.shape[0], boxesp.shape[2]
    grid = (qp // bq, bp // bb)
    out = pl.pallas_call(
        _lb_tile_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, m), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, m), lambda i, j: (i, 0)),
            pl.BlockSpec((1, m), lambda i, j: (0, 0)),
            pl.BlockSpec((4, m, bb), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, bp), jnp.float32),
        interpret=interpret,
    )(d1p, d2p, deltas[None, :], boxesp)
    return out[:q, :b]
