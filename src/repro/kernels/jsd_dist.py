"""Pallas TPU kernel: blocked pairwise Jensen-Shannon distance.

Unlike l2 (MXU matmul identity), JSD has no contraction form — it is a
transcendental-heavy VPU workload:

    JS(x, y) = sum_i [ x_i/2 log x_i + y_i/2 log y_i - m_i log m_i ],
    m = (x+y)/2;   d = sqrt(JS / ln 2)

Tiling: grid over (M/bm, N/bn) output tiles; each cell streams an (bm, K)
X tile and (bn, K) Y tile into VMEM and loops the pair reduction on the VPU.
The x-entropy term depends only on x (resp. y) — precomputed per tile to
avoid recomputing it bn (resp. bm) times.  The mixture-entropy broadcast is
reduced in K-chunks of ``_K_CHUNK`` lanes so the (bm, bn, Kc) transient is
bounded at 8 MiB even for 128x128 tiles (the BSS masked exact phase ties
bm/bn to the query-tile / block sizes) and large metric-space dims.

Dtype-parametrised like the rest of the family (``pairwise_dist``, "Mixed
precision"): operands stream at their storage dtype and the tile kernel
upcasts to fp32 on entry, so a bfloat16 Y (the engines' bf16 corpus
mirror) halves the streamed bytes while the log/entropy arithmetic and
accumulation stay fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiles import TILE_KCHUNK, TILE_VPU

__all__ = ["pairwise_jsd_kernel_call"]

_EPS = 1e-12
# lanes reduced per VPU pass; bounds the (bm, bn, Kc) transient.
# Overridable via REPRO_TILE_KCHUNK (repro.kernels.tiles).
_K_CHUNK = TILE_KCHUNK


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _xlogx(v):
    return jnp.where(v > _EPS, v * jnp.log(jnp.maximum(v, _EPS)), 0.0)


def _jsd_tile_kernel(x_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (bm, K)
    y = y_ref[...].astype(jnp.float32)  # (bn, K)
    k = x.shape[1]
    hx = jnp.sum(_xlogx(x), axis=1)  # (bm,) entropy terms, computed once
    hy = jnp.sum(_xlogx(y), axis=1)  # (bn,)
    hm = jnp.zeros((x.shape[0], y.shape[0]), jnp.float32)  # (bm, bn)
    for k0 in range(0, k, _K_CHUNK):  # static K => unrolled at trace time
        # chunks are sliced from the REFS (2-D loads): the TPU compiler
        # refuses a lane slice of the 3-D broadcast value
        xs = x_ref[:, k0 : k0 + _K_CHUNK].astype(jnp.float32)
        ys = y_ref[:, k0 : k0 + _K_CHUNK].astype(jnp.float32)
        m = 0.5 * (xs[:, None, :] + ys[None, :, :])
        hm = hm + jnp.sum(_xlogx(m), axis=-1)
    js = 0.5 * hx[:, None] + 0.5 * hy[None, :] - hm
    o_ref[...] = jnp.sqrt(jnp.maximum(js, 0.0) / jnp.log(2.0))


def _pad_to(a, mult, axis):
    rem = a.shape[axis] % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(a, pad)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def pairwise_jsd_kernel_call(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    bm: int = TILE_VPU,
    bn: int = TILE_VPU,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(m, K), (n, K) probability vectors -> (m, n) JS distance matrix."""
    if interpret is None:
        interpret = _interpret_default()
    m, k = x.shape
    n, _ = y.shape
    # padding rows are all-zero -> valid inputs for the xlogx guard
    xp = _pad_to(x, bm, 0)
    yp = _pad_to(y, bn, 0)
    grid = (xp.shape[0] // bm, yp.shape[0] // bn)
    out = pl.pallas_call(
        _jsd_tile_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], yp.shape[0]), jnp.float32),
        interpret=interpret,
    )(xp, yp)
    return out[:m, :n]
