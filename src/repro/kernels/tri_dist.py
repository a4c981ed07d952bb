"""Pallas TPU tile kernel: blocked pairwise Triangular distance.

Triangular discrimination has no MXU contraction form — like JSD it is a
pure-VPU broadcast reduction over probability vectors:

    d(x, y) = sqrt( 0.5 * sum_i (x_i - y_i)^2 / (x_i + y_i) )

The (bm, bn, Kc) broadcast is reduced in K-chunks of ``_K_CHUNK`` lanes, so
the VMEM transient never exceeds bm*bn*_K_CHUNK*4 bytes (8 MiB at 128x128
tiles) regardless of the metric-space dimension.

Padding rows are all-zero: (0-0)^2 / max(0+0, eps) = 0, a valid input —
padded cells are sliced away by the caller / masked by the BSS valid mask.

This module holds only the tile kernel; the grid/padding plumbing and the
jitted entry points live in ``pairwise_dist`` (``pairwise_kernel_call`` /
``masked_pairwise_kernel_call`` dispatch on ``"triangular"``) so every
metric shares one copy of the call machinery.

Dtype-parametrised like the rest of the family (``pairwise_dist``, "Mixed
precision"): operands stream at their storage dtype and the tile kernel
upcasts to fp32 on entry, so a bfloat16 Y (the engines' bf16 corpus
mirror) halves the streamed bytes while the division and accumulation
stay fp32.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.tiles import TILE_KCHUNK

__all__ = ["_tri_tile_kernel"]

_EPS = 1e-12
# lanes reduced per VPU pass; bounds the (bm, bn, Kc) transient.
# Overridable via REPRO_TILE_KCHUNK (repro.kernels.tiles).
_K_CHUNK = TILE_KCHUNK


def _tri_tile_kernel(x_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (bm, K)
    y = y_ref[...].astype(jnp.float32)  # (bn, K)
    k = x.shape[1]
    acc = jnp.zeros((x.shape[0], y.shape[0]), jnp.float32)
    for k0 in range(0, k, _K_CHUNK):  # static K => unrolled at trace time
        # chunks are sliced from the REFS (2-D loads): the TPU compiler
        # refuses a lane slice of the 3-D broadcast value
        xs = x_ref[:, k0 : k0 + _K_CHUNK].astype(jnp.float32)[:, None, :]
        ys = y_ref[:, k0 : k0 + _K_CHUNK].astype(jnp.float32)[None, :, :]
        num = (xs - ys) ** 2
        den = jnp.maximum(xs + ys, _EPS)
        acc = acc + jnp.sum(num / den, axis=-1)
    o_ref[...] = jnp.sqrt(jnp.maximum(0.5 * acc, 0.0))
