"""jsd, Jensen-Shannon distance (square root of the JS divergence in
bits): the yardstick's view of one metric.

* Reference: float64, ``JS = H_x/2 + H_y/2 - H_m`` with
  ``H_v = sum v log v`` and ``m = (x + y) / 2``.
* Control: the configuration states float32 on the VPU; the control
  rounds the rows to bfloat16 (``lax.reduce_precision``, which XLA may not
  drop) and computes in float32.
* Work: ``dim`` logarithms per distance on the VPU, for which the chip
  publishes no peak: bounded by bandwidth alone.
"""

from __future__ import annotations

import numpy as np

OPS_PEAK = None  # no published peak for the unit that does the work
QUERY_BLOCK = 8  # reference queries per block (the broadcast is Q x N x dim)
CONTROL_QUERY_BLOCK = 8


def ops(dim: int) -> int:
    return 0


def pair_elems(dim: int) -> int:
    """float64 elements of the reference's temporaries per (query, row)."""
    return dim


def _xlogx(v: np.ndarray) -> np.ndarray:
    return np.where(v > 1e-12, v * np.log(np.maximum(v, 1e-12)), 0.0)


def row_terms(c: np.ndarray) -> np.ndarray:
    return np.sum(_xlogx(c), axis=1)


def reference(q: np.ndarray, c: np.ndarray, hc: np.ndarray) -> np.ndarray:
    hm = np.sum(_xlogx(0.5 * (q[:, None, :] + c[None, :, :])), axis=-1)
    js = 0.5 * np.sum(_xlogx(q), axis=1)[:, None] + 0.5 * hc[None, :] - hm
    return np.sqrt(np.maximum(js, 0.0) / np.log(2.0))


def control():
    """(q, c) -> float32 distances from rows rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def xlogx(v):
        return jnp.where(v > 1e-12, v * jnp.log(jnp.maximum(v, 1e-12)), 0.0)

    def jsd_bf16(q, c):
        q, c = bf16(q), bf16(c)
        hm = jnp.sum(xlogx(0.5 * (q[:, None, :] + c[None, :, :])), axis=-1)
        js = (0.5 * jnp.sum(xlogx(q), axis=1)[:, None]
              + 0.5 * jnp.sum(xlogx(c), axis=1)[None] - hm)
        return jnp.sqrt(jnp.maximum(js, 0.0) / jnp.log(2.0))

    return jax.jit(jsd_bf16)
