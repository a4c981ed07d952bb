"""l2, Euclidean distance: the yardstick's view of one metric.

* Reference: float64, ``sqrt(|q|^2 + |c|^2 - 2 q.c)``, clipped at 0.
* Control: the configuration states float32 contractions at ``HIGHEST``;
  the control takes the dot products at ``HIGH``, three bfloat16 passes.
  Written out as its three passes (each operand split into a bfloat16
  high part and a bfloat16 remainder, the remainder-by-remainder product
  dropped), so that it computes the same on the chip and on a CPU, which
  ignores the precision flag.  Each rounding to bfloat16 is a
  ``lax.reduce_precision``: a float32 -> bfloat16 -> float32 pair of
  converts may be removed by XLA.
* Work: one dot product of ``dim`` multiply-adds (``2 * dim`` operations)
  per distance, on the MXU, bounded by its published bf16 peak, which no
  float32 contraction can beat.
"""

from __future__ import annotations

import numpy as np

OPS_PEAK = "mxu_bf16_flops_per_s"  # key in peaks.json
QUERY_BLOCK = 64  # reference queries per block
CONTROL_QUERY_BLOCK = None  # the control takes every query at once


def ops(dim: int) -> int:
    """Operations per distance."""
    return 2 * dim


def pair_elems(dim: int) -> int:
    """float64 elements of the reference's temporaries per (query, row)."""
    return 1


def row_terms(c: np.ndarray) -> np.ndarray:
    return np.sum(c * c, axis=1)


def reference(q: np.ndarray, c: np.ndarray, c_sq: np.ndarray) -> np.ndarray:
    g = q @ c.T
    g *= -2.0
    g += c_sq[None, :]
    g += np.sum(q * q, axis=1)[:, None]
    np.maximum(g, 0.0, out=g)
    return np.sqrt(g, out=g)


def control():
    """(q, c) -> float32 distances at the next precision down."""
    import jax
    import jax.numpy as jnp

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def dot(a, b):
        # operands that are bfloat16 values: every product is exact, sums
        # are float32 (one MXU pass each on the chip)
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    def l2_high(q, c):
        qh, ch = bf16(q), bf16(c)
        ql, cl = bf16(q - qh), bf16(c - ch)
        qc = dot(qh, ch) + dot(qh, cl) + dot(ql, ch)
        sq = (jnp.sum(q * q, axis=1)[:, None] + jnp.sum(c * c, axis=1)[None]
              - 2.0 * qc)
        return jnp.sqrt(jnp.maximum(sq, 0.0))

    return jax.jit(l2_high)
