"""Distance metrics with explicit supermetric (four-point) classification.

The paper's taxonomy (Connor et al., Supermetric Search, 2017, §2.2):

* four-point property (isometrically 4-embeddable in l2^3, i.e. *supermetric*):
  Euclidean, Jensen-Shannon, Triangular, and the properly-formulated Cosine
  distance; also ``d^alpha`` for any metric ``d`` and ``0 < alpha <= 1/2``.
* NOT four-point: Manhattan (l1), Chebyshev (linf), Levenshtein.

Every metric exposes

* ``pairwise(X, Y) -> (n, m)`` distance matrix — the batched form every
  engine in this framework consumes (TPU-first design),
* ``point(x, y) -> scalar`` convenience wrapper,
* ``four_point`` — whether Hilbert exclusion / planar lower-bounding is sound.

All functions are pure jnp and jit/vmap/pjit-compatible. ``pairwise`` for
Euclidean/Cosine routes through a single matmul (MXU-friendly); the Pallas
kernel in ``repro.kernels.pairwise_dist`` implements the same contraction
with explicit VMEM tiling and is validated against these references.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = [
    "Metric",
    "METRICS",
    "get_metric",
    "l2",
    "cosine",
    "jsd",
    "triangular",
    "l1",
    "linf",
    "power_transform",
]

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class Metric:
    """A distance metric with batched evaluation and supermetric metadata."""

    name: str
    pairwise: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    four_point: bool
    # True when inputs must be probability vectors (non-negative, sum to 1).
    probability_space: bool = False

    def point(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        return self.pairwise(x[None, :], y[None, :])[0, 0]

    def to_query(self, q: jnp.ndarray, xs: jnp.ndarray) -> jnp.ndarray:
        """Distances from one query to a set of points, shape (n,)."""
        return self.pairwise(q[None, :], xs)[0]


# ---------------------------------------------------------------------------
# Supermetric distances (four-point property holds)
# ---------------------------------------------------------------------------


def _sq_norms(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(x * x, axis=-1)


def _dot_t(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """``x @ y.T`` at fp32 accuracy: the TPU's DEFAULT f32 matmul is one
    bf16 pass (~1e-3 relative error), which would move exact hits across a
    threshold and loosen the planar bounds.  A no-op on CPU."""
    return jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)


def _l2_pairwise(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """||x-y|| via the matmul identity; fp32 accumulation; clamped at 0."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    sq = _sq_norms(x)[:, None] + _sq_norms(y)[None, :] - 2.0 * _dot_t(x, y)
    return jnp.sqrt(jnp.maximum(sq, 0.0))


def _cosine_pairwise(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Proper (supermetric) Cosine distance, per Connor et al. [1]:

    the Euclidean distance between l2-normalised vectors,
    ``d(x, y) = sqrt(2 - 2 cos(x, y))``.  (The common ``1 - cos`` form is not
    even a metric; this form inherits the n-point property from l2.)
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), _EPS)
    yn = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), _EPS)
    cos = jnp.clip(_dot_t(xn, yn), -1.0, 1.0)
    return jnp.sqrt(jnp.maximum(2.0 - 2.0 * cos, 0.0))


def _xlogx(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(v > _EPS, v * jnp.log(jnp.maximum(v, _EPS)), 0.0)


def _jsd_pairwise(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Jensen-Shannon *distance* (sqrt of base-2 JS divergence).

    Defined over probability vectors; value in [0, 1].  Has the n-point
    property (Connor et al. [1], via isometric Hilbert-space embedding).
    Quadratic-memory formulation (broadcast over pairs) — the Pallas/blocked
    path tiles this; the reference keeps it simple.
    """
    x = x.astype(jnp.float32)[:, None, :]
    y = y.astype(jnp.float32)[None, :, :]
    m = 0.5 * (x + y)
    # JS = H(m) - (H(x)+H(y))/2, computed as sum of xlogx terms (natural log).
    js = jnp.sum(0.5 * _xlogx(x) + 0.5 * _xlogx(y) - _xlogx(m), axis=-1)
    js = jnp.maximum(js, 0.0) / jnp.log(2.0)  # base-2, in [0, 1]
    return jnp.sqrt(js)


def _triangular_pairwise(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Triangular distance: sqrt of (half the) triangular discrimination,

    ``d(x, y) = sqrt( 0.5 * sum_i (x_i - y_i)^2 / (x_i + y_i) )``

    over probability vectors; supermetric per Connor et al. [1].
    """
    x = x.astype(jnp.float32)[:, None, :]
    y = y.astype(jnp.float32)[None, :, :]
    num = (x - y) ** 2
    den = jnp.maximum(x + y, _EPS)
    return jnp.sqrt(jnp.maximum(0.5 * jnp.sum(num / den, axis=-1), 0.0))


# ---------------------------------------------------------------------------
# Plain-metric distances (four-point property FAILS — kept as controls)
# ---------------------------------------------------------------------------


def _l1_pairwise(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.float32)[:, None, :]
    y = y.astype(jnp.float32)[None, :, :]
    return jnp.sum(jnp.abs(x - y), axis=-1)


def _linf_pairwise(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.float32)[:, None, :]
    y = y.astype(jnp.float32)[None, :, :]
    return jnp.max(jnp.abs(x - y), axis=-1)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

l2 = Metric("l2", _l2_pairwise, four_point=True)
cosine = Metric("cosine", _cosine_pairwise, four_point=True)
jsd = Metric("jsd", _jsd_pairwise, four_point=True, probability_space=True)
triangular = Metric(
    "triangular", _triangular_pairwise, four_point=True, probability_space=True
)
l1 = Metric("l1", _l1_pairwise, four_point=False)
linf = Metric("linf", _linf_pairwise, four_point=False)

METRICS: dict[str, Metric] = {
    m.name: m for m in (l2, cosine, jsd, triangular, l1, linf)
}


def power_transform(base: Metric, alpha: float = 0.5) -> Metric:
    """``d^alpha`` for ``0 < alpha <= 1/2`` has the four-point property for
    ANY metric ``d`` (paper §2.2 item 4) — this upgrades e.g. l1 into a
    supermetric at the cost of distorting the distance distribution.

    The metric is REGISTERED: it lands in ``METRICS`` under
    ``"{base}^{alpha}"`` and gets a numpy twin in ``npdist``, so every
    engine (``build_bss``, ``build_tree``, ``pairwise_np``, benchmarks)
    accepts the name like any built-in metric."""
    if not (0.0 < alpha <= 0.5):
        raise ValueError("four-point property only guaranteed for 0 < alpha <= 1/2")

    def pw(x, y, _base=base.pairwise, _a=alpha):
        return jnp.power(jnp.maximum(_base(x, y), 0.0), _a)

    m = Metric(
        f"{base.name}^{alpha}",
        pw,
        four_point=True,
        probability_space=base.probability_space,
    )
    METRICS[m.name] = m
    # numpy twin, so the host-side engines accept the name too (late import:
    # npdist is numpy-only and must not depend on this jnp module)
    from repro.core import npdist

    npdist.register_power(base.name, alpha)
    return m


def get_metric(name: str) -> Metric:
    """Registry lookup; ``"{base}^{alpha}"`` power-transform names (e.g.
    ``"l1^0.5"``) are parsed and registered on first use."""
    if name not in METRICS and "^" in name:
        base, _, exp = name.partition("^")
        if base in METRICS:
            try:
                # parses a STATIC metric-name string at trace time
                alpha = float(exp)  # lint: disable=R2
            except ValueError:
                alpha = None
            # only canonical names register ("l1^0.5", not "l1^0.50") — a
            # failed lookup must not mutate the registry as a side effect
            if alpha is not None and f"{base}^{alpha}" == name:
                power_transform(METRICS[base], alpha)
    if name not in METRICS:
        raise KeyError(f"unknown metric {name!r}; have {sorted(METRICS)}")
    return METRICS[name]
