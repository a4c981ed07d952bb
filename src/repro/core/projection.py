"""Tetrahedral projection onto a plane (paper §3) — the core geometric tool.

Given two pivots ``p1, p2`` with inter-pivot distance ``delta`` and a point
``s`` with measured distances ``d1 = d(s, p1)``, ``d2 = d(s, p2)``, the point
projects to the apex of the triangle with base
``p1 = (-delta/2, 0), p2 = (+delta/2, 0)``:

    x = (d1^2 - d2^2) / (2 * delta)
    y = sqrt(max(d1^2 - (x + delta/2)^2, 0))          (upper half-plane)

**Lower-bound theorem (paper §3, Fig. 3/4).**  If the space has the
four-point property then for any two points ``s, u``

    l2( proj(s), proj(u) ) <= d(s, u)

so any partition of the plane yields a sound exclusion rule: a query farther
than ``t`` (in the plane) from a region cannot have solutions inside it.
Hilbert exclusion is the special case of the vertical line ``x = 0``.

All functions take an ``xp`` array namespace: ``jax.numpy`` (default —
float32, jit/vmap-friendly, shapes broadcast over leading dims) or ``numpy``
(host dtype preserved, i.e. the float64 tree walks).  The host twins used to
be re-derived in ``core/lrt.py`` and ``core/flat_index.py``; they now share
THIS body, so the degenerate-plane handling (the PR 2 duplicate-pivot fix)
cannot drift between engines.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.constants import DEGENERATE_DELTA, MIN_DELTA

__all__ = [
    "project",
    "project_x",
    "rotate",
    "planar_lower_bound",
    "point_to_interval",
    "point_to_box",
]


def _coerce(xp, *arrays):
    """THE dtype policy for every xp-generic geometry function (here and in
    ``core/exclusion.py``): jnp computes in float32 (the engines' dtype);
    numpy keeps the host dtype (float64 walks, float32 index build)."""
    if xp is jnp:
        return tuple(jnp.asarray(a, jnp.float32) for a in arrays)
    return tuple(xp.asarray(a) for a in arrays)


def project(d1, d2, delta, *, xp=jnp):
    """Planar apex coordinates for distances (d1, d2) w.r.t. pivot gap delta.

    Broadcasts over any leading shape.  Degenerate triangles (numerical noise
    making d1 + d2 < delta) are clamped onto the X-axis, which keeps the
    lower-bound property (clamping can only *reduce* planar distances).

    Degenerate PLANES (delta below ``DEGENERATE_DELTA``: duplicate or
    near-duplicate pivots) project to the ring (x=0, y=d1) — the sound
    triangle-inequality bound — instead of dividing float noise by a tiny
    baseline (see ``repro.core.constants``).
    """
    d1, d2, raw = _coerce(xp, d1, d2, delta)
    delta = xp.maximum(raw, MIN_DELTA)
    x = xp.where(
        raw < DEGENERATE_DELTA, 0.0, (d1 * d1 - d2 * d2) / (2.0 * delta)
    )
    y_sq = d1 * d1 - (x + delta / 2.0) ** 2
    y = xp.sqrt(xp.maximum(y_sq, 0.0))
    return x, y


def project_x(d1, d2, delta, *, xp=jnp):
    """X coordinate only — this is the Hilbert-exclusion quantity
    ``(d1^2 - d2^2) / (2 delta)`` (signed distance to the separating
    hyperplane's planar image).  Degenerate planes yield 0 (no exclusion —
    coincident pivots separate nothing)."""
    d1, d2, raw = _coerce(xp, d1, d2, delta)
    delta = xp.maximum(raw, MIN_DELTA)
    return xp.where(
        raw < DEGENERATE_DELTA, 0.0, (d1 * d1 - d2 * d2) / (2.0 * delta)
    )


def rotate(x, y, theta, h, *, xp=jnp):
    """Rotate planar points by ``-theta``-style LRT transform around the
    X-intercept ``(h, 0)`` (paper Eq. 2-3):

        r_x = (x - h) cos(theta) + y sin(theta)
        r_y = -(x - h) sin(theta) + y cos(theta)

    Note: the paper prints the rotation with the signs producing a rotation
    by ``-theta``; what matters for correctness is that it is a *rigid*
    transform (distance-preserving), so the lower-bound property survives.
    """
    x, y, theta, h = _coerce(xp, x, y, theta, h)
    c = xp.cos(theta)
    s = xp.sin(theta)
    xs = x - h
    return xs * c + y * s, -xs * s + y * c


def planar_lower_bound(x1, y1, x2, y2, *, xp=jnp):
    """l2 distance in the plane == lower bound on true distance (supermetric)."""
    return xp.sqrt((x1 - x2) ** 2 + (y1 - y2) ** 2)


def point_to_interval(v, lo, hi, *, xp=jnp):
    """Distance from scalar coordinate(s) to interval(s) [lo, hi] (0 inside)."""
    return xp.maximum(xp.maximum(lo - v, v - hi), 0.0)


def point_to_box(x, y, box, *, xp=jnp):
    """Planar distance from point(s) to axis-aligned box(es).

    ``box[0..3] = (x_lo, x_hi, y_lo, y_hi)`` along the LEADING axis (the
    BSS index's planes-major ``(4, M, B)`` table).  Broadcasts.  Because the
    planar metric lower-bounds the true metric, this is a sound lower bound
    on the distance from the query to EVERY point whose projection lies in
    the box — the Blocked Supermetric Scan's pruning primitive.
    """
    dx = point_to_interval(x, box[0], box[1], xp=xp)
    dy = point_to_interval(y, box[2], box[3], xp=xp)
    return xp.sqrt(dx * dx + dy * dy)
