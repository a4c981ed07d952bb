"""Colour-histogram corpus and query pool (SISAP ``colors`` layout), split
by the paper's protocol: a random ``query_fraction`` of the rows held out
as queries.

A copy of the program's surrogate (``repro.data.metricsets.colors_surrogate``),
kept here so that the program cannot change the data it is measured on.
One change from it: the cluster layout is drawn from the configuration's
``structure_seed`` and only the points from ``--seed`` (see
``data/sift.py``).
"""

from __future__ import annotations

import numpy as np


def rows(n: int, dim: int, structure_seed: int, seed: int) -> np.ndarray:
    """Non-negative rows summing to 1, float64: 40 Dirichlet clusters with
    Zipf-skewed weights plus 4% diffuse outliers."""
    srng = np.random.default_rng([int(structure_seed), 3])
    k = 40
    centres = srng.gamma(0.35, size=(k, dim))
    centres /= centres.sum(axis=1, keepdims=True)
    weights = 1.0 / np.arange(1, k + 1) ** 1.1
    weights /= weights.sum()
    kappa = srng.lognormal(mean=4.5, sigma=0.6, size=k)
    rng = np.random.default_rng([int(seed), 4])
    assign = rng.choice(k, size=n, p=weights)
    alpha = centres[assign] * kappa[assign, None] + 1e-3
    pts = rng.gamma(np.maximum(alpha, 1e-6))
    pts /= np.maximum(pts.sum(axis=1, keepdims=True), 1e-12)
    outliers = rng.random(n) < 0.04
    if outliers.any():
        o = rng.gamma(0.5, size=(int(outliers.sum()), dim))
        o /= o.sum(axis=1, keepdims=True)
        pts[outliers] = o
    return pts


def make(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(corpus, query pool), both float32."""
    n = int(cfg["n_rows"])
    x = rows(n, int(cfg["dim"]), int(cfg["data"]["structure_seed"]),
             seed).astype(np.float32)
    perm = np.random.default_rng([int(seed), 5]).permutation(n)
    nq = int(n * float(cfg["query_fraction"]))
    return x[perm[nq:]], x[perm[:nq]]
