"""SIFT-shaped corpus and query pool (ANN-benchmarks ``sift-128-euclidean``
layout): the first ``n_base`` rows are the corpus, the next ``n_queries``
the query pool.

A copy of the program's surrogate (``repro.data.metricsets.sift_surrogate``),
kept here so that the program cannot change the data it is measured on.
One change from it: the rows are drawn from the configuration's
``structure_seed`` alone, not from ``--seed``, so every seed serves the same
corpus and pool, as a public data set is one fixed set of rows; the seed
orders the traffic.  The index draws its pivots from the corpus, so a
corpus drawn per seed changes how much the index prunes: one such seed
answered 11% fewer kNN queries in the same window than the others.
"""

from __future__ import annotations

import numpy as np


def rows(n: int, dim: int, structure_seed: int) -> np.ndarray:
    """128-d non-negative descriptors, float32: 256 clusters with
    Zipf-skewed sizes around sparse gamma centres, isotropic Gaussian
    spread with a per-cluster scale, clipped at zero."""
    srng = np.random.default_rng([int(structure_seed), 1])
    k = 256
    centres = srng.gamma(0.6, 30.0, size=(k, dim)).astype(np.float32)
    weights = 1.0 / np.arange(1, k + 1) ** 0.8
    weights /= weights.sum()
    scale = srng.lognormal(mean=2.0, sigma=0.3, size=k).astype(np.float32)
    rng = np.random.default_rng([int(structure_seed), 2])
    assign = rng.choice(k, size=n, p=weights)
    pts = rng.standard_normal((n, dim), dtype=np.float32)
    pts *= scale[assign, None]
    pts += centres[assign]
    return np.maximum(pts, 0.0, out=pts)


def make(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(corpus, query pool), both float32, the same for every ``seed``."""
    n, nq = int(cfg["n_base"]), int(cfg["n_queries"])
    x = rows(n + nq, int(cfg["dim"]), int(cfg["data"]["structure_seed"]))
    return x[:n], x[n:]
