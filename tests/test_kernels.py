"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("m,n,k", [(128, 128, 16), (200, 310, 48), (1, 7, 3),
                                   (130, 128, 112), (64, 500, 20), (256, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("squared", [False, True])
def test_pairwise_l2_sweep(m, n, k, dtype, squared):
    rng = np.random.default_rng(m * 7 + n * 3 + k)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    y = jnp.asarray(rng.normal(size=(n, k)), dtype)
    got = ops.pairwise_l2(x, y, squared=squared, interpret=True)
    want = ref.pairwise_l2_ref(x, y, squared=squared)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("m,n,k", [(256, 384, 32), (100, 200, 64)])
def test_masked_pairwise_sweep(m, n, k):
    rng = np.random.default_rng(5)
    bm = bn = 128
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    tm = jnp.asarray(
        rng.integers(0, 2, size=(math.ceil(m / bm), math.ceil(n / bn))), jnp.int32
    )
    got = ops.masked_pairwise_l2(x, y, tm, bm=bm, bn=bn, interpret=True)
    want = ref.masked_pairwise_l2_ref(x, y, tm, bm, bn)
    g, w = np.asarray(got), np.asarray(want)
    assert np.array_equal(np.isinf(g), np.isinf(w))
    fin = ~np.isinf(w)
    np.testing.assert_allclose(g[fin], w[fin], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q,m,b", [(150, 12, 70), (128, 24, 128), (3, 4, 5),
                                   (257, 32, 130)])
def test_planar_lower_bound_sweep(q, m, b):
    rng = np.random.default_rng(q + m + b)
    d1 = jnp.asarray(np.abs(rng.normal(size=(q, m))) + 1.0, jnp.float32)
    delta = jnp.asarray(np.abs(rng.normal(size=(m,))) + 0.5, jnp.float32)
    d2 = jnp.asarray(np.abs(d1 + rng.normal(size=(q, m)) * 0.2), jnp.float32)
    lo = rng.normal(size=(b, m, 2))
    hi = lo + np.abs(rng.normal(size=(b, m, 2)))
    # planes-major (4, M, B), the index's box layout
    boxes = jnp.asarray(
        np.stack([lo[..., 0], hi[..., 0], lo[..., 1], hi[..., 1]]).transpose(0, 2, 1),
        jnp.float32,
    )
    got = ops.planar_lower_bound(d1, d2, delta, boxes, interpret=True)
    want = ref.planar_lower_bound_ref(d1, d2, delta, boxes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_bss_query_fused_end_to_end():
    """Fused kernel path returns exactly the dense-reference hit set."""
    from repro.core import flat_index
    from repro.core.npdist import pairwise_np

    rng = np.random.default_rng(11)
    db = rng.random((512, 24)).astype(np.float32)
    q = rng.random((64, 24)).astype(np.float32)
    idx = flat_index.build_bss("l2", db, n_pivots=8, n_pairs=12, block=128, seed=2)
    t = 0.45
    dist, tile_mask = ops.bss_query_fused(
        jnp.asarray(q),
        jnp.asarray(idx.pivots),
        jnp.asarray(idx.pairs),
        jnp.asarray(idx.deltas),
        jnp.asarray(idx.boxes),
        jnp.asarray(idx.data),
        t,
        block=idx.block,
        bq=32,
        interpret=True,
    )
    d = np.asarray(dist)
    truth = pairwise_np("l2", q, idx.data)
    truth = np.where(idx.valid[None, :], truth, np.inf)
    # exactness: every true hit must be present with a finite distance
    hits_true = truth <= t
    assert np.all(np.isfinite(d[hits_true])), "pruning dropped a true hit"
    np.testing.assert_allclose(d[hits_true], truth[hits_true], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,n,k", [(64, 64, 16), (100, 70, 48), (3, 130, 24)])
def test_pairwise_jsd_sweep(m, n, k):
    rng = np.random.default_rng(m + n + k)
    x = rng.gamma(1.0, size=(m, k)).astype(np.float32)
    x /= x.sum(axis=1, keepdims=True)
    y = rng.gamma(1.0, size=(n, k)).astype(np.float32)
    y /= y.sum(axis=1, keepdims=True)
    got = ops.pairwise_jsd(jnp.asarray(x), jnp.asarray(y), interpret=True)
    want = ref.pairwise_jsd_ref(jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # cross-check against the metric registry implementation
    from repro.core.npdist import pairwise_np

    np.testing.assert_allclose(np.asarray(got), pairwise_np("jsd", x, y),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("m,n,k", [(64, 64, 16), (100, 200, 64), (3, 130, 24),
                                   (128, 128, 112)])
def test_pairwise_tri_sweep(m, n, k):
    rng = np.random.default_rng(m * 3 + n + k)
    x = rng.gamma(1.0, size=(m, k)).astype(np.float32)
    x /= x.sum(axis=1, keepdims=True)
    y = rng.gamma(1.0, size=(n, k)).astype(np.float32)
    y /= y.sum(axis=1, keepdims=True)
    got = ops.pairwise_tri(jnp.asarray(x), jnp.asarray(y), interpret=True)
    want = ref.pairwise_tri_ref(jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    from repro.core.npdist import pairwise_np

    np.testing.assert_allclose(np.asarray(got), pairwise_np("triangular", x, y),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("metric", ops.KERNEL_METRICS)
@pytest.mark.parametrize("m,n,k", [(256, 384, 32), (100, 200, 48)])
def test_masked_pairwise_metric_family_sweep(metric, m, n, k):
    """The metric-dispatched masked family: excluded tiles are +inf, live
    tiles match the unmasked reference, for every metric with a kernel."""
    rng = np.random.default_rng(7 + m)
    bm = bn = 128
    x = rng.gamma(1.0, size=(m, k)).astype(np.float32)
    y = rng.gamma(1.0, size=(n, k)).astype(np.float32)
    if metric in ("jsd", "triangular"):
        x /= x.sum(axis=1, keepdims=True)
        y /= y.sum(axis=1, keepdims=True)
    tm = jnp.asarray(
        rng.integers(0, 2, size=(math.ceil(m / bm), math.ceil(n / bn))),
        jnp.int32,
    )
    got = ops.masked_pairwise_metric(
        metric, jnp.asarray(x), jnp.asarray(y), tm, bm=bm, bn=bn,
        interpret=True,
    )
    dense = {
        "l2": ref.pairwise_l2_ref,
        "jsd": ref.pairwise_jsd_ref,
        "triangular": ref.pairwise_tri_ref,
    }[metric](jnp.asarray(x), jnp.asarray(y))
    want = ref.masked_pairwise_metric_ref(dense, tm, bm, bn)
    g, w = np.asarray(got), np.asarray(want)
    assert np.array_equal(np.isinf(g), np.isinf(w))
    fin = ~np.isinf(w)
    np.testing.assert_allclose(g[fin], w[fin], rtol=1e-5, atol=1e-5)


def test_quantile_split_tree_exact():
    """Controlled unbalancing (paper §6 future work) stays exact."""
    from repro.core import lrt, tree
    from repro.data import metricsets

    data = metricsets.colors_surrogate(1200, dim=24, seed=9)
    db, q = metricsets.split_queries(data, 0.05, seed=2)
    q = q[:15]
    t = metricsets.calibrate_threshold("l2", db, 5e-3)
    truth = tree.exhaustive_search("l2", db, q, t)
    for quant in (0.3, 0.7):
        tr = lrt.build_monotone_tree("lrt", "far", "l2", db, seed=5,
                                     split_quantile=quant)
        res, _ = lrt.range_search_monotone(tr, q, t, "hilbert")
        assert all(sorted(a) == sorted(b) for a, b in zip(res, truth)), quant


def test_tile_constants_env_override():
    """REPRO_TILE_* env vars reshape the kernel tiling without a rebuild
    (the ROADMAP autotuning knob).  ``tiles`` is import-light, so the
    subprocess check is cheap; the in-process defaults are asserted too."""
    import os
    import subprocess
    import sys

    from repro.kernels import pairwise_dist, planar_exclusion, tiles

    assert pairwise_dist.DEFAULT_BM == tiles.TILE_BQ
    assert pairwise_dist.DEFAULT_BN == tiles.TILE_BLOCK
    assert planar_exclusion.DEFAULT_BQ == tiles.TILE_BQ
    assert planar_exclusion.DEFAULT_BB == tiles.TILE_BLOCK

    env = dict(os.environ)
    env.update({"REPRO_TILE_BQ": "64", "REPRO_TILE_BLOCK": "256",
                "REPRO_TILE_KCHUNK": "32"})
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.kernels import tiles; "
         "print(tiles.TILE_BQ, tiles.TILE_BLOCK, tiles.TILE_KCHUNK)"],
        env=env, capture_output=True, text=True, check=True,
    )
    assert out.stdout.split() == ["64", "256", "32"]

    env["REPRO_TILE_BQ"] = "not-a-number"
    bad = subprocess.run(
        [sys.executable, "-c", "import repro.kernels.tiles"],
        env=env, capture_output=True, text=True,
    )
    assert bad.returncode != 0 and "REPRO_TILE_BQ" in bad.stderr
