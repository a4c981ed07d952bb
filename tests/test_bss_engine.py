"""Fused batched BSS engine vs the numpy oracle.

The contract under test: ``bss_query_batched`` / ``bss_knn_batched`` return
EXACTLY the numpy path's results — same hit indices, same per-query order
for range search; the same neighbour set for kNN — across metrics, odd
shapes, padded blocks, and both backends (pure-jnp and the Pallas kernels
in interpret mode).

Thresholds are snapped to midpoints of well-separated gaps in the true
(float64) distance distribution so the float32 engine and the float64
oracle cannot disagree about ``d <= t`` at the boundary — the comparison is
then exact, not approximate.
"""

import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro.core import flat_index
from repro.core.backends import EngineOpts
from repro.core.distances import METRICS, get_metric
from repro.core.npdist import DistanceCounter, pairwise_np

_JNP = EngineOpts(backend="jnp")
_PALLAS = EngineOpts(backend="pallas", interpret=True, bq=8)

SUPERMETRICS = ["l2", "cosine", "jsd", "triangular"]
# every four-point metric the registry serves, incl. a power transform
ALL_SUPERMETRICS = SUPERMETRICS + ["l1^0.5"]
get_metric("l1^0.5")  # ensure registration before METRICS introspection


def _space(metric, n, dim, seed):
    rng = np.random.default_rng(seed)
    x = rng.random((n, dim)).astype(np.float32) + 1e-3
    if metric in METRICS and METRICS[metric].probability_space:
        x /= x.sum(axis=1, keepdims=True)
    return x


def safe_threshold(dvals: np.ndarray, frac: float) -> float:
    """A threshold at ~the given quantile, snapped to the midpoint of a
    well-separated gap so float32 and float64 agree on every d <= t."""
    vals = np.unique(np.sort(np.asarray(dvals, np.float64).ravel()))
    i = int(np.clip(frac * len(vals), 0, len(vals) - 2))
    for j in range(i, len(vals) - 1):
        if vals[j + 1] - vals[j] > 1e-4 * max(1.0, vals[j]):
            return float(0.5 * (vals[j] + vals[j + 1]))
    return float(vals[-1] + 1.0)


# ------------------------------------------------------------- range search

# odd query counts, non-power-of-two corpora, blocks that end up padded
SHAPES = [
    ("l2", 801, 17, 64, 33),
    ("l2", 1024, 32, 128, 128),
    ("cosine", 513, 9, 128, 21),
    ("jsd", 330, 11, 32, 7),
    ("triangular", 257, 7, 64, 5),
    ("l1^0.5", 410, 13, 64, 9),
]


@pytest.mark.parametrize("metric,n,dim,block,nq", SHAPES)
def test_range_matches_oracle(metric, n, dim, block, nq):
    data = _space(metric, n + nq, dim, seed=n + dim)
    db, q = data[:n], data[n:]
    idx = flat_index.build_bss(metric, db, n_pivots=8, n_pairs=10,
                               block=block, seed=1)
    t = safe_threshold(pairwise_np(metric, q, db), 0.02)
    oracle, so = flat_index.bss_query(idx, q, t)
    batched, sb = flat_index.bss_query_batched(idx, q, t, opts=_JNP)
    assert batched == oracle  # same indices AND same per-query order
    # both paths prune identically (shared lower bound definition)
    assert sb["dists_per_query"] == pytest.approx(so["dists_per_query"])
    assert 0.0 <= sb["tile_exclusion_rate"] <= 1.0


@pytest.mark.parametrize("metric", SUPERMETRICS)
def test_range_matches_oracle_pallas_interpret(metric):
    """Kernel wiring (interpret mode off-TPU) returns the oracle's hits."""
    data = _space(metric, 450, 12, seed=3)
    db, q = data[:420], data[420:]
    idx = flat_index.build_bss(metric, db, n_pivots=6, n_pairs=8,
                               block=128, seed=2)
    t = safe_threshold(pairwise_np(metric, q, db), 0.03)
    oracle, _ = flat_index.bss_query(idx, q, t)
    batched, _ = flat_index.bss_query_batched(idx, q, t, opts=_PALLAS)
    assert batched == oracle


@pytest.mark.parametrize("t,expect_all", [(-1.0, False), (1e6, True)])
def test_range_all_and_none_excluded(t, expect_all):
    """Degenerate masks: a negative threshold excludes every block (lb >= 0
    always; empty hit lists); a threshold above every distance computes
    every cell — both must still match the oracle exactly."""
    db = _space("l2", 400, 10, seed=9)
    q = _space("l2", 23, 10, seed=10)
    idx = flat_index.build_bss("l2", db, n_pivots=6, n_pairs=8, block=64,
                               seed=3)
    oracle, _ = flat_index.bss_query(idx, q, t)
    batched, sb = flat_index.bss_query_batched(idx, q, t, opts=_JNP)
    assert batched == oracle
    if expect_all:
        assert all(len(r) == len(db) for r in batched)
        assert sb["block_exclusion_rate"] == 0.0
    else:
        assert all(len(r) == 0 for r in batched)
        assert sb["block_exclusion_rate"] == 1.0


def _mixed_radii(metric, nq):
    """An index, queries, and per-query radii that mix padding rows (t < 0),
    rows with no hit, rows with a few hits and rows with many, each radius
    snapped to a gap of its own query's distances."""
    data = _space(metric, 600 + nq, 10, seed=nq)
    db, q = data[:600], data[600:]
    idx = flat_index.build_bss(metric, db, n_pivots=6, n_pairs=8,
                               block=128, seed=4)
    d = pairwise_np(metric, q, db)
    t = np.empty(nq, np.float32)
    for i in range(nq):
        t[i] = (-1.0, 0.5 * d[i].min(), safe_threshold(d[i], 0.02),
                safe_threshold(d[i], 0.6))[i % 4]
    return idx, q, t


def _compact_d2h(idx, nq, cap):
    """Bytes the compacted Pallas range call copies: counts (Q,) and
    pos (cap,) int32, alive (Q, B) and tile_mask (Qtiles, B) bools."""
    qt = -(-nq // _PALLAS.bq)
    return 4 * nq + 4 * cap + nq * idx.n_blocks + qt * idx.n_blocks


@pytest.mark.parametrize("metric,nq", [("l2", 24), ("jsd", 21)])
def test_pallas_range_compacts_hits_on_device(metric, nq, monkeypatch):
    """The Pallas range path compacts its hits on the device: the hit lists
    and their per-query order are the oracle's, the call copies only the
    counts, the hit slots and the two masks, and a repeated call at the
    same shape compiles nothing.  A small step makes the hits span several
    steps of the compaction loop."""
    monkeypatch.setattr(flat_index, "_COMPACT_CHUNK", 256)
    flat_index._query_batched_jit.clear_cache()  # trace again at this step
    idx, q, t = _mixed_radii(metric, nq)
    oracle, _ = flat_index.bss_query(idx, q, t)
    batched, stats = flat_index.bss_query_batched(idx, q, t, opts=_PALLAS)
    assert batched == oracle
    assert sum(map(len, batched)) > 256
    assert any(t < 0) and all(not r for r, ti in zip(batched, t) if ti < 0)
    assert stats["compact_overflow"] == 0
    assert stats["d2h_bytes"] == _compact_d2h(
        idx, nq, flat_index._compact_cap(nq))
    again, stats2 = flat_index.bss_query_batched(idx, q, t, opts=_PALLAS)
    assert again == oracle and stats2["compiles"] == {}


@pytest.mark.parametrize("metric,nq", [("l2", 24), ("jsd", 21)])
def test_pallas_range_overflow_selects_from_dense(metric, nq, monkeypatch):
    """A call with more hits than compaction slots copies the dense
    distances and selects on the host: identical hits and stats, with
    ``compact_overflow`` 1 (0 for a call that fits)."""
    idx, q, t = _mixed_radii(metric, nq)
    oracle, _ = flat_index.bss_query(idx, q, t)
    fit, s_fit = flat_index.bss_query_batched(idx, q, t, opts=_PALLAS)
    monkeypatch.setattr(flat_index, "_COMPACT_CAP_FLOOR", 64)
    monkeypatch.setattr(flat_index, "_COMPACT_CAP_PER_ROW", 0)
    over, s_over = flat_index.bss_query_batched(idx, q, t, opts=_PALLAS)
    assert sum(map(len, oracle)) > 64
    assert fit == over == oracle
    assert (s_fit["compact_overflow"], s_over["compact_overflow"]) == (0, 1)
    npad = idx.n_blocks * idx.block
    assert s_over["d2h_bytes"] == _compact_d2h(idx, nq, 64) + 4 * nq * npad
    host = {"spans", "d2h_bytes", "compiles", "compact_overflow"}
    assert set(s_fit) == set(s_over)
    for k in set(s_fit) - host:
        a, b = s_fit[k], s_over[k]
        if isinstance(a, dict):
            assert a.keys() == b.keys()
            assert all(np.array_equal(a[m], b[m]) for m in a), k
        else:
            assert np.array_equal(a, b), k


# --------------------------------------------------------------------- kNN


@pytest.mark.parametrize("metric,n,dim,block,nq,k", [
    ("l2", 900, 16, 64, 37, 7),
    ("l2", 1111, 24, 128, 128, 1),
    ("cosine", 640, 12, 128, 19, 10),
    ("jsd", 385, 9, 32, 11, 5),
    ("triangular", 300, 8, 64, 9, 4),
    ("l1^0.5", 420, 10, 64, 13, 6),
])
def test_knn_matches_bruteforce(metric, n, dim, block, nq, k):
    data = _space(metric, n + nq, dim, seed=n * 3 + k)
    db, q = data[:n], data[n:]
    idx = flat_index.build_bss(metric, db, n_pivots=8, n_pairs=10,
                               block=block, seed=4)
    truth = pairwise_np(metric, q, db)
    want_idx = np.argsort(truth, axis=1)[:, :k]
    got_idx, got_d, stats = flat_index.bss_knn_batched(idx, q, k,
                                                       opts=_JNP)
    for i in range(nq):
        assert set(got_idx[i].tolist()) == set(want_idx[i].tolist()), i
        np.testing.assert_allclose(  # ascending exact distances
            got_d[i], np.sort(truth[i])[:k], rtol=1e-5, atol=1e-5
        )
    assert stats["rounds"] >= 1
    assert stats["dists_per_query"] >= stats["pivot_dists_per_query"]


@pytest.mark.parametrize("metric", SUPERMETRICS)
def test_knn_pallas_interpret_matches_jnp(metric):
    """The masked Pallas kernel family (interpret mode off-TPU) returns the
    jnp engine's kNN for every supermetric — cosine through the l2 kernels
    on the sphere, jsd/triangular through their own tile kernels."""
    db = _space(metric, 384, 8, seed=6)
    q = _space(metric, 9, 8, seed=7)
    idx = flat_index.build_bss(metric, db, n_pivots=6, n_pairs=8, block=128,
                               seed=5)
    i_jnp, d_jnp, _ = flat_index.bss_knn_batched(idx, q, 6, opts=_JNP)
    i_pal, d_pal, _ = flat_index.bss_knn_batched(idx, q, 6, opts=_PALLAS)
    np.testing.assert_array_equal(np.sort(i_jnp, 1), np.sort(i_pal, 1))
    np.testing.assert_allclose(d_jnp, d_pal, rtol=1e-5, atol=1e-6)


def test_knn_k_exceeding_corpus_pads():
    db = _space("l2", 40, 6, seed=8)
    q = _space("l2", 3, 6, seed=9)
    idx = flat_index.build_bss("l2", db, n_pivots=4, n_pairs=4, block=32,
                               seed=6)
    got_idx, got_d, _ = flat_index.bss_knn_batched(idx, q, 50, opts=_JNP)
    assert got_idx.shape == (3, 50)
    assert (got_idx[:, :40] >= 0).all() and (got_idx[:, 40:] == -1).all()
    assert np.isinf(got_d[:, 40:]).all()
    truth = pairwise_np("l2", q, db)
    for i in range(3):
        assert set(got_idx[i, :40].tolist()) == set(range(40))
        np.testing.assert_allclose(got_d[i, :40], np.sort(truth[i]),
                                   rtol=1e-5, atol=1e-5)


def test_knn_fixed_r0_and_serving_path():
    """An explicit initial radius (the serving layer's t0_guess) stays
    exact, whether it starts too tight or too wide."""
    db = _space("l2", 700, 14, seed=11)
    q = _space("l2", 17, 14, seed=12)
    idx = flat_index.build_bss("l2", db, n_pivots=8, n_pairs=10, block=64,
                               seed=7)
    truth = np.argsort(pairwise_np("l2", q, db), axis=1)[:, :5]
    for r0 in (1e-6, 0.3, 100.0):
        got, _, _ = flat_index.bss_knn_batched(idx, q, 5, r0=r0, opts=_JNP)
        for i in range(len(q)):
            assert set(got[i].tolist()) == set(truth[i].tolist()), (r0, i)


# -------------------------------------------------------------- soundness


@settings(max_examples=15, deadline=None)
@given(
    st.integers(100, 500),
    st.integers(4, 24),
    st.floats(0.005, 0.2),
    st.integers(0, 10_000),
)
def test_no_excluded_block_contains_a_true_hit(n, dim, t_frac, seed):
    """THE soundness property the engine's exactness rests on: for ANY
    corpus/threshold, a block excluded by the planar lower bound never
    contains a point within the search radius."""
    rng = np.random.default_rng(seed)
    db = rng.random((n, dim)).astype(np.float32)
    q = rng.random((8, dim)).astype(np.float32)
    idx = flat_index.build_bss("l2", db, n_pivots=min(8, n), n_pairs=10,
                               block=32, seed=seed % 23)
    d = pairwise_np("l2", q, idx.data)
    d = np.where(idx.valid[None, :], d, np.inf)
    per_block_min = d.reshape(len(q), idx.n_blocks, idx.block).min(axis=2)
    lb = flat_index.bss_lower_bounds(idx, q)
    t = float(np.quantile(d[np.isfinite(d)], t_frac))
    excluded = lb > t
    # excluded => no point in the block at distance <= t (float tolerance:
    # the bound is float32, the truth float64)
    assert np.all(per_block_min[excluded] > t - 1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(100, 400), st.integers(3, 16), st.integers(0, 10_000))
def test_batched_range_property(n, dim, seed):
    """Property form of oracle equivalence on random spaces."""
    rng = np.random.default_rng(seed)
    db = rng.random((n, dim)).astype(np.float32)
    q = rng.random((7, dim)).astype(np.float32)
    idx = flat_index.build_bss("l2", db, n_pivots=min(8, n), n_pairs=8,
                               block=32, seed=seed % 17)
    t = safe_threshold(pairwise_np("l2", q, db), 0.05)
    oracle, _ = flat_index.bss_query(idx, q, t)
    batched, _ = flat_index.bss_query_batched(idx, q, t, opts=_JNP)
    assert batched == oracle


@settings(max_examples=12, deadline=None)
@given(
    st.sampled_from(ALL_SUPERMETRICS),
    st.integers(120, 400),
    st.integers(4, 20),
    st.integers(0, 10_000),
)
def test_lower_bound_never_exceeds_true_distance(metric, n, dim, seed):
    """Four-point soundness per metric: the per-block planar lower bound
    never exceeds the true distance to ANY valid point of the block, for
    every supermetric the registry serves (incl. a power transform)."""
    db = _space(metric, n, dim, seed=seed % 1000)
    q = _space(metric, 6, dim, seed=seed % 1000 + 1)
    idx = flat_index.build_bss(metric, db, n_pivots=min(8, n), n_pairs=8,
                               block=32, seed=seed % 13)
    lb = flat_index.bss_lower_bounds(idx, q)  # (Q, B)
    d = pairwise_np(metric, q, idx.data)  # permuted order (normalised for
    d = np.where(idx.valid[None, :], d, np.inf)  # cosine: idempotent)
    per_block_min = d.reshape(len(q), idx.n_blocks, idx.block).min(axis=2)
    assert np.all(lb <= per_block_min + 1e-4), metric


def test_non_four_point_metric_rejected():
    """Planar exclusion is unsound without the four-point property; the
    engine must refuse plain l1/linf (their power transforms are fine)."""
    db = _space("l2", 64, 6, seed=0)
    with pytest.raises(ValueError, match="four-point"):
        flat_index.build_bss("l1", db, n_pivots=4, n_pairs=4, block=32)
    flat_index.build_bss("l1^0.5", db, n_pivots=4, n_pairs=4, block=32)


# ---------------------------------------------------- distance accounting


@pytest.mark.parametrize("n", [300, 1000])  # NOT multiples of block=128
def test_exact_dists_accounting_excludes_padding(n):
    """Regression: ``exact_dists_per_query`` used ``survived * block``,
    counting the padded slots of partial blocks as real distance
    evaluations.  The corrected accounting must equal a DistanceCounter
    replay that evaluates only VALID points of surviving blocks."""
    assert n % 128 != 0
    db = _space("l2", n, 12, seed=n)
    q = _space("l2", 17, 12, seed=n + 1)
    idx = flat_index.build_bss("l2", db, n_pivots=8, n_pairs=10, block=128,
                               seed=2)
    t = safe_threshold(pairwise_np("l2", q, db), 0.05)

    # replay the oracle's exact phase through a DistanceCounter, evaluating
    # only the valid slots of each surviving block
    lb = flat_index.bss_lower_bounds(idx, q)
    alive = lb <= t
    counter = DistanceCounter("l2", len(q))
    bsz = idx.block
    for b in range(idx.n_blocks):
        qrows = np.nonzero(alive[:, b])[0]
        if len(qrows) == 0:
            continue
        blk_valid = idx.valid[b * bsz:(b + 1) * bsz]
        pts = idx.data[b * bsz:(b + 1) * bsz][blk_valid]
        counter.pairwise(qrows, q[qrows], pts)

    for results, stats in (
        flat_index.bss_query(idx, q, t),
        flat_index.bss_query_batched(idx, q, t, opts=_JNP),
    ):
        assert stats["exact_dists_per_query"] == pytest.approx(counter.mean)
        assert stats["dists_per_query"] == pytest.approx(
            idx.pivots.shape[0] + counter.mean
        )
    # the old (buggy) accounting would have been strictly larger whenever a
    # partial block survives; make sure some query DID hit the partial block
    assert alive[:, -1].any(), "test space must exercise the partial block"
    n_pad = idx.n_blocks * bsz
    assert n_pad > n  # padding exists, and is excluded from the count


def test_knn_accounting_excludes_padding():
    """kNN rounds share the padding-free accounting: with a radius that
    admits every block in round one, exactly n_valid (200) distances are
    charged — the old accounting would have charged n_pad (256)."""
    db = _space("l2", 200, 8, seed=3)  # 2 blocks of 128, second half-empty
    q = _space("l2", 5, 8, seed=4)
    idx = flat_index.build_bss("l2", db, n_pivots=6, n_pairs=8, block=128,
                               seed=3)
    _, _, stats = flat_index.bss_knn_batched(idx, q, 3, r0=1e6, opts=_JNP)
    assert stats["rounds"] == 1
    assert stats["exact_dists_per_query"] == pytest.approx(200.0)
    assert stats["dists_per_query"] == pytest.approx(206.0)  # + 6 pivots


# ------------------------------------------------------- degenerate deltas


def test_duplicate_pivots_delta_zero_stays_sound():
    """Regression for the inconsistent zero-baseline floors: with duplicate
    points forced into the pivot set (delta == 0 planes), exclusion through
    the shared MIN_DELTA floor must stay sound — bounds never exceed true
    distances and the fused engine still matches the oracle exactly."""
    rng = np.random.default_rng(7)
    # only TWO distinct locations: with 8 pivots, FFT is forced to select
    # duplicates, and keeping all 28 pivot pairs guarantees delta == 0 planes
    locs = rng.random((2, 8)).astype(np.float32)
    db = np.repeat(locs, 50, axis=0)  # 100 points, blocks end up padded too
    q = rng.random((11, 8)).astype(np.float32)
    idx = flat_index.build_bss("l2", db, n_pivots=8, n_pairs=28, block=32,
                               seed=5)
    assert (idx.deltas == 0.0).any(), "need at least one degenerate plane"
    lb = flat_index.bss_lower_bounds(idx, q)
    d = pairwise_np("l2", q, idx.data)
    d = np.where(idx.valid[None, :], d, np.inf)
    per_block_min = d.reshape(len(q), idx.n_blocks, idx.block).min(axis=2)
    assert np.all(lb <= per_block_min + 1e-4)
    assert np.all(np.isfinite(lb)), "degenerate plane produced inf/nan bound"
    t = safe_threshold(d[np.isfinite(d)], 0.05)
    oracle, _ = flat_index.bss_query(idx, q, t)
    batched, _ = flat_index.bss_query_batched(idx, q, t, opts=_JNP)
    assert batched == oracle
