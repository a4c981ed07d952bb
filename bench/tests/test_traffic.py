"""Both traffic generators are determined by the seed, and every seed
offers the same amount of work."""

from __future__ import annotations

import numpy as np

from bench.traffic import closed, open as open_loop

TRAFFIC = {"kind": "range", "radius_factor": [0.7, 1.3]}
CELL = {"rate_per_s": 150.0, "radius": 2.0}


def test_open_schedule_is_determined_by_the_seed():
    a = open_loop.schedule(TRAFFIC, CELL, 2**31 + 7, 30.0, 10_000)
    b = open_loop.schedule(TRAFFIC, CELL, 2**31 + 7, 30.0, 10_000)
    for key in ("due", "qidx", "t"):
        np.testing.assert_array_equal(a[key], b[key])


def test_open_schedule_same_work_for_every_seed():
    a = open_loop.schedule(TRAFFIC, CELL, 1, 30.0, 10_000)
    b = open_loop.schedule(TRAFFIC, CELL, 2, 30.0, 10_000)
    assert len(a["due"]) == len(b["due"]) == 4500
    assert not np.array_equal(a["qidx"], b["qidx"])
    # the same set of radii, in another order
    np.testing.assert_allclose(np.sort(a["t"]), np.sort(b["t"]))
    assert not np.array_equal(a["t"], b["t"])
    # the same gaps between arrivals, in another order
    ga, gb = (np.sort(np.diff(s["due"], prepend=0.0)) for s in (a, b))
    j = np.clip(np.searchsorted(gb, ga), 1, len(gb) - 1)
    near = np.minimum(np.abs(gb[j] - ga), np.abs(gb[j - 1] - ga))
    assert np.sum(near > 1e-9) <= 1  # each drops one of the n + 1 gaps
    assert not np.array_equal(a["due"], b["due"])
    for s in (a, b):
        assert np.all(np.diff(s["due"]) >= 0)
        assert 0.0 <= s["due"][0] and s["due"][-1] < 30.0
        assert len(np.unique(s["qidx"])) == len(s["qidx"])  # 4500 < pool
        assert s["t"].min() >= 0.7 * 2.0 and s["t"].max() <= 1.3 * 2.0


def test_open_schedule_reuses_the_pool_when_it_runs_out():
    s = open_loop.schedule(TRAFFIC, CELL, 3, 30.0, 1000)
    assert len(s["qidx"]) == 4500
    assert np.bincount(s["qidx"], minlength=1000).min() >= 4


class _Echo:
    """An entry that answers at once, recording the batches it gets."""

    def __init__(self):
        self.batches = []

    def search(self, batch, kind, **kw):
        self.batches.append(np.asarray(batch[:, 0]))
        return None


def _closed_batches(seed: int) -> list:
    pool = np.arange(300, dtype=np.float32)[:, None]
    entry = _Echo()
    closed.run(entry, pool, {"kind": "knn", "k": 10, "batch": 64}, {},
               seed, 0.05)
    return entry.batches


def test_closed_batches_are_determined_by_the_seed():
    a, b = _closed_batches(11), _closed_batches(11)
    n = min(len(a), len(b))
    assert n >= 5
    for x, y in zip(a[:n], b[:n]):
        np.testing.assert_array_equal(x, y)
    c = _closed_batches(12)
    assert not np.array_equal(a[0], c[0])


def test_closed_batches_walk_the_whole_pool():
    a = _closed_batches(13)
    first = np.concatenate(a[:4])[:256]
    # a permutation of the pool is used up before any row repeats
    assert len(np.unique(first)) == 256
    assert all(len(b) == 64 for b in a)
