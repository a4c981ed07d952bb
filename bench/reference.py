"""The plain reference: float64 brute force, and the numbers that decide
``correct``.

Nothing here imports the program or reads what it made.  Distances are
computed in float64 over the whole corpus by the metric's own file
(``bench/distances/<metric>.py``), in blocks of queries and corpus rows so
that a block's temporaries stay near 8 MiB, on at most eight threads:
the whole check holds a few GiB (an earlier oracle whose blocks grew with
the corpus ran a chip host out of memory).

The numbers compared, each a worst case over the checked requests:

* ``range_gap``: for each range answer, the largest relative distance
  ``|d - t| / t`` of a corpus row that the answer gets wrong (a hit it
  left out, or a row it returned that lies outside ``t``).  0 when the
  answer equals the reference's.  A float32 engine can only err on rows
  within its rounding of the radius; a lower precision errs farther out.
* ``knn_err``: for each kNN answer, the larger of two errors, both
  relative to the reference's k-th nearest distance: how far the farthest
  returned row lies beyond that k-th nearest (a wrong row), and the
  largest error of a returned distance against the float64 distance of
  the same row.  A float32 engine may swap rows that tie to within its
  rounding, which reads less than its distance error; a lower precision,
  or a wrong row, reads more.
* ``unanswered``: requests due in the window that got no answer.

A malformed answer (an id out of range, a repeated id, fewer than k ids)
reads infinity.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np
from threadpoolctl import threadpool_limits

_BLOCK_ELEMS = 1 << 20  # float64 elements per temporary (8 MiB)
_THREADS = min(8, os.cpu_count() or 1)


class Reference:
    """float64 brute force over one corpus under one metric, given as its
    ``bench/distances/<metric>.py`` module."""

    def __init__(self, dist, corpus: np.ndarray):
        self.dist = dist
        self.corpus = np.asarray(corpus, np.float64)
        self._aux = dist.row_terms(self.corpus)

    def rows(self, queries: np.ndarray, consume) -> None:
        """Call ``consume(i, d)`` with each query's float64 distance row
        over the whole corpus (``i`` indexes ``queries``).  Queries go in
        blocks; the threads split each block's corpus, then its rows."""
        queries = np.asarray(queries, np.float64)
        n, dim = self.corpus.shape
        per = int(self.dist.QUERY_BLOCK)
        step = max(1, _BLOCK_ELEMS // (per * int(self.dist.pair_elems(dim))))
        fn = self.dist.reference
        # one BLAS thread per worker: the workers already fill the cores
        with threadpool_limits(1, "blas"), \
                cf.ThreadPoolExecutor(_THREADS) as pool:
            for q0 in range(0, len(queries), per):
                q = queries[q0:q0 + per]
                d = np.empty((len(q), n))

                def part(lo: int, q=q, d=d) -> None:
                    d[:, lo:lo + step] = fn(q, self.corpus[lo:lo + step],
                                            self._aux[lo:lo + step])

                for fut in [pool.submit(part, lo) for lo in range(0, n, step)]:
                    fut.result()
                for fut in [pool.submit(consume, q0 + j, d[j])
                            for j in range(len(q))]:
                    fut.result()

    def range_gaps(self, queries, radii, answers) -> np.ndarray:
        """Per query: ``range_gap`` of ``answers[i]`` (a list of corpus
        ids) at radius ``radii[i]``."""
        out = np.zeros(len(queries))

        def consume(i: int, d: np.ndarray) -> None:
            t = float(radii[i])
            ids = np.asarray(answers[i], np.int64)
            if ids.size and (ids.min() < 0 or ids.max() >= len(d)
                             or np.unique(ids).size != ids.size):
                out[i] = np.inf
                return
            served = np.zeros(len(d), bool)
            served[ids] = True
            wrong = served != (d <= t)
            out[i] = (float(np.max(np.abs(d[wrong] - t))) / t
                      if wrong.any() else 0.0)

        self.rows(queries, consume)
        return out

    def knn_errs(self, queries, k: int, ids, dists) -> np.ndarray:
        """Per query: ``knn_err`` of the returned ``ids[i]`` (k corpus ids)
        and ``dists[i]`` (their distances)."""
        out = np.zeros(len(queries))

        def consume(i: int, d: np.ndarray) -> None:
            got = np.asarray(ids[i], np.int64)
            if (got.size != k or got.min() < 0 or got.max() >= len(d)
                    or np.unique(got).size != k):
                out[i] = np.inf
                return
            kth = max(float(np.partition(d, k - 1)[k - 1]), 1e-30)
            wrong_row = max(0.0, float(d[got].max()) - kth)
            dist_err = float(np.max(np.abs(
                np.asarray(dists[i], np.float64) - d[got])))
            out[i] = max(wrong_row, dist_err) / kth

        self.rows(queries, consume)
        return out


def sample(answers: list, n_check: int, seed: int, kind: str) -> list:
    """The answers to check, ``n_check`` of them.  kNN: drawn from the
    seed.  Range: the half holding the most hits (rows lie near the radius
    there, where a wrong precision shows), the other half drawn from the
    seed among the rest."""
    rng = np.random.default_rng([int(seed), 20])
    m = min(int(n_check), len(answers))
    if kind == "range":
        sizes = np.asarray([len(a["hits"]) for a in answers])
        order = np.argsort(-sizes, kind="stable")
        top, rest = order[: m // 2], order[m // 2:]
        pick = np.concatenate([top, rng.choice(rest, m - len(top),
                                               replace=False)])
    else:
        pick = rng.choice(len(answers), m, replace=False)
    return [answers[i] for i in np.sort(pick)]


def numbers(ref: Reference, kind: str, k, pool: np.ndarray, checked: list,
            unanswered: int) -> dict:
    """The compared numbers of one run over the ``checked`` answers."""
    out = {}
    q = pool[[a["qidx"] for a in checked]]
    if kind == "range":
        gaps = ref.range_gaps(q, [a["t"] for a in checked],
                              [a["hits"] for a in checked])
        out["range_gap"] = float(gaps.max()) if len(gaps) else 0.0
    else:
        errs = ref.knn_errs(q, int(k), [a["ids"] for a in checked],
                            [a["dists"] for a in checked])
        out["knn_err"] = float(errs.max()) if len(errs) else 0.0
    out["unanswered"] = int(unanswered)
    return out
