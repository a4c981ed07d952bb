"""The control: the plain reference put in the program's place, computed in
the precision just below the one the configuration states.  Each metric's
file (``bench/distances/<metric>.py``) says which precision that is and
computes it.

The control answers the same requests the program answered in the window;
``bench/run.py`` with ``control=True`` compares those answers in place of
the program's, and has to read them as not correct.
"""

from __future__ import annotations

import numpy as np

_CHUNK = 8192  # corpus rows per device step


def distances(dist, queries: np.ndarray, corpus: np.ndarray) -> np.ndarray:
    """(Q, N) float32 control distances, computed on the default device
    in corpus chunks (and in query blocks where the metric asks)."""
    import jax.numpy as jnp

    fn = dist.control()
    qb = dist.CONTROL_QUERY_BLOCK or max(1, len(queries))
    out = np.empty((len(queries), len(corpus)), np.float32)
    n = len(corpus)
    pad = (-n) % _CHUNK
    c = jnp.asarray(np.pad(np.asarray(corpus, np.float32), ((0, pad), (0, 0))))
    for q0 in range(0, len(queries), qb):
        q = jnp.asarray(np.asarray(queries[q0:q0 + qb], np.float32))
        for lo in range(0, n, _CHUNK):
            d = np.asarray(fn(q, c[lo:lo + _CHUNK]))
            hi = min(lo + _CHUNK, n)
            out[q0:q0 + len(q), lo:hi] = d[:, : hi - lo]
    return out


def answers(dist, kind: str, k, corpus: np.ndarray, pool: np.ndarray,
            checked: list) -> list:
    """The control's answers to the ``checked`` requests, shaped as the
    program's (range: hit ids; kNN: ids and distances, ascending)."""
    d = distances(dist, pool[[a["qidx"] for a in checked]], corpus)
    out = []
    for a, row in zip(checked, d):
        if kind == "range":
            out.append(dict(a, hits=np.nonzero(row <= a["t"])[0].tolist()))
        else:
            ids = np.argsort(row, kind="stable")[: int(k)]
            out.append(dict(a, ids=ids, dists=row[ids]))
    return out
