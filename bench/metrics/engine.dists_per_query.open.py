"""engine.dists_per_query.open: distances the BSS engine evaluated per
answered request of an open-loop window (``ServeResult.n_dists``: the
query's pivot distances plus the valid rows of the blocks its bound left
alive), the paper's figure of merit.

Layer: BSS engine (``core/flat_index.py``).  Source: the engine's
counter.  Moves: p95_ms."""

import numpy as np


def read(ctx):
    n = [r["res"].n_dists for r in ctx.rec.get("requests", ()) if r["ok"]]
    return float(np.mean(n)) if n else None
