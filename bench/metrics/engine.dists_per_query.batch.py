"""engine.dists_per_query.batch: distances the BSS engine evaluated per
query of a closed-loop window (``stats["per_query_dists"]`` of every
``RetrievalServer.search`` call, summed over kNN rounds).

Layer: BSS engine (``core/flat_index.py``).  Source: the engine's
counter.  Moves: qps."""

import numpy as np


def read(ctx):
    calls = [c for c in ctx.rec.get("calls", ()) if c["res"] is not None]
    if not calls:
        return None
    total = sum(float(np.sum(c["res"].stats["per_query_dists"]))
                for c in calls)
    return total / sum(len(c["qidx"]) for c in calls)
