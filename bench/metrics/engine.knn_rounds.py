"""engine.knn_rounds: radius-deepening rounds per kNN batch
(``stats["rounds"]`` of every ``RetrievalServer.search`` call), each a
masked exact pass and a corpus-wide ``top_k`` with host round trips.

Layer: BSS engine, kNN host driver (``core/flat_index.py``).  Source: the
engine's counter.  Moves: qps."""

import numpy as np


def read(ctx):
    r = [c["res"].stats["rounds"] for c in ctx.rec.get("calls", ())
         if c["res"] is not None and "rounds" in c["res"].stats]
    return float(np.mean(r)) if r else None
