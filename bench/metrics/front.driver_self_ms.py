"""front.driver_self_ms: the serving front's own time per micro-batch, in
milliseconds: the ``dispatch`` span less its ``dispatch/engine`` child
(assembling the batch, folding its telemetry, resolving its futures),
averaged over the window's batches (``ServeResult.batch.spans``, each
batch once).

Layer: serving front (``serve/front.py``).  Source: the front's spans.
Moves: p95_ms."""

import numpy as np

from bench import program_spans


def _self_s(spans):
    root = next((i for i, s in enumerate(spans)
                 if s[0] == "dispatch" and s[3] is None), None)
    if root is None or spans[root][2] is None:
        return None
    eng = next((s for s in spans
                if s[0] == "dispatch/engine" and s[3] == root), None)
    if eng is None or eng[2] is None:
        return None
    return (spans[root][2] - spans[root][1]) - (eng[2] - eng[1])


def read(ctx):
    _, batches = program_spans.open_batches(ctx)
    vals = [v for v in (_self_s(b.spans) for b in batches) if v is not None]
    return 1e3 * float(np.mean(vals)) if vals else None
