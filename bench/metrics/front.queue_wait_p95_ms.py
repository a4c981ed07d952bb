"""front.queue_wait_p95_ms: 95th percentile of the serving front's own
queue wait (admission to dispatch, ``ServeResult.queue_wait_s``) over the
answered requests of the window, in milliseconds.

Layer: serving front (``serve/front.py``).  Source: the front's host-clock
span.  Moves: p95_ms."""

import numpy as np


def read(ctx):
    waits = [r["res"].queue_wait_s for r in ctx.rec.get("requests", ())
             if r["ok"]]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
