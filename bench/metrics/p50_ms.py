"""p50_ms: the 50th percentile, in milliseconds, of the time from when
each request of an open-loop window was due to when its future resolved;
a failed request counts as resolved at the end of the wait.  Host clock.
Open loops only."""

import numpy as np


def read(ctx):
    if ctx.rec["loop"] != "open":
        return None
    return 1e3 * float(np.percentile(ctx.loop.latencies(ctx.rec), 50))
