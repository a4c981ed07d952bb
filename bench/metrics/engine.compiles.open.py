"""engine.compiles.open: compiles the BSS engine's jits made during the
window's front micro-batches: the sum of ``ServeResult.batch.compiles``
(new compile-cache entries per jitted function), each batch once.  Every
shape is warmed in set-up, so anything above 0 is a compile inside the
measured window.

Layer: BSS engine (``core/flat_index.py``).  Source: the engine's
counter.  Moves: p95_ms."""

from bench import program_spans


def read(ctx):
    _, batches = program_spans.open_batches(ctx)
    counted = [b.compiles for b in batches if b.compiles is not None]
    if not counted:
        return None
    return float(sum(sum(c.values()) for c in counted))
