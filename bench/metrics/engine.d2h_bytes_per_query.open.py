"""engine.d2h_bytes_per_query.open: bytes the BSS engine copied from the
device to the host per answered request of an open-loop window: the
``d2h_bytes`` of every front micro-batch (``ServeResult.batch``, each
batch once), over the answered requests.

Layer: BSS engine (``core/flat_index.py``).  Source: the engine's
counter.  Moves: p95_ms."""

from bench import program_spans


def read(ctx):
    rows, batches = program_spans.open_batches(ctx)
    counted = [b.d2h_bytes for b in batches if b.d2h_bytes is not None]
    if not rows or not counted:
        return None
    return sum(counted) / len(rows)
