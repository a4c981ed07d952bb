"""engine.compiles.batch: compiles the BSS engine's jits made during the
window's ``RetrievalServer.search`` calls: the sum of
``stats["compiles"]`` (new compile-cache entries per jitted function)
over the answered calls.  The cell's shape is warmed in set-up, so
anything above 0 is a compile inside the measured window.

Layer: BSS engine (``core/flat_index.py``).  Source: the engine's
counter.  Moves: qps."""

from bench import program_spans


def read(ctx):
    calls = program_spans.closed_calls(ctx, "compiles")
    if not calls:
        return None
    return float(sum(sum(c["res"].stats["compiles"].values())
                     for c in calls))
