"""engine.d2h_bytes_per_query.batch: bytes the BSS engine copied from the
device to the host per query of a closed-loop window
(``stats["d2h_bytes"]`` of every answered ``RetrievalServer.search``
call, over the queries those calls answered).

Layer: BSS engine (``core/flat_index.py``).  Source: the engine's
counter.  Moves: qps."""

from bench import program_spans


def read(ctx):
    calls = program_spans.closed_calls(ctx, "d2h_bytes")
    if not calls:
        return None
    return (sum(int(c["res"].stats["d2h_bytes"]) for c in calls)
            / sum(len(c["qidx"]) for c in calls))
