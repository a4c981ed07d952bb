"""device.idle_in_engine_host.batch: share of the traced window, in
percent, in which no operation ran on the chip while the host was inside
the BSS engine's own host work: an ``engine/*`` span of a
``RetrievalServer.search`` call (``stats["spans"]``) other than an
``engine/*/device`` wait.  At most ``device.idle_share.batch``.

Layer: BSS engine host driver (``core/flat_index.py``).  Source: the
device trace, against the program's spans on the same clock.  Moves:
qps."""

from bench import program_spans


def read(ctx):
    calls = program_spans.closed_calls(ctx, "spans")
    return program_spans.idle_in_engine_host(
        ctx.trace, [c["res"].stats["spans"] for c in calls])
