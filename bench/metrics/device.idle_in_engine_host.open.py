"""device.idle_in_engine_host.open: share of the traced window, in
percent, in which no operation ran on the chip while the host was inside
the BSS engine's own host work: an ``engine/*`` span of a front
micro-batch (``ServeResult.batch.spans``) other than an
``engine/*/device`` wait.  At most ``device.idle_share.open``.

Layer: BSS engine host driver (``core/flat_index.py``).  Source: the
device trace, against the program's spans on the same clock.  Moves:
p95_ms."""

from bench import program_spans


def read(ctx):
    _, batches = program_spans.open_batches(ctx)
    return program_spans.idle_in_engine_host(
        ctx.trace, [b.spans for b in batches])
