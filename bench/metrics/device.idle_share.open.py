"""device.idle_share.open: share of the traced window, in percent, in which
no operation ran on the chip (moves p95_ms).  Read from the device trace."""

from bench import trace_reduce


def read(ctx):
    return trace_reduce.idle_share(ctx.trace)
