"""device.idle_share.batch: share of the traced window, in percent, in which
no operation ran on the chip (moves qps).  Read from the device trace."""

from bench import trace_reduce


def read(ctx):
    return trace_reduce.idle_share(ctx.trace)
