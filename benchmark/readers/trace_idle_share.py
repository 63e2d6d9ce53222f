"""The share of the traced window, in %, in which no operation ran on the
device: 1 - (union of the device-op intervals) / window, per chip, mean
over the chips of a mesh."""

from benchmark import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    t0, t1 = ctx.trace_window
    if t1 <= t0:
        return None
    busy = trace_reduce.busy_seconds(ctx.trace, t0, t1)
    return (1.0 - busy / ((t1 - t0) / 1e9)) * 100.0
