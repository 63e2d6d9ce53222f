"""Results per second of the window: the real frames whose result
reached the sink inside ``[start, end)`` over the window's seconds — the
host's total, on however many chips the cell has."""

import numpy as np


def read(ctx):
    _, _, done_t = ctx.results
    t0, t1 = ctx.window
    return float(np.sum((done_t >= t0) & (done_t < t1))) / ctx.window_s
