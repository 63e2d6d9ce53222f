"""The sum of a host-clock series over the window, as a share of the
window's length (or of another series' sum): ``producer_blocked_share`` is
the generator's seconds blocked on a full ring over the window seconds."""

import numpy as np


def read(ctx, numerator: str, denominator: str = "window_s", scale: float = 100.0):
    num = ctx.series.get(numerator)
    if num is None:
        return None
    den = ctx.window_s if denominator == "window_s" else float(np.sum(ctx.series[denominator]))
    if den <= 0:
        return None
    return float(np.sum(num)) / den * scale
