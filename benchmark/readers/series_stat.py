"""A statistic of one of the harness's own host-clock series over the
window: ``gen.late_s`` (sent minus due, frames due in the window),
``gen.blocked_s`` (seconds each put waited on a full ring, frames sent in
the window), ``sink.append_s`` (seconds per batch append),
``ring.depth`` (slots occupied, sampled by the parent over a monitor
handle). ``stat`` is ``mean``, ``sum`` or a quantile ``q``; ``scale``
multiplies (1000 for ms)."""

import numpy as np


def read(ctx, series: str, stat="mean", q=None, scale: float = 1.0):
    values = ctx.series.get(series)
    if values is None or len(values) == 0:
        return None
    if q is not None:
        return float(np.quantile(values, float(q))) * scale
    return float({"mean": np.mean, "sum": np.sum}[stat](values)) * scale
