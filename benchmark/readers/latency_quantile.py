"""A quantile, in ms, of due-to-result latency over every frame DUE
inside the window: from the instant the frame was due at the generator
(open loop: a stall is charged to the frames behind it) to its result
appended at the sink. A frame with no result has no latency to give; it
is counted in ``failed`` and makes the run incorrect."""

import numpy as np


def window_latencies(ctx) -> np.ndarray:
    """Seconds from due to result, of every frame due inside the window
    whose result reached the sink."""
    _, idx, done_t = ctx.results
    due = ctx.generated["due"]
    t0, t1 = ctx.window
    ok = (idx >= 0) & (idx < len(due))
    idx, done_t = idx[ok], done_t[ok]
    in_window = (due[idx] >= t0) & (due[idx] < t1)
    return done_t[in_window] - due[idx][in_window]


def read(ctx, q: float):
    lat = window_latencies(ctx)
    if len(lat) == 0:
        return None
    return float(np.quantile(lat, float(q))) * 1e3
