"""The mean, in ms, of the due-to-result latencies between two quantiles
of the frames DUE inside the window (``lo`` 0.45 and ``hi`` 0.55: the
middle tenth). Latency as ``latency_quantile`` has it: from the instant
the frame was due at the generator to its result appended at the sink.

Why not the median: a paced stream's latencies come in as many clusters
as a batch has places, a batch period / batch size apart, and with an even
number of places the median is the mean of two cluster EDGES; one batch
that lands late takes its lower-half frames across it and the reading
jumps by a good part of the cluster distance (PERF.md, PR 24 finding 4).
The mean over a band that reaches well into the clusters on both sides
stands on their bodies: the same late batch leaves the band and shifts it
by (frames moved / frames in the band) of the cluster distance. The reader
knows nothing of batches; it is a trimmed mean."""

import numpy as np

from benchmark.readers.latency_quantile import window_latencies


def read(ctx, lo: float, hi: float):
    lat = np.sort(window_latencies(ctx))
    band = lat[int(np.floor(float(lo) * len(lat))):int(np.ceil(float(hi) * len(lat)))]
    if len(band) == 0:
        return None
    return float(np.mean(band)) * 1e3
