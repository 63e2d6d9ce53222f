"""A quantile of one of the PROGRAM's own latency histograms
(``utils.metrics.PipelineMetrics``), in ms: ``step_latency`` (the host's
wait per batch, observed by ``SfxPipeline.drain`` / ``drive_step``) or
``stages.<name>`` (hop-stamp stage of traced frames: ``batch``,
``device_put``, ``dispatch`` ...). The adapter resets the histograms after
warm-up, so they hold the streamed batches only."""


def read(ctx, histogram: str, q: float = 0.5):
    metrics = ctx.metrics
    if metrics is None:
        return None
    if histogram == "step_latency":
        stat = metrics.step_latency
    elif histogram.startswith("stages."):
        stat = metrics.stages.stat(histogram[len("stages."):])
    else:
        raise ValueError(f"unknown histogram {histogram!r}")
    if stat is None or stat.count == 0:
        return None
    return float(stat.quantile(float(q))) * 1e3
