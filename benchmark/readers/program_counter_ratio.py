"""The quotient of two of the PROGRAM's own counters, by their names in
``PipelineMetrics.snapshot()``: ``expert_load_peak`` is the busiest
expert's token slots over the even share, ``attn_live_tile_share`` the
live attention tiles over the causal ones (``scale`` 100 for %). A
program without one of the two counters (the parent of the PR that added
them), or a denominator of zero, gives nothing to read."""


def read(ctx, numerator: str, denominator: str, scale: float = 1.0):
    if ctx.metrics is None:
        return None
    snap = ctx.metrics.snapshot()
    num, den = snap.get(numerator), snap.get(denominator)
    if num is None or den is None or not den:
        return None
    return float(num) / float(den) * float(scale)
