"""A quantile of the durations of one named span in the program's own
per-frame trace spool (``obs.tracing.TRACER``: one JSON object per line,
``{"t":"s","n":<stage>,"a":<start>,"b":<end>}`` on the process's
monotonic clock), over spans that ended inside the window, in ms. The
traced run's generator marks frames as sampled, so
``batches_from_queue`` seeds their hop stamps at the pop and
``emit_batch_spans`` writes one span per stage."""

import json

import numpy as np


def read(ctx, span: str, q: float = 0.5):
    if not ctx.spool_path:
        return None
    t0, t1 = ctx.window
    durations = []
    with open(ctx.spool_path, encoding="utf-8") as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("t") == "s" and rec.get("n") == span and t0 <= rec["b"] < t1:
                durations.append(rec["b"] - rec["a"])
    if not durations:
        return None
    return float(np.quantile(durations, float(q))) * 1e3
