"""The median time, in ms, from the END of a device event matching
``after`` (a program's run on ``XLA Modules``) to the START of the next
host annotation called ``until`` that begins after it: for each
annotation, the latest such device end at or before its start.
``result_hold_ms`` reads it as "the step finished; how long until its
result's append began"."""

import bisect

from benchmark import trace_reduce
from benchmark.readers.trace_event_time import resolve


def read(ctx, after: str, until: str, line: str = "XLA Modules"):
    if ctx.trace is None:
        return None
    t0, t1 = ctx.trace_window
    chip = min(ctx.trace.device) if ctx.trace.device else None
    if chip is None:
        return None
    events = trace_reduce.named_events(ctx.trace, resolve(ctx, after), line, t0, t1)[chip]
    ends = sorted(e[1] + e[2] for e in events)
    gaps = []
    for _, start, _ in trace_reduce.annotation_events(ctx.trace, until):
        i = bisect.bisect_right(ends, start)
        if i and t0 <= start <= t1:
            gaps.append(start - ends[i - 1])
    med = trace_reduce.median(gaps)
    return None if med is None else med / 1e6
