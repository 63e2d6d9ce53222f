"""The median time, in ms, from the START of the host region ``launch``
(``stage.launch``: the step call) to the START of the device run it led
to: for each run of ``step`` on ``XLA Modules`` of chip 0 inside the
traced window, its start less the start of the latest such region at or
before it. Where the device is idle at every launch (a paced stream
under capacity) that is the launch itself plus what the runtime does
before the program can start: the implicit host-to-device copy of the
step's arguments. Where steps queue behind one another it is the queue,
and says nothing of the copy."""

import bisect

from benchmark import trace_reduce
from benchmark.readers.trace_event_time import resolve


def read(ctx, step: str, launch: str, line: str = "XLA Modules"):
    trace = ctx.trace
    if trace is None or not trace.device:
        return None
    t0, t1 = ctx.trace_window
    runs = trace_reduce.named_events(trace, resolve(ctx, step), line, t0, t1)[min(trace.device)]
    starts = [e[1] for e in trace_reduce.annotation_events(trace, launch)]
    leads = []
    for _, start, _ in runs:
        i = bisect.bisect_right(starts, start)
        if i:
            leads.append(start - starts[i - 1])
    med = trace_reduce.median(leads)
    return None if med is None else med / 1e6
