"""The share of the traced window, in %, in which chip 0 ran no operation
WHILE a host thread was inside one of the named regions of the profiler's
timeline (``stage.<phase>`` annotations; ``regions`` a list): the idle
gaps of the chip cut with the union of those regions from ALL threads,
over the traced window. It bills by NAME, not by which region began
first: in ``InfeedPipeline`` the serving thread's phases and the
prefetch thread's have disjoint names, so a name is a thread's state,
and a gap that two threads' regions cover counts for both. A trace
without one of the named regions (the parent of the PR that added it)
gives nothing to read."""

from benchmark import trace_reduce


def read(ctx, regions):
    trace = ctx.trace
    if trace is None or not trace.device:
        return None
    t0, t1 = ctx.trace_window
    if t1 <= t0:
        return None
    events = []
    for name in regions:
        found = trace_reduce.annotation_events(trace, name)
        if not found:
            return None
        events.extend(found)
    cover = trace_reduce.union(trace_reduce.clip(events, t0, t1))
    gaps = trace_reduce.idle_gaps(trace, min(trace.device), t0, t1)

    def length(intervals):
        return sum(b - a for a, b in intervals)

    # |gaps AND cover| = |gaps| + |cover| - |gaps OR cover|, each list disjoint in itself
    under = length(gaps) + length(cover) - length(trace_reduce.union(gaps + cover))
    return under / (t1 - t0) * 100.0
