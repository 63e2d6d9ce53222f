"""What the program's own compile-path listener (``psana_ray_tpu/obs/
jitwatch.py``, installed by ``jaxenv.configure_compile_cache()``) heard
between process start and the start of the window: JAX's timings of every
trace (``jit.trace``), lowering (``jit.lower``), load from the persistent
cache (``jit.cache_load``) and compile (``jit.compile``), as rows ``(kind,
fun_name, t0, t1, thread)`` on ``time.monotonic()``: the clock of the
harness's marks and of the sleeping child's stops. Read from the run's own
process, in memory: an untraced start has the record too.

``kind`` alone: the seconds of that kind in set-up, the UNION of its rows
on each thread (trace events nest; a sum of durations would count an inner
jit once for itself and once for every function around it), clipped to
``[t_process, window start]``, LESS the seconds of it during which the
sandbox stood still (``stops.overlap_s``, as ``setup_s`` does). ``count``:
the rows of that kind that end in set-up (``startup_cache_misses``: the
programs this start had to compile). ``rest_of``: the named phase of the
harness (``warm_up``) less its stops and less the union of ALL four kinds
inside it, whatever the thread: the transfers, the executables' load onto
the chip and the two runs, what no compile-path work explains.

``None`` where the program has no such listener (an older package), and
where the record DROPPED a row of set-up (the bound is 10,000 rows a
process, kept in the order they end: the record is whole up to the
earliest start among the dropped rows, ``dropped_from()``): a short
record would read low, so it reads nothing and says so. Rows dropped
after the window opened (the reference check's own compiles come last)
take nothing from the start's account."""

import sys

from benchmark import stops
from benchmark.trace_reduce import union


def clipped(rows, kind, t0: float, t1: float, by_thread: bool) -> list:
    """The intervals of the rows of ``kind`` (``None``: of every kind),
    cut to ``[t0, t1]`` and merged: a thread at a time, or all together."""
    groups = {}
    for row_kind, _, a, b, thread in rows:
        a, b = max(a, t0), min(b, t1)
        if kind in (None, row_kind) and b > a:
            groups.setdefault(thread if by_thread else 0, []).append((a, b))
    return [iv for ivs in groups.values() for iv in union(ivs)]


def seconds_less_stops(intervals, gaps) -> float:
    return float(sum((b - a) - stops.overlap_s(gaps, a, b) for a, b in intervals))


def account(rows, ctx, kind=None, count=False, rest_of=None):
    """The reading, from rows in hand (the tests' way in)."""
    t0, t1 = ctx.t_process, ctx.window[0]
    if rest_of is not None:
        if rest_of not in ctx.phases:
            return None
        a, b = ctx.phases[rest_of]
        busy = seconds_less_stops(clipped(rows, None, a, b, by_thread=False), ctx.stops)
        return float((b - a) - stops.overlap_s(ctx.stops, a, b) - busy)
    if count:
        return int(sum(1 for r in rows if r[0] == kind and t0 <= r[3] <= t1))
    return seconds_less_stops(clipped(rows, kind, t0, t1, by_thread=True), ctx.stops)


def read(ctx, kind=None, count=False, rest_of=None):
    if ctx.stops is None or ctx.t_process is None:
        return None
    try:
        from psana_ray_tpu.obs.jitwatch import WATCH
    except ImportError:
        return None  # this package has no listener: nothing to read
    if not WATCH.installed:
        return None
    rows, dropped = WATCH.record()
    if dropped and WATCH.dropped_from() < ctx.window[0]:
        print(f"[bench] startup_spans: the listener dropped {dropped} rows past its bound of "
              f"{WATCH.max_rows}, the first of them inside set-up: a short record reads low, "
              f"so it is not read", file=sys.stderr, flush=True)
        return None
    return account(rows, ctx, kind=kind, count=count, rest_of=rest_of)
