"""Seconds of set-up, process start to the start of the window, that the
program had and could use: the wall clock LESS the seconds in between
during which the sandbox stood still (the sleeping child's gaps,
``benchmark/stops.py``) and LESS the phases named in ``leave_out`` (the
harness's marks; ``device_open`` is the call that opens the TPU, whose
length is the chip's and the sandbox's, never the repository's: PERF.md
section 6, PR 33). What stays: imports, the native ring's build, ``import
jax``, weights, compile or cache load, warm-up, and the lead-in during
which the stream reaches its steady state, so work moved into set-up
still shows. What goes is reported beside it (``setup_stopped_s`` by
``stopped_time``, ``device_open_s`` by ``phase_time``): a restart by the
wall clock is the sum of the three. A stop after the window's start is not
set-up's (it is the window's ``stopped_ms``)."""

from benchmark import stops


def read(ctx, leave_out=()):
    if ctx.stops is None:
        return None
    t0, t1 = ctx.t_process, ctx.window[0]
    total = t1 - t0 - stops.overlap_s(ctx.stops, t0, t1)
    for phase in leave_out:
        if phase not in ctx.phases:
            return None
        a, b = ctx.phases[phase]
        total -= (b - a) - stops.overlap_s(ctx.stops, a, b)
    return float(total)
