#!/usr/bin/env python3
"""The sleeping child: what the SANDBOX did during a run, seen by a
process that shares nothing with the program.

    python3 -S benchmark/stops.py        # run.py starts it before anything else

The chip machine is a gVisor sandbox that stops as a whole: 105-120 ms at
a time at random, and for seconds while a process of ours opens the TPU
(PERF.md, PR 26 second session: sleepers and a spinner frozen together to
0.1 ms). The child sleeps ``TICK_S`` at a time, reads the monotonic clock
(one timeline for every process of a host) and notes every interval
between two ticks longer than ``GAP_S``. It imports nothing of the
program or of JAX, opens no file and makes no other system call (but one
``getppid`` a second, so that it never outlives a parent that was killed)
until it is told to end (SIGTERM), when it prints its record as one JSON
line and exits. ``run.py`` takes two sums from the record (:func:`overlap_s`): the
stopped seconds between process start and the window's start, which
``setup_s`` leaves out, and the stopped milliseconds inside the window.

A gap is what the child could not see through: it cannot tell a stop of
the sandbox from its own starvation. It asks for one core in 500 of a
13-core machine, and the gaps it notes on the chip have the stops'
signature (PERF.md section 6, PR 33); a change that loads every core in
set-up for seconds could hide there, and ``setup_stopped_s`` beside
``setup_s`` is where that would show."""

from __future__ import annotations

TICK_S = 0.002  # the child sleeps this long between two readings of the clock
GAP_S = 0.020  # two readings further apart than this are a stop


def gap_of(prev: float, now: float):
    """``(start, seconds)`` of the stop between two ticks, or ``None``:
    the child was due ``TICK_S`` after ``prev``; what it overslept is the
    stop."""
    if now - prev <= GAP_S:
        return None
    return (prev + TICK_S, now - prev - TICK_S)


def find_gaps(ticks) -> list:
    """Every stop in a series of tick instants, as the child notes them."""
    out = []
    for prev, now in zip(ticks[:-1], ticks[1:]):
        gap = gap_of(float(prev), float(now))
        if gap is not None:
            out.append(gap)
    return out


def inside(gaps, t0: float, t1: float) -> list:
    """The stops (``(start, seconds)`` each) that touch ``[t0, t1)``, cut
    to it, as ``[seconds after t0, seconds long]``."""
    out = []
    for start, seconds in gaps:
        a, b = max(start, t0), min(start + seconds, t1)
        if b > a:
            out.append([a - t0, b - a])
    return out


def overlap_s(gaps, t0: float, t1: float) -> float:
    """Seconds of ``gaps`` that fall inside ``[t0, t1)``; a stop that
    straddles an end is cut there."""
    return sum(seconds for _, seconds in inside(gaps, t0, t1))


def watch() -> int:
    import json
    import os
    import signal
    import sys
    import time

    told = []
    signal.signal(signal.SIGTERM, lambda *_: told.append(1))
    signal.signal(signal.SIGINT, signal.SIG_IGN)  # the parent decides when
    monotonic, sleep = time.monotonic, time.sleep
    gaps = []
    first = prev = monotonic()
    ticks = 0
    parent = os.getppid()
    while not told:
        sleep(TICK_S)
        now = monotonic()
        gap = gap_of(prev, now)
        if gap is not None:
            gaps.append(gap)
        prev = now
        ticks += 1
        if ticks % 500 == 0 and os.getppid() != parent:
            return 1  # orphaned: nobody is left to read the record
    sys.stdout.write(json.dumps(
        {"first": first, "last": prev, "ticks": ticks, "gaps": gaps}) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(watch())
