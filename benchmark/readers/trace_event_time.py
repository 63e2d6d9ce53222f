"""The median device duration, in ms, of the events of one line of the
device trace whose name matches a pattern: a compiled program's runs
(``XLA Modules``, ``jit_<fn>(...)``) or one kernel's (``XLA Ops``).
``pattern`` may name a key of the configuration's ``trace_names``
(``@step``), so one metric file serves every configuration. On a mesh
the median is over all chips' events."""

import re

from benchmark import trace_reduce


def resolve(ctx, pattern: str) -> str:
    if pattern.startswith("@"):
        return "^%?" + re.escape(ctx.cfg["trace_names"][pattern[1:]])
    return pattern


def read(ctx, pattern: str, line: str = "XLA Modules"):
    if ctx.trace is None:
        return None
    t0, t1 = ctx.trace_window
    per_chip = trace_reduce.named_events(ctx.trace, resolve(ctx, pattern), line, t0, t1)
    durations = [e[2] for evs in per_chip.values() for e in evs]
    med = trace_reduce.median(durations)
    return None if med is None else med / 1e6
