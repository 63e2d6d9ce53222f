"""``trace_scope_time`` for a scope that holds a LOOP: device time per run
of a compiled program, in ms, of the operations under one
``jax.named_scope``, a loop's body counted once.

The profile's ``XLA Ops`` line carries an event for a ``while`` (and a
``conditional``) that spans the events of its body, and both stand under
the scope: ``trace_scope_time`` adds them all, and read the held rows'
loop of the expert layer twice (134.2 ms of a 690.9 ms step whose other
scopes left 107: my chip run, PR 42). Here the events of the control-flow
instructions themselves are left out and their bodies' stay. ``leaf``
narrows the scope to one primitive's operations, the last component of
the name stack (``pallas_call``: the kernels): an operation in a loop's
body keeps the scopes the LOOP stands under and loses those entered
inside the body (``jit(kimi_k2_step)/moe/jit(mlp)/pallas_call``, looked
at on a v5e trace, PR 42), and its instruction is named after its opcode
there, so neither a scope of its own nor the kernel's name finds it. A
profile whose programs name no such scope gives nothing to read."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Optional

from benchmark import trace_reduce
from benchmark.readers.trace_event_time import resolve
from benchmark.readers.trace_scope_time import INSTRUCTION, in_scope, load_scopes

CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*$")  # its body's events are the work


def leaf_scope_ms(trace, scopes: Dict[str, str], scope: str, within: str,
                  t0: float, t1: float, leaf: Optional[str] = None,
                  ran: Optional[set] = None) -> Optional[float]:
    """``trace_scope_time.scope_ms`` without the control-flow instructions'
    own events: per run of the program matching ``within`` that lies wholly
    inside ``[t0, t1]``, the summed duration of the other ``XLA Ops`` events
    that start inside the run and whose instruction is under ``scope``; the
    median over runs, in ms. ``ran``, where given, takes the names of the
    instructions whose events were summed."""
    wanted = {name for name, op_name in scopes.items()
              if in_scope(op_name, scope) and not CONTAINER.match(name)
              and (leaf is None or op_name.rsplit("/", 1)[-1] == leaf)}
    if not wanted:
        return None
    runs = trace_reduce.named_events(trace, within, trace_reduce.LINE_MODULES, t0, t1)
    totals = []
    for chip, events in runs.items():
        scoped = [
            (m.group(1), s, d) for name, s, d in trace.device[chip].get(trace_reduce.LINE_OPS, [])
            if (m := INSTRUCTION.match(name)) is not None and m.group(1) in wanted
        ]
        for _, start, duration in events:
            inside = [(name, d) for name, s, d in scoped if start <= s < start + duration]
            totals.append(sum(d for _, d in inside))
            if ran is not None:
                ran.update(name for name, _ in inside)
    med = trace_reduce.median(totals)
    return None if med is None else med / 1e6


def profile_scopes(ctx) -> Optional[Dict[str, str]]:
    """Instruction -> op_name of the traced run's profile (beside the span
    spool, as ``trace_scope_time.read`` finds it); None without one."""
    if ctx.trace is None or not ctx.spool_path:
        return None
    work = os.path.dirname(os.path.dirname(ctx.spool_path))
    paths = glob.glob(os.path.join(work, "trace", "**", "*.xplane.pb"), recursive=True)
    return load_scopes(paths[0]) if paths else None


def read(ctx, scope: str, within: str = "@step", leaf: Optional[str] = None):
    scopes = profile_scopes(ctx)
    if scopes is None:
        return None
    t0, t1 = ctx.trace_window
    return leaf_scope_ms(ctx.trace, scopes, scope, resolve(ctx, within), t0, t1, leaf)
