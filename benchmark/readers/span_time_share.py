"""The share of the window, in %, that one thread spent inside the named
spans of the program's own spool (``obs.tracing.TRACER``; a loop phase's
span is ``stage.<name>``): the sum of the parts of those spans that lie
inside the window, over the window. A span is CLIPPED to the window, not
counted by where it ended: a ``stage.queue_wait`` can be seconds long.
The names given are phases of ONE thread, which never overlap, so the
shares of a thread's phases add up to the part of the window it spent in
any of them.

Nothing to read, with the reason on the standard error, where the spool
says that the tracer dropped a loop phase's span (its tally lines,
``{"t":"d",...,"phase_dropped":n}``): a sum over what is left would be a
lower bound under the metric's name. A program whose spool lacks one of
the named spans altogether (the parent of the PR that named the phase)
gives nothing to read either."""

import json
import sys


def read(ctx, spans):
    if not ctx.spool_path:
        return None
    t0, t1 = ctx.window
    if t1 <= t0:
        return None
    inside = dict.fromkeys(spans, None)  # name -> seconds inside the window
    with open(ctx.spool_path, encoding="utf-8") as f:
        for line in f:
            rec = json.loads(line)
            kind = rec.get("t")
            if kind == "d" and rec.get("phase_dropped"):
                print(f"[bench] span_time_share: the tracer dropped {rec['phase_dropped']} "
                      f"phase spans, so {list(spans)} do not add up: nothing read",
                      file=sys.stderr, flush=True)
                return None
            if kind == "s" and rec.get("n") in inside:
                part = min(rec["b"], t1) - max(rec["a"], t0)
                inside[rec["n"]] = (inside[rec["n"]] or 0.0) + max(part, 0.0)
    if any(v is None for v in inside.values()):
        return None
    return sum(inside.values()) / (t1 - t0) * 100.0
