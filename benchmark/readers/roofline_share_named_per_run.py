"""``roofline_share_per_run`` for a kernel whose instructions keep their
NAME, read beside other Pallas kernels under the same scope: the least
time the chip could take for the kernel's calls of one run that stand
OUTSIDE a loop, over the summed device time of the instructions under the
scope whose name matches ``name`` (``gmm``, ``gmm.7``), in one run of the
program, the median over the runs that lie wholly in the traced window,
in %.

Why a second reader: ``roofline_share_per_run`` reads EVERY Pallas call
under the scope, because a loop's body loses its kernels' names, and reads
nothing once a second kernel joins the scope. A holder of a LARGE share of
the experts (``parallel/moe._held_rows_ahead``) runs its grouped products
in one pass AHEAD of the held rows' loop, outside it, where the profile
names them ``%gmm``; since PR 52 the pass's way back is Pallas kernels of
its own under the same scope (``rows_as_words``, ``sum_counted_rows``),
and the products are told from them by name.

What the named instructions worked on is the rows the PASS took, the
program's own count (``share``: ``expert_rows_ahead_total /
expert_rows_routed_total``); rows beyond the pass go through the loop,
whose products the profile names by opcode: they match no name, and
neither their time nor their rows are counted here. A program that does
not count the pass's rows (the parent of PR 52) is read by the rows HELD
(``share_where_alone``) where the named instructions are the only Pallas
calls that ran under the scope: the loop then stood idle and the pass took
every held row. Where others ran there, nothing says how the rows were
split, and nothing is read (stderr says so). The roofline function says
how many call sites the kernel has outside the loop (``call_sites``):
where another number of named instructions ran, nothing is read either.
Never clamped."""

import importlib
import re
import sys

from benchmark.readers import program_counter_ratio, trace_scope_leaf_time
from benchmark.readers.roofline_share import _lookup
from benchmark.readers.trace_event_time import resolve


def read(ctx, scope: str, leaf: str, name: str, within: str, function: str, shape_from: dict,
         share: dict, share_where_alone: dict):
    scopes = trace_scope_leaf_time.profile_scopes(ctx)
    if scopes is None:
        return None
    t0, t1 = ctx.trace_window
    step = resolve(ctx, within)
    rx = re.compile(name)
    named, ran = set(), set()
    ms = trace_scope_leaf_time.leaf_scope_ms(
        ctx.trace, {n: op for n, op in scopes.items() if rx.fullmatch(n)}, scope, step, t0, t1,
        leaf, named)
    if not ms:
        return None
    rows_share = program_counter_ratio.read(ctx, **share)
    if rows_share is None:  # no count of the pass's rows: the held rows', where the pass took them all
        trace_scope_leaf_time.leaf_scope_ms(ctx.trace, scopes, scope, step, t0, t1, leaf, ran)
        if ran != named:
            print(f"[bench] roofline_share_named_per_run: the program counts no {share['numerator']} "
                  f"and other {leaf} instructions ran under scope {scope!r} beside the "
                  f"{len(named)} named {name!r}: how the rows were split is not known, nothing "
                  f"read: {sorted(ran - named)}", file=sys.stderr, flush=True)
            return None
        rows_share = program_counter_ratio.read(ctx, **share_where_alone)
        if rows_share is None:
            return None
    module, fn = function.rsplit(".", 1)
    need = getattr(importlib.import_module(f"benchmark.roofline.{module}"), fn)(
        held_share=rows_share, **{k: _lookup(ctx.cfg, path) for k, path in shape_from.items()})
    if len(named) != need["call_sites"]:
        print(f"[bench] roofline_share_named_per_run: {len(named)} {leaf} instructions named "
              f"{name!r} ran under scope {scope!r} where {function} counts {need['call_sites']} "
              f"call sites, nothing read: {sorted(named)}", file=sys.stderr, flush=True)
        return None
    least_s = max(need["flops"] / ctx.peaks["bf16_flops_per_s"],
                  need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return least_s / (ms / 1e3) * 100.0
