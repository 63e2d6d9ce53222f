"""A kernel's share of its roofline where one run of the compiled program
calls it a varying number of times on a varying number of rows (a loop
over the rows HELD: ``parallel/moe._held_rows_moe``), in %: the least
time the chip could take for ALL the kernel's calls of one run, from a
function under ``benchmark/roofline/`` applied to the configuration's
shapes and to the quotient of two of the program's counters
(``held_share``: how much of the work fell to this holder, over the
window), over the summed device time of the Pallas calls (``leaf``:
the name stack's last component) under the ``jax.named_scope`` the loop
stands under, in one run of the program, the median over the runs that
lie wholly in the traced window.

By scope and primitive and not by the kernel's name: inside a loop's body
XLA names a Pallas call by its opcode or its kernel function
(``%tpu_custom_call.3``, ``%_lambda_.11``), and the name stack keeps the
scopes around the loop only (``readers/trace_scope_leaf_time.py``). So what
is read is EVERY Pallas kernel under the scope, and the roofline function
says how many call sites its kernel has in the program (``call_sites``:
three grouped products an expert layer). At this cell's width the grouped
product is the expert layer's only Pallas kernel (the row gather's kernel
takes rows of whole 2,048-column tiles: 7,168 goes by XLA's gather;
``tests/test_chip_compile.py`` pins the three calls of the compiled
layer). Where another number of Pallas instructions ran under the scope,
another kernel has joined it (a row scatter, a wider row gather) or one
has left: the summed time is then no longer the counted kernel's, and the
reader says so on stderr, names the instructions, and reads nothing. A
median over single calls would mix a full chunk with a last, nearly empty
one. Never clamped. A program without the counters or without the scope
(the parent of the PR that added them), or a trace without a whole run,
gives nothing to read."""

import importlib
import sys

from benchmark.readers import program_counter_ratio, trace_scope_leaf_time
from benchmark.readers.roofline_share import _lookup
from benchmark.readers.trace_event_time import resolve


def read(ctx, scope: str, leaf: str, within: str, function: str, shape_from: dict, share: dict):
    scopes = trace_scope_leaf_time.profile_scopes(ctx)
    held_share = program_counter_ratio.read(ctx, **share) if scopes is not None else None
    if held_share is None:
        return None
    t0, t1 = ctx.trace_window
    ran = set()
    ms = trace_scope_leaf_time.leaf_scope_ms(ctx.trace, scopes, scope, resolve(ctx, within),
                                             t0, t1, leaf, ran)
    if not ms:
        return None
    module, fn = function.rsplit(".", 1)
    need = getattr(importlib.import_module(f"benchmark.roofline.{module}"), fn)(
        held_share=held_share, **{k: _lookup(ctx.cfg, path) for k, path in shape_from.items()})
    if len(ran) != need["call_sites"]:
        print(f"[bench] roofline_share_per_run: {len(ran)} {leaf} instructions ran under scope "
              f"{scope!r} where {function} counts {need['call_sites']} call sites: the scope's "
              f"time is not that kernel's alone, nothing read: {sorted(ran)}",
              file=sys.stderr, flush=True)
        return None
    least_s = max(need["flops"] / ctx.peaks["bf16_flops_per_s"],
                  need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return least_s / (ms / 1e3) * 100.0
