"""A kernel's share of its roofline, in %: the least time the chip could
take for the call — the larger of needed operations over peak FLOP/s and
needed bytes over peak bytes/s, both from a function under
``benchmark/roofline/`` applied to the call's shapes — over the kernel's
median time in the device trace. Peaks come from ``benchmark/peaks.json``
by ``device_kind``. Never clamped: a share over 100% means the bytes or
operations are counted too high or the time misses part of the work."""

import importlib

from benchmark import trace_reduce
from benchmark.readers.trace_event_time import resolve


def read(ctx, pattern: str, function: str, shape_from: dict):
    if ctx.trace is None:
        return None
    module, fn = function.rsplit(".", 1)
    need = getattr(importlib.import_module(f"benchmark.roofline.{module}"), fn)(
        **{k: _lookup(ctx.cfg, path) for k, path in shape_from.items()}
    )
    t0, t1 = ctx.trace_window
    per_chip = trace_reduce.named_events(ctx.trace, resolve(ctx, pattern), "XLA Ops", t0, t1)
    med = trace_reduce.median([e[2] for evs in per_chip.values() for e in evs])
    if not med:
        return None
    least_s = max(need["flops"] / ctx.peaks["bf16_flops_per_s"],
                  need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return least_s / (med / 1e9) * 100.0


def _lookup(cfg: dict, path: str):
    node = cfg
    for key in path.split("."):
        node = node[key]
    return node
