"""A compiled program's share of the chip's peak, in %: the model FLOPs of
one run — from a function under ``benchmark/roofline/`` applied to the
configuration's shapes — over the peak bf16 FLOP/s (``benchmark/peaks.json``
by ``device_kind``) times the program's median device duration
(``XLA Modules``, as ``trace_event_time`` reads it): ``step_mfu`` is the
whole served step's. It says how far the step as a WHOLE is from the chip,
which no kernel's roofline share does. Never clamped: a share over 100%
means the FLOPs are counted too high. A trace without such a program gives
nothing to read."""

import importlib

from benchmark.readers import trace_event_time
from benchmark.readers.roofline_share import _lookup


def read(ctx, pattern: str, function: str, shape_from: dict):
    ms = trace_event_time.read(ctx, pattern)
    if not ms:
        return None
    module, fn = function.rsplit(".", 1)
    need = getattr(importlib.import_module(f"benchmark.roofline.{module}"), fn)(
        **{k: _lookup(ctx.cfg, path) for k, path in shape_from.items()})
    return need["flops"] / ctx.peaks["bf16_flops_per_s"] / (ms / 1e3) * 100.0
