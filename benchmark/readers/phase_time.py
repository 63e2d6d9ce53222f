"""Seconds one phase of set-up took, between two of the harness's marks
(``imports``, ``native_ring``, ``generator_spawn``, ``jax_import``,
``device_open``, ``build``, ``warm_up``, ``generator_ready``, ``lead``),
less the seconds of it during which the sandbox stood still (those are
in ``setup_stopped_s``)."""

from benchmark import stops


def read(ctx, phase: str):
    if phase not in ctx.phases or ctx.stops is None:
        return None
    a, b = ctx.phases[phase]
    return float((b - a) - stops.overlap_s(ctx.stops, a, b))
