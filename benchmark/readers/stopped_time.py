"""Time the sandbox stood still, as the sleeping child beside the run saw
it (``benchmark/stops.py``: a tick every 2 ms, every gap over 20 ms): over
``span`` ``"setup"`` (process start to the window's start, what ``setup_s``
leaves out) or ``"window"``. ``scale`` multiplies (1000 for ms). A run in
which the child saw no stop reads 0: that is a reading, not a lack of one."""

from benchmark import stops


def read(ctx, span: str, scale: float = 1.0):
    if ctx.stops is None:
        return None
    t0, t1 = {"setup": (ctx.t_process, ctx.window[0]), "window": tuple(ctx.window)}[span]
    return float(stops.overlap_s(ctx.stops, t0, t1)) * float(scale)
