"""Seconds from the start of the process to the start of the window:
imports, the native ring's build, JAX start-up, weights, compile or cache
load, warm-up, and the lead-in during which the stream reaches its
steady state."""


def read(ctx):
    return float(ctx.setup_s)
