"""Device-level tracing: jax.profiler integration for the streaming path.

The reference has no profiling story at all (SURVEY.md §5 — "no tracing,
no timeline; debugging a slow consumer means print statements"). Counters
and latency quantiles live in :mod:`psana_ray_tpu.utils.metrics`; this
module adds the device timeline half: XLA/TPU traces viewable in
TensorBoard or Perfetto (``tensorboard --logdir <dir>`` -> Profile tab).

Two surfaces:

- :func:`trace` — context manager capturing a device trace of the
  enclosed block (producer/consumer loops, a bench section);
- :func:`annotate` — named region that shows up on the trace timeline
  (wrap one pipeline stage: batch assembly, device put, step dispatch).

A trace that cannot be started or written raises: a caller that asked
for a device timeline must not get a run without one.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Iterator, Optional

logger = logging.getLogger(__name__)


def start_trace_python_tracer_off(jax, path: str) -> None:
    """``jax.profiler.start_trace`` with the python tracer OFF.

    On long captures the python tracer's host events flood the trace
    (observed hitting the xprof converter's 1M-event cap with ZERO device
    events surviving) — the device timeline is what these traces are for."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)


@contextlib.contextmanager
def trace(logdir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler device trace into ``logdir``.

    ``logdir=None`` disables tracing (zero overhead) so callers can wire
    an optional ``--profile_dir`` flag straight through. Traces from
    repeated runs land in distinct subdirectories (timestamped) the way
    TensorBoard expects.
    """
    if not logdir:
        yield
        return
    import jax

    path = os.path.join(logdir, time.strftime("%Y%m%d-%H%M%S"))
    start_trace_python_tracer_off(jax, path)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logger.info("device trace written to %s", path)


def annotate(name: str):
    """Named region on the profiler timeline (host + device annotation).

    Usable as context manager. No-op outside an active
    trace; safe to leave in hot loops (TraceAnnotation is a thin RAII
    wrapper around a TraceMe)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def annotate_stage(stage: str):
    """Timeline region for one CANONICAL pipeline stage
    (:data:`psana_ray_tpu.obs.stages.STAGES`), named ``stage.<name>`` —
    the device-trace half of the stage-timing story: the same stage names
    that label the latency histograms on the metrics endpoint label the
    regions on the TensorBoard/Perfetto timeline, so a p99 outlier in
    ``queue_dwell`` vs ``device_put`` points at the same vocabulary in
    both tools.

    Also tags the calling thread for the continuous profiler
    (ISSUE 16): flame samples taken inside the region bill to this
    stage, so ``device_put``/``dispatch`` CPU shows up in the same
    vocabulary on the CPU flame as on the device timeline."""
    from psana_ray_tpu.obs.profiling.stagetag import stage_region

    return stage_region(stage, annotate(f"stage.{stage}"))
