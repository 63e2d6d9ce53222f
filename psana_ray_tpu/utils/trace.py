"""Device-level tracing: jax.profiler integration for the streaming path.

The reference has no profiling story at all (SURVEY.md §5 — "no tracing,
no timeline; debugging a slow consumer means print statements"). Counters
and latency quantiles live in :mod:`psana_ray_tpu.utils.metrics`; this
module adds the device timeline half: XLA/TPU traces viewable in
TensorBoard or Perfetto (``tensorboard --logdir <dir>`` -> Profile tab).

Three surfaces:

- :func:`trace` — context manager capturing a device trace of the
  enclosed block (producer/consumer loops, a benchmark window);
- :func:`annotate` — named region that shows up on the trace timeline;
- :class:`phase` — THE way a serving loop marks what its thread is doing:
  one mark feeds the profiler timeline, the flame sampler's stage tags,
  the stage histograms and the span spool.

A trace that cannot be started or written raises: a caller that asked
for a device timeline must not get a run without one.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Iterator, Optional

from psana_ray_tpu.obs.profiling.stagetag import (
    TAG_OF_STAGE,
    TAG_UNTAGGED,
    set_stage,
    swap_stage,
)
from psana_ray_tpu.obs.tracing import TRACER, profiler_annotation

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


class phase:
    """One phase of a serving thread's loop (:data:`psana_ray_tpu.obs.
    stages.PHASES`), marked ONCE::

        with phase(PHASE_LAUNCH, metrics, batch.batch_id, batch.num_valid):
            out = step(batch)

    - a ``stage.<name>`` region on the profiler's timeline, beside the
      device ops and on their clock (``jax.profiler.TraceAnnotation``;
      skipped in a process that never imported jax, where no profile can
      be running);
    - the calling thread's tag for the flame sampler, restored on exit;
    - on exit ONE observation of the duration in ``metrics.stages``
      (``metrics``: a ``PipelineMetrics`` or None) and, while the tracer
      is on, ONE ``stage.<name>`` span in its spool under ``batch_id``
      with the ``frames`` the batch or loop turn held and the ``nbytes``
      it moved (kept under the phases' own bound, ``Tracer.phase_span``).

    Phases of one thread are consecutive and never nested, and together
    cover the loop body: the benchmark bills each idle gap of the device
    to the phase the host was in. ``t0``/``t1`` (monotonic seconds) stay
    readable after exit; a loop may set ``batch_id``/``frames``/``nbytes``
    inside the region once it knows them, widen ``t0`` to the start of a wait
    that spanned several polls, or clear ``record`` for a turn that has
    nothing to report (an empty poll): the timeline region and the tag
    are kept, the histogram and the spool skipped. An object may be
    entered again and again (a loop that turns a thousand times a second
    builds its phases once); each entry starts with ``record`` set."""

    __slots__ = ("name", "batch_id", "frames", "nbytes", "record", "t0", "t1",
                 "_metrics", "_tag", "_region", "_ann", "_prev")

    def __init__(self, name: str, metrics=None, batch_id: int = 0, frames: int = 0):
        self.name = name
        self.batch_id = batch_id
        self.frames = frames
        self.nbytes = 0
        self.record = True
        self.t0 = self.t1 = 0.0
        self._metrics = metrics
        self._tag = TAG_OF_STAGE.get(name, TAG_UNTAGGED)
        self._region = "stage." + name
        self._ann = None
        self._prev = TAG_UNTAGGED

    def __enter__(self) -> "phase":
        self.record = True
        self._prev = swap_stage(self._tag)
        ann = self._ann = profiler_annotation(self._region)
        if ann is not None:
            ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = self.t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        set_stage(self._prev)
        if self.record:
            if self._metrics is not None:
                self._metrics.stages.observe(self.name, t1 - self.t0)
            if TRACER.enabled:
                TRACER.phase_span(
                    self.batch_id, self._region, self.t0, t1, self.frames, self.nbytes)
        return False
