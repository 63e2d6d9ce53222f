"""Canonical pipeline stage names + per-record latency decomposition.

Every frame crosses the same boundaries on its way from detector source to
device step; this module names them ONCE so the record envelope
(:func:`psana_ray_tpu.records.mark_hop`), the latency histograms
(:class:`psana_ray_tpu.utils.metrics.StageTimes`), the Prometheus export,
and the device-timeline annotations (:func:`psana_ray_tpu.utils.trace.
phase`) all agree.

Hop boundaries (monotonic timestamps stamped on the record)::

    src ──enqueue──▶ enq ──queue_dwell──▶ deq ──dequeue──▶ push
        ──batch──▶ batch ──device_put──▶ device_put ──dispatch──▶ (step done)

Stage semantics:

- ``enqueue``      source read done → accepted by the transport
  (includes producer-side backpressure wait);
- ``queue_dwell``  accepted → popped by a consumer (queue residency);
- ``dequeue``      popped → copied into the batch buffer (decode + memcpy);
- ``batch``        in the batch buffer → batch emitted (waiting for the
  batch to fill; first records of a batch wait longest);
- ``device_put``   batch emitted → staged on device (host→device copy,
  or global sharded assembly on multi-host);
- ``dispatch``     staged → step returned (prefetch-buffer dwell + device
  step; with ``block_until_ready`` a true device latency).

Loop phases (what a serving THREAD is doing, marked once per loop turn or
per batch by :func:`psana_ray_tpu.utils.trace.phase`; consecutive, never
nested, covering the whole loop body)::

    batches_from_queue   queue_wait -> decode -> copy
    DevicePrefetcher     device_put -> prefetch_full
      (its watcher)      h2d_tail, while the tracer is on
    InfeedPipeline.run   infeed_wait -> launch -> device_wait -> (on_result)
    SfxPipeline.run      (the batcher's three) -> [put_ahead] -> launch
                         -> device_wait -> fold -> append

``SfxPipeline.run`` drains a batch (``device_wait -> fold -> append``)
after the next batch's ``launch`` or, when its result is ready sooner,
between two turns of the batcher: after one turn's ``copy``, before the
next turn's ``queue_wait``, never inside a turn. At the same place,
after that drain, it puts the frames that landed without filling the
arena on the device (``put_ahead``: the ``device_put`` call, one span a
turn with the frames and bytes it started), so ``launch`` carries only
the frames of the turn that filled the batch.

A phase is a ``stage.<name>`` region on the profiler's timeline, a tag
for the flame sampler, one span in the trace spool (named ``stage.<name>``
there too, its id the batch's) and, where the loop owns a
``PipelineMetrics``, one observation per batch or turn in its stage
histograms. Hop stages are per FRAME and phases per THREAD, and in the
histograms, the spool and the profile a name means one thing: ``dequeue``
and ``batch`` are a frame's hop stages (popped -> copied in -> batch
emitted), ``decode`` and ``copy`` the batcher thread's two phases of a
turn that popped something. One name serves both vocabularies,
``device_put``: the prefetcher's phase, one value a batch and the same
for all its frames.

``device_put`` times the CALL: ``jax.device_put`` returns while the
bytes still cross. While the tracer is on, a watcher thread beside the
prefetcher waits for every staged batch's arrays (``h2d_tail``, from the
call's return to the bytes' arrival) and writes ONE span ``h2d`` a batch
into the spool under the batch's id: ``device_put`` called -> every
array of the batch on the device.

Because stages are CONSECUTIVE differences of one record's timeline, the
per-stage means over a set of records sum EXACTLY to the mean of the
``e2e`` pseudo-stage (src → step done) over the same records — that is
what lets the gap between device time and end-to-end time decompose
into named stages instead of a single opaque number. A missing boundary (e.g. records that
crossed a process hop, where monotonic stamps don't travel) never breaks
the telescoping: the next present boundary's stage absorbs the gap.
"""

from __future__ import annotations

from typing import Optional

from psana_ray_tpu.obs.tracing import TRACE_KEY, TRACER
from psana_ray_tpu.utils.metrics import StageTimes  # noqa: F401  (re-export)

# Hop (boundary) names, in pipeline order.
HOP_SRC = "src"
HOP_ENQ = "enq"
HOP_DEQ = "deq"
HOP_PUSH = "push"
HOP_BATCH = "batch"
HOP_DEVICE_PUT = "device_put"
# the final boundary (step done) is passed explicitly, never stamped

HOPS = (HOP_SRC, HOP_ENQ, HOP_DEQ, HOP_PUSH, HOP_BATCH, HOP_DEVICE_PUT)

# Stage names: STAGES[i] spans HOPS[i] -> HOPS[i+1]; the last stage spans
# the last hop -> step completion.
STAGE_ENQUEUE = "enqueue"
STAGE_QUEUE_DWELL = "queue_dwell"
STAGE_DEQUEUE = "dequeue"
STAGE_BATCH = "batch"
STAGE_DEVICE_PUT = "device_put"
STAGE_DISPATCH = "dispatch"
STAGE_E2E = "e2e"  # pseudo-stage: src -> step done (the decomposed total)

STAGES = (
    STAGE_ENQUEUE,
    STAGE_QUEUE_DWELL,
    STAGE_DEQUEUE,
    STAGE_BATCH,
    STAGE_DEVICE_PUT,
    STAGE_DISPATCH,
)


# Loop phases (see the module docstring). The first three run once per
# turn of ``batches_from_queue``; the rest once per batch.
PHASE_QUEUE_WAIT = "queue_wait"  # blocked in the transport's pop
PHASE_DECODE = "decode"  # EOS tally, decode, stamps
PHASE_COPY = "copy"  # the copy into the batch arena
PHASE_DEVICE_PUT = STAGE_DEVICE_PUT  # host -> device placement: the call
PHASE_PREFETCH_FULL = "prefetch_full"  # staged batch waits for room
PHASE_H2D_TAIL = "h2d_tail"  # device_put returned -> the bytes are there
PHASE_INFEED_WAIT = "infeed_wait"  # serving thread waits for a staged batch
PHASE_LAUNCH = "launch"  # the step call returns (async dispatch)
PHASE_DEVICE_WAIT = "device_wait"  # host blocks on the step's result
PHASE_FOLD = "fold"  # device rows -> per-event results
PHASE_APPEND = "append"  # sink append + cursor
PHASE_GC = "gc"  # a generation-2 collection, inside whatever phase was open
PHASE_PUT_AHEAD = "put_ahead"  # landed frames put on the device before their batch fills: the call

# One span a staged batch in the spool, beside the phases' (not a phase:
# it overlaps the prefetch thread's next turns): ``device_put`` called ->
# every array of the batch on the device.
SPAN_H2D = "h2d"

PHASES = (
    PHASE_QUEUE_WAIT,
    PHASE_DECODE,
    PHASE_COPY,
    PHASE_DEVICE_PUT,
    PHASE_PREFETCH_FULL,
    PHASE_H2D_TAIL,
    PHASE_INFEED_WAIT,
    PHASE_LAUNCH,
    PHASE_DEVICE_WAIT,
    PHASE_FOLD,
    PHASE_APPEND,
    PHASE_GC,
    PHASE_PUT_AHEAD,  # last: the tags of the phases before it stay what they were
)

# The hops a frame has crossed by the time its batch is emitted: what the
# per-frame fold walks (everything later is the same for a whole batch).
_FRAME_HOPS = (HOP_SRC, HOP_ENQ, HOP_DEQ, HOP_PUSH, HOP_BATCH)


def _legs(hops: dict, boundaries=HOPS):
    """``(stage, start, end)`` for each pair of consecutive PRESENT
    boundaries of one record: THE telescoping walk. A missing boundary is
    skipped, and the stage ending at the next present one absorbs the gap."""
    prev: Optional[float] = None
    for i, hop in enumerate(boundaries):
        t = hops.get(hop)
        if t is None:
            continue
        if prev is not None:
            yield STAGES[i - 1], prev, t
        prev = t


def observe_record_stages(
    stages: StageTimes, hops: dict, t_end: float
) -> None:
    """Fold one record's hop stamps + the step-completion time into the
    per-stage histograms. Missing boundaries are skipped; the stage ending
    at the next present boundary absorbs the gap, so the observed stages
    always telescope to (last boundary - first boundary).

    A traced record (its hops dict carries the sampled trace id under
    ``obs.tracing.TRACE_KEY``) stamps that id as the stage histograms'
    exemplar — the retained "which frame is in the bad bucket" link that
    ``trace_merge --exemplar`` resolves (ISSUE 13)."""
    exemplar = hops.get(TRACE_KEY)  # the sampled trace id, when traced
    for stage, start, end in _legs(hops):
        stages.observe(stage, end - start, exemplar=exemplar)
    last = next((hops[h] for h in reversed(HOPS) if hops.get(h) is not None), None)
    if last is not None:
        stages.observe(STAGE_DISPATCH, t_end - last, exemplar=exemplar)
        t0 = hops.get(HOP_SRC)
        if t0 is not None:
            stages.observe(STAGE_E2E, t_end - t0, exemplar=exemplar)


def observe_frame_stages(stages: StageTimes, batch, tracer=None) -> None:
    """The per-FRAME half of a batch's stage timing, from stamps that
    exist once the batch is emitted (src .. batch): ``enqueue``,
    ``queue_dwell``, ``dequeue`` and ``batch`` of every timed record, the
    same telescoping walk as :func:`observe_record_stages`. A traced
    record (``TRACE_KEY`` in its hops) also gets one span per stage in
    the trace spool, carrying the id of the batch it joined — all of a
    batch's spans under one lock. The serving loops call this AFTER
    launching the batch's step and BEFORE blocking on it, where the host
    would only wait. Untimed streams: ``batch.hops`` is None, no work."""
    hops_list = batch.hops
    if not hops_list:
        return
    tr = TRACER if tracer is None else tracer
    rows = [] if tr.enabled else None
    batch_id = batch.batch_id
    for hops in hops_list:
        tid = hops.get(TRACE_KEY)
        for stage, start, end in _legs(hops, _FRAME_HOPS):
            stages.observe(stage, end - start, exemplar=tid)
            # the enqueue leg is the PRODUCER's span (its sender emitted
            # it; in-process transports share the hops dict)
            if rows is not None and tid is not None and stage != STAGE_ENQUEUE:
                rows.append((tid, stage, start, end, batch_id, 0))
    if rows:
        tr.extend(rows)


def observe_batch_done(stages: StageTimes, batch, t_end: float) -> None:
    """The per-BATCH half, once the batch's result is out (step done for
    ``InfeedPipeline``, append done for ``SfxPipeline``): ``dispatch``
    (staged on the device, ``batch.t_staged``, or else emitted ->
    ``t_end``: one value for all its frames, observed once) and ``e2e`` —
    per frame for a batch whose frames carry stamps (from ``src``, or
    from ``enq`` behind a process hop), else
    ONCE, for the batch's oldest frame (``batch.t_enq``, the transport's
    own enqueue stamp: the worst case of the batch), never both."""
    hops_list = batch.hops
    if not hops_list:
        if batch.t_enq:
            stages.observe(STAGE_E2E, t_end - batch.t_enq)
        return
    last = batch.t_staged or hops_list[0].get(HOP_BATCH)
    if last is not None:
        stages.observe(STAGE_DISPATCH, t_end - last)
    for hops in hops_list:
        t0 = hops.get(HOP_SRC)
        if t0 is None:
            t0 = hops.get(HOP_ENQ)
        if t0 is not None:
            stages.observe(STAGE_E2E, t_end - t0, exemplar=hops.get(TRACE_KEY))
