"""Fixed-shape batching with pad+mask for partial batches.

pjit compiles one program per input shape; a variable-rate stream must
therefore never present a short batch (SURVEY.md §7 hard part (b)). The
batcher assembles ``[B, P, H, W]`` stacks; on EOS flush, the tail batch is
padded to B and a per-row validity mask marks real rows. Metadata
(shard_rank, event_idx, photon_energy) rides along as arrays so provenance
survives into the pjit'd world (the reference's `(rank, idx)` stamp,
``producer.py:101``).
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.obs.stages import (
    HOP_BATCH,
    HOP_DEQ,
    HOP_ENQ,
    HOP_PUSH,
    PHASE_COPY,
    PHASE_DECODE,
    PHASE_QUEUE_WAIT,
)
from psana_ray_tpu.obs.tracing import TRACE_KEY, TRACER
from psana_ray_tpu.records import EndOfStream, EosTally, FrameRecord, mark_hop
from psana_ray_tpu.transport.recovery import return_to_queue
from psana_ray_tpu.transport.registry import TransportClosed, TransportWedged
from psana_ray_tpu.utils.bufpool import WIRE
from psana_ray_tpu.utils.trace import phase

# Batch ids, unique in the process whatever the number of batchers: the id
# the phases' spans carry in the trace spool, and the one a traced frame's
# own spans name as the batch it joined.
_BATCH_IDS = itertools.count(1)


class DrainControl:
    """The live dial of :func:`batches_from_queue`: ``poll_s`` is the
    starvation poll interval (None = the call's ``poll_interval_s``).
    The drain loop re-reads it every turn — a plain attribute read — so
    the consumer that owns the loop's thread (``SfxPipeline.run``, from
    its hooks) shortens the wait while a device step runs."""

    __slots__ = ("poll_s",)

    def __init__(self, poll_s: Optional[float] = None):
        self.poll_s = poll_s


class StreamStalled(RuntimeError):
    """A stream went silent — no data AND no EOS for longer than the
    caller's stall budget. Distinct from :class:`TransportClosed` (the
    transport is still up; the producer side is just not feeding it) so
    multi-host consumers can degrade the leg loudly instead of hanging
    the pod's collective schedule (VERDICT r4 weak #6)."""


@dataclasses.dataclass
class Batch:
    """One fixed-shape batch of frames + aligned metadata.

    ``valid`` marks real rows (padding rows are zeros with valid=0); all
    arrays have leading dim B regardless of how many events remain.
    ``num_valid`` is a plain host int (known at assembly time) so consumers
    never force a device sync just to count rows.
    """

    frames: np.ndarray  # [B, P, H, W]
    valid: np.ndarray  # [B] uint8
    shard_rank: np.ndarray  # [B] int32
    event_idx: np.ndarray  # [B] int64
    photon_energy: np.ndarray  # [B] float32
    num_valid: int = -1
    # Host-only observability metadata: one hop-stamp dict per TIMED real
    # record (psana_ray_tpu.records.mark_hop), None for untimed streams.
    # Deliberately NOT part of map_arrays — device placement and global
    # assembly must never touch it (dataclasses.replace carries it along).
    hops: Optional[List[dict]] = None
    # More host-only scalars, carried along the same way. ``batch_id``
    # (0 = not from a batcher) names the batch in the trace spool.
    # ``t_enq`` is the OLDEST enqueue stamp among its frames (monotonic
    # seconds, from a transport that stamps its slots; 0.0 = unknown):
    # an enqueue -> result ``e2e`` once per batch on untimed streams,
    # with no per-frame object. ``t_staged`` is the instant the
    # prefetcher had it on the device (0.0 = never staged by one).
    batch_id: int = 0
    t_enq: float = 0.0
    t_staged: float = 0.0

    def __post_init__(self):
        if self.num_valid < 0:
            self.num_valid = int(np.asarray(self.valid).sum())

    @property
    def batch_size(self) -> int:
        return len(self.frames)

    def map_arrays(self, fn) -> "Batch":
        """A copy with ``fn`` applied to every per-row array field (frames,
        valid, and metadata) — THE single enumeration of those fields, so
        device placement (pipeline) and global assembly (multihost) cannot
        drift when a field is added. ``num_valid`` (host int) passes
        through untouched."""
        return dataclasses.replace(
            self,
            frames=fn(self.frames),
            valid=fn(self.valid),
            shard_rank=fn(self.shard_rank),
            event_idx=fn(self.event_idx),
            photon_energy=fn(self.photon_energy),
        )


class FrameBatcher:
    """Accumulates FrameRecords into fixed-shape Batches.

    ``push`` returns a completed Batch or None; ``flush`` pads and returns
    the tail (or None if empty). Frame shape is locked by the first record —
    a mismatched frame raises (one batcher per detector; multi-detector
    fan-in uses one batcher per stream, see models/multi-detector configs).

    Records are copied into the batch buffer EAGERLY at push time (not
    held and stacked at emit), so a record's frame memory is releasable
    the moment ``push`` returns — that is what lets transports reuse
    decode scratch and keeps at most one frame alive beyond the batch.

    ``n_buffers > 0`` preallocates that many batch-buffer sets and reuses
    them round-robin instead of allocating ~batch-size x frame-size fresh
    per batch (at epix scale a fresh 138 MB allocation is re-page-faulted
    every batch, which costs more than the copy into it). CONTRACT: a pooled Batch's arrays are overwritten
    ``n_buffers`` batches later, so ``n_buffers`` must EXCEED the maximum
    number of batches simultaneously alive anywhere downstream — queued
    in a prefetcher or merge queue, held by the consumer, still being
    transferred (an async/aliasing device_put may read the host buffer
    after the batcher moved on; on CPU backends the "device" array can
    alias the pooled memory outright), or sitting un-yielded in
    :func:`batches_from_queue`'s ready list (it defers yields until
    every transport lease from a pop is released, so one completed
    batch — plus the tail at EOS — counts as alive while the next arena
    is acquired). :class:`~psana_ray_tpu.infeed.pipeline.InfeedPipeline`
    validates its own bound; direct users must size it themselves. The
    default (0) keeps the always-fresh behavior, safe for consumers
    that retain batches indefinitely.
    """

    def __init__(
        self,
        batch_size: int,
        dtype: Optional[np.dtype] = None,
        n_buffers: int = 0,
    ):
        if batch_size <= 0:
            raise ValueError("batch_size must be positive")
        self.batch_size = batch_size
        self.dtype = np.dtype(dtype) if dtype is not None else None
        self.n_buffers = n_buffers
        self._frame_shape: Optional[tuple] = None
        self._pool: List[tuple] = []
        self._pool_i = 0
        self._cur: Optional[tuple] = None
        self._fill = 0
        self._told = 0  # rows of the current arena a consumer was told of
        self._hops: Optional[List[dict]] = None  # stamps of the current batch
        self._t_enq = 0.0  # oldest enqueue stamp of the current batch

    def _alloc(self) -> tuple:
        b = self.batch_size
        return (
            np.empty((b, *self._frame_shape), dtype=self.dtype),
            np.empty((b,), np.uint8),
            np.empty((b,), np.int32),
            np.empty((b,), np.int64),
            np.empty((b,), np.float32),
        )

    def _acquire(self) -> tuple:
        if self.n_buffers > 0:
            if not self._pool:
                self._pool = [self._alloc() for _ in range(self.n_buffers)]
            buf = self._pool[self._pool_i % self.n_buffers]
            self._pool_i += 1
            return buf
        return self._alloc()

    def push(self, rec: FrameRecord) -> Optional[Batch]:
        if self._frame_shape is None:
            self._frame_shape = rec.panels.shape
            if self.dtype is None:
                self.dtype = rec.panels.dtype
        elif rec.panels.shape != self._frame_shape:
            raise ValueError(
                f"frame shape {rec.panels.shape} != locked shape {self._frame_shape}"
            )
        if self._cur is None:
            self._cur = self._acquire()
            self._fill = self._told = 0
        frames, valid, rank, idx, energy = self._cur
        i = self._fill
        frames[i] = rec.panels
        WIRE.add(rec.panels.nbytes)  # THE consumer-side memcpy (wire obs)
        valid[i] = 1
        rank[i] = rec.shard_rank
        idx[i] = rec.event_idx
        energy[i] = rec.photon_energy
        t_enq = rec.t_enq
        if t_enq and (t_enq < self._t_enq or not self._t_enq):
            self._t_enq = t_enq
        hops = rec.hops
        if hops is not None:  # timed stream: stamp copy-into-batch done
            hops[HOP_PUSH] = time.monotonic()
            if self._hops is None:
                self._hops = []
            self._hops.append(hops)
        self._fill += 1
        if self._fill == self.batch_size:
            return self._emit()
        return None

    def push_view(self, rec: FrameRecord) -> Optional[Batch]:
        """``push`` for zero-copy records: copy the panels into the
        batch-arena slot, then release the record's transport-buffer
        lease (pooled TCP recv buffer, shm ring slot). The release
        happens strictly AFTER the copy — crash-redelivery semantics
        depend on a leased buffer never returning to its pool while the
        payload could still be needed — and makes the consumer side
        exactly ONE memcpy (wire -> batch slot). No-op release for
        records that own their data, so callers need not distinguish."""
        try:
            return self.push(rec)
        finally:
            release = getattr(rec, "release", None)
            if release is not None:
                release()

    def flush(self) -> Optional[Batch]:
        """Pad + emit the tail batch (EOS flush). None when nothing pends."""
        if self._cur is None:
            return None
        return self._emit()

    @property
    def pending(self) -> int:
        return self._fill if self._cur is not None else 0

    def landed(self) -> Optional[tuple]:
        """``(frames, lo, hi)``: rows ``lo:hi`` of the CURRENT arena's
        ``frames`` were copied in since this was last asked; None when
        none were. Only rows below the fill are ever named (the tail's
        padding is written at ``flush``), and each row once: what a
        consumer was told of stays as it is until the arena is emitted."""
        if self._cur is None or self._told == self._fill:
            return None
        lo, self._told = self._told, self._fill
        return self._cur[0], lo, self._fill

    def _emit(self) -> Batch:
        frames, valid, rank, idx, energy = self._cur
        n = self._fill
        if n < self.batch_size:  # padded tail: zero only the padding rows
            frames[n:] = 0
            valid[n:] = 0
            rank[n:] = 0
            idx[n:] = 0
            energy[n:] = 0
        self._cur = None
        self._fill = self._told = 0
        hops, self._hops = self._hops, None
        t_enq, self._t_enq = self._t_enq, 0.0
        if hops is not None:  # one emit stamp for every record in the batch
            t = time.monotonic()
            for h in hops:
                h[HOP_BATCH] = t
        return Batch(
            frames, valid, rank, idx, energy, num_valid=n, hops=hops,
            batch_id=next(_BATCH_IDS), t_enq=t_enq,
        )


def batches_from_queue(
    queue,
    batch_size: int,
    poll_interval_s: float = 0.01,
    max_wait_s: Optional[float] = None,
    stop=None,
    n_buffers: int = 0,
    raise_on_stall: bool = False,
    prefer_stream: bool = True,
    control: Optional[DrainControl] = None,
    metrics=None,
    between_turns: Optional[Callable[[], Optional[bool]]] = None,
    rows_landed: Optional[Callable[[np.ndarray, int, int], None]] = None,
) -> Iterator[Batch]:
    """Drain a transport queue into fixed-shape batches until EOS.

    Uses ``get_batch`` (one lock acquisition for many items) rather than the
    reference's one-RPC-per-event read (``data_reader.py:35``). On stream
    completion the tail is flushed padded; iteration then stops.
    When the transport offers a server-push stream drain
    (``get_batch_stream`` — the TCP streaming mode, transport.tcp) it is
    preferred: the server pushes frames under a credit window, so the
    per-pop round trip and the empty-queue poll both disappear and
    ``poll_interval_s`` only paces this loop's stop/stall checks
    (``prefer_stream=False`` forces the request/response pull, e.g. for
    A/B benchmarking). A sharded cluster queue (:class:`psana_ray_tpu.
    cluster.client.ClusterClient`) presents the same entry point: its
    ``get_batch_stream`` fans in over every assigned partition's credit
    stream and already aggregates per-partition EOS markers into ONE
    end-of-stream, so this loop's tally sees a cluster exactly like a
    single queue.
    ``max_wait_s`` bounds total starvation (None = wait forever, matching
    the reference consumer loop); with ``raise_on_stall=True`` hitting it
    raises :class:`StreamStalled` (after yielding any pending tail) instead
    of returning, so callers can tell a silent producer from a completed
    stream. ``stop`` (a ``threading.Event``) makes
    the generator cancellable from another thread — a starved poll loop
    would otherwise be uninterruptible (pending frames are NOT flushed on
    a stop: cancellation abandons the stream).

    Multiple producer runtimes may feed one queue, each emitting its own
    EOS (no global MPI barrier here, unlike reference ``producer.py:
    119-126``); an :class:`EosTally` stops iteration only once every
    global shard is covered, and duplicate markers (copies meant for
    sibling consumers) are re-enqueued.

    ``control`` (a :class:`DrainControl`) makes the poll interval a LIVE
    dial the consumer adjusts from its hooks while this loop runs.

    Every turn of the loop is three consecutive phases (``utils.trace.
    phase``): ``queue_wait`` (the pop: it blocks up to the poll interval
    for a first record, and on the view path copies nothing), ``decode``
    (EOS tally, stamps) and ``copy`` (the copy into the arena; its span
    carries the bytes copied); time suspended at a ``yield`` is the
    consumer's. ``metrics`` (the serving loop's ``PipelineMetrics``,
    optional) gets one observation of each per turn that popped
    something, ``queue_wait``'s covering the whole wait since the
    previous such turn — empty polls report nothing of their own.

    ``between_turns`` (optional) is called on this loop's own thread at
    the end of every turn that emitted no batch — a starved poll, or
    frames that did not fill the arena — outside the three phases: the
    consumer's chance to do work of its own while the stream has nothing
    for it (``SfxPipeline.run`` drains a finished result there). It must
    not block: frames wait in the transport meanwhile. It returns None
    when it found nothing to do, False after work, and a truthy value to
    end iteration as ``stop`` does. The time of its work is the
    consumer's, like time suspended at a ``yield``: a ``queue_wait`` that
    spans several empty polls starts anew after it. A turn ends with
    every frame that arrives, and on a silent stream with every poll
    interval.

    ``rows_landed(frames, lo, hi)`` (optional) is called at the same
    place, on the same thread, right after ``between_turns`` (and not at
    all when that ended the iteration): rows ``lo:hi`` of the CURRENT
    arena's ``frames`` were copied in since the consumer was last told
    (:meth:`FrameBatcher.landed`) — in a turn that did not fill the
    arena, or behind a batch that an earlier turn emitted. ``frames`` is
    the very array the next emitted :class:`Batch` will carry, so the
    consumer can start on a frame's bytes in the turn they arrived
    (``SfxPipeline.run`` puts them on the device there) and know the
    batch they belong to by identity. Rows are named once, in order,
    only below the fill; a full arena that one turn filled is never
    named (the batch itself says it). Its time is the consumer's too.
    Under ``n_buffers > 0`` a named row is overwritten when its arena
    comes round again: whoever reads it later than that must not pool.
    Without either hook a turn does what it did (``InfeedPipeline``,
    the fan-in, the gateway pass none).
    """
    if not poll_interval_s > 0:  # 0 would spin on the pop, a negative wait means nothing
        raise ValueError(f"poll_interval_s must be positive, got {poll_interval_s!r}")
    if control is None:
        control = DrainControl()
    batcher: Optional[FrameBatcher] = None
    starved_since: Optional[float] = None
    tally = EosTally()
    wait_t0: Optional[float] = None  # start of the first of a run of empty polls
    # the loop's three phases, built once: it can turn a thousand times a second
    in_queue_wait = phase(PHASE_QUEUE_WAIT, metrics)
    in_decode = phase(PHASE_DECODE, metrics)
    in_copy = phase(PHASE_COPY, metrics)

    def consumers_turn() -> Optional[bool]:
        """The end of a turn that emitted no batch: ``between_turns``,
        then ``rows_landed``. None = neither found anything to do,
        True = end the iteration."""
        done = between_turns() if between_turns is not None else None
        if done:
            return True
        if rows_landed is not None and batcher is not None:
            rows = batcher.landed()
            if rows is not None:
                rows_landed(*rows)
                return False
        return done

    hooked = between_turns is not None or rows_landed is not None
    # drain preference: server-push stream (TCP streaming mode — no pull
    # RTT, no empty-queue polls) > zero-copy view drain (shm ring slots)
    # > plain get_batch. Every TCP variant returns lease-backed records
    # (pooled recv), so copies/frame stays at exactly the one batch-arena
    # memcpy in push_view below.
    pop = (getattr(queue, "get_batch_stream", None) if prefer_stream else None) or (
        getattr(queue, "get_batch_view", None) or queue.get_batch
    )
    try:
        while True:
            if stop is not None and stop.is_set():
                return
            # the live dial: re-read every turn, the call's own
            # parameter where the consumer set none
            poll_s = control.poll_s or poll_interval_s
            closed = False
            with in_queue_wait as ph:
                try:
                    items = pop(batch_size, timeout=poll_s)
                except TransportWedged:
                    # a peer crashed mid-claim and frames are stuck behind
                    # the wedge: this is data loss, NOT a clean end of
                    # stream — propagate instead of flushing-and-returning
                    raise
                except TransportClosed:
                    closed, items = True, ()
                if items:
                    ph.frames = len(items)
                    if wait_t0 is not None:  # the wait began polls ago
                        ph.t0, wait_t0 = wait_t0, None
                else:
                    ph.record = False
                    if wait_t0 is None:
                        wait_t0 = ph.t0
            if closed:
                # transport died mid-stream: deliver what we already hold
                # (reference dead-queue parity = clean exit, producer.py:112-114)
                if batcher is not None and (tail := batcher.flush()) is not None:
                    yield tail
                return
            if not items:
                # starved: return any held sibling markers (cross-holding
                # consumers would otherwise deadlock — see iter_records).
                # When markers WERE returned, sleep before polling again:
                # the flush and our next pop share one GIL slice, so
                # without the yield we pop our own marker straight back
                # and the blocked sibling never gets it (the competing-
                # consumer livelock; see EosTally.flush_duplicates)
                if tally.flush_duplicates(queue):
                    time.sleep(max(poll_s, 0.02))
                now = time.monotonic()
                starved_since = starved_since if starved_since is not None else now
                if max_wait_s is not None and now - starved_since >= max_wait_s:
                    if batcher is not None and (tail := batcher.flush()) is not None:
                        yield tail
                    if raise_on_stall:
                        FLIGHT.record("stream_stalled", max_wait_s=max_wait_s)
                        raise StreamStalled(
                            f"stream silent for {max_wait_s:.1f}s: no data, "
                            f"no EOS (producer stalled or unreachable)"
                        )
                    return
                if hooked:
                    done = consumers_turn()
                    if done:
                        return
                    if done is not None:  # it worked: the wait is counted anew
                        wait_t0 = None
                continue
            starved_since = None
            t_deq = ph.t1  # the pop returned
            # Every record from this pop is copied-and-released BEFORE any
            # yield: a generator suspended at yield (slow consumer, full
            # prefetch queue) must not sit on transport leases — over the
            # shm ring a held slot blocks producers and, past the wedge
            # timeout, would misdiagnose the stall as a crashed peer.
            # The deferred batch counts as ALIVE for the n_buffers arena
            # contract (see FrameBatcher docstring; InfeedPipeline budgets
            # prefetch_depth + 4 for it).
            ready: List[Batch] = []
            frames: List[FrameRecord] = []
            stream_done = False
            in_decode.frames = len(items)
            with in_decode:
                tally.flush_duplicates(queue)  # gets just freed slots
                tracing = TRACER.enabled
                for pos, item in enumerate(items):
                    if isinstance(item, EndOfStream):
                        if tally.process(item):
                            # items after the completing marker were already
                            # popped; hand them to the tally (sibling EOS
                            # copies) or back to the queue so nothing this
                            # consumer holds is silently dropped
                            leftover_frames = []
                            for rest in items[pos + 1:]:
                                if isinstance(rest, EndOfStream):
                                    tally.process(rest)
                                else:
                                    # materialize BEFORE re-enqueueing: a view-
                                    # backed leftover still occupies the very
                                    # transport slot/buffer a put may need
                                    # (self-deadlock against a full ring)
                                    leftover_frames.append(
                                        rest.materialize() if hasattr(rest, "materialize") else rest
                                    )
                            if leftover_frames:
                                return_to_queue(queue, leftover_frames, what="re-popped record")
                            FLIGHT.record("eos_complete", source="batches_from_queue")
                            stream_done = True
                            break
                        continue
                    trace = item.trace
                    if trace is not None and trace.sampled and tracing:
                        # traced frame from the wire: seed the hops dict so
                        # the batcher's stamps become its spans once its
                        # batch is launched (obs.stages.observe_frame_stages).
                        # TRACE_KEY carries the id; stage observation ignores it
                        mark_hop(item, HOP_DEQ, t_deq)
                        item.hops[TRACE_KEY] = trace.trace_id
                    elif item.hops is not None:  # timed stream: stamp the pop
                        item.hops[HOP_DEQ] = t_deq
                    hops = item.hops
                    if hops is not None and item.t_enq and HOP_ENQ not in hops:
                        # the transport's own enqueue stamp crossed the
                        # process hop with the slot: queue_dwell and a
                        # per-frame e2e exist on this side of it
                        hops[HOP_ENQ] = item.t_enq
                    frames.append(item)
            in_copy.frames = len(frames)
            with in_copy:
                if tracing:  # the span's bytes: summed for the spool alone
                    in_copy.nbytes = sum(f.nbytes for f in frames)
                for item in frames:
                    if batcher is None:
                        batcher = FrameBatcher(batch_size, n_buffers=n_buffers)
                    out = batcher.push_view(item)  # copy into arena, release lease
                    if out is not None:
                        ready.append(out)
                if stream_done and batcher is not None and (tail := batcher.flush()) is not None:
                    ready.append(tail)
                del items, frames, item  # drop any lingering record refs with the pop
            yield from ready  # suspended-at-yield time is the consumer's
            if stream_done:
                return
            if not ready and hooked and consumers_turn():
                return
    finally:
        tally.flush_duplicates(queue, final=True)
