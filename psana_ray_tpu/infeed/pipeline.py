"""Double-buffered device prefetch + the end-to-end infeed pipeline.

The reference's consumer does a blocking cross-node RPC per frame and
sleeps 1 s when starved (``data_reader.py:35``, ``psana_consumer.py:40``) —
device compute and host transfer never overlap. Here a background thread
stages the next ``prefetch_depth`` batches onto the devices while the
current batch computes (the classic double-buffering pattern). Depth 2
suffices when a transfer, from the ``device_put`` call to the last byte
on the device, is shorter than the lead the depth buys; that is measured,
not promised (``h2d_ms.hit``, PERF.md §5 (2): in the hit-finding cell,
batches of 831 MB, a transfer takes 1.2 batch periods and 8-24% of the
launches find their bytes not yet there).

Host-side memory discipline (ISSUE 2): the batch source underneath
(``batches_from_queue``) drains zero-copy when the transport offers it
and copies each record ONCE into the batch arena (``FrameBatcher.
push_view``), releasing the transport buffer lease immediately after —
so the full queue -> batch -> device path performs one host memcpy per
frame plus the H2D transfer, with steady-state allocations handled by
the recv pool (``utils/bufpool.py``) and optional batch-arena recycling
(``batcher_buffers``)."""

from __future__ import annotations

import queue as _queue
import threading
from typing import Any, Callable, Iterator, Optional

import jax
import numpy as np

from psana_ray_tpu.infeed.batcher import Batch, batches_from_queue
from psana_ray_tpu.obs.stages import (
    PHASE_DEVICE_PUT,
    PHASE_DEVICE_WAIT,
    PHASE_H2D_TAIL,
    PHASE_INFEED_WAIT,
    PHASE_LAUNCH,
    PHASE_PREFETCH_FULL,
    SPAN_H2D,
    observe_batch_done,
    observe_frame_stages,
)
from psana_ray_tpu.obs.jitwatch import WATCH
from psana_ray_tpu.obs.tracing import TRACER
from psana_ray_tpu.utils.metrics import PipelineMetrics
from psana_ray_tpu.utils.trace import phase


class StopStream(Exception):
    """Raise from a ``run()`` step callback to end the loop early —
    consumer-side stop (training-step quota reached, result budget hit)
    as opposed to the producer-side typed EOS. ``run()`` catches it,
    closes the pipeline cleanly, and returns the count so far."""


def _arrays_of(staged) -> list:
    """The arrays of a staged batch, whatever ``to_device`` made of it."""
    if not isinstance(staged, Batch):
        return jax.tree_util.tree_leaves(staged)
    arrays: list = []
    staged.map_arrays(arrays.append)  # THE enumeration of a Batch's array fields
    return arrays


class DevicePrefetcher:
    """Wrap a host Batch iterator; yield device-resident batches.

    ``sharding`` may be a Sharding (placed on a mesh) or None (default
    device). Transfers run on a background thread ``prefetch_depth`` ahead
    of consumption; ``jax.device_put`` is async, so the thread's role is to
    keep the H2D copy stream busy, not to block compute. The thread's
    loop is two phases per batch (``utils.trace.phase``): ``device_put``
    (the placement) and ``prefetch_full`` (the staged batch waits for
    room in the buffer); the rest of its time is the source's own
    (``batches_from_queue``'s phases). ``metrics`` (optional) gets one
    observation of each per batch.

    ``device_put`` times the CALL, which returns while the bytes still
    cross. While ``TRACER`` is on, every staged batch is also handed to a
    watcher thread (started with the first such batch) that waits for its
    arrays (``jax.block_until_ready``, the ``h2d_tail`` phase) and writes
    ONE span ``h2d`` into the spool under the batch's id: ``device_put``
    called -> every array on the device. The staged batch goes on to the
    buffer without waiting for it. With the tracer off there is no such
    thread and no further reference to the arrays.

    Always ``close()`` (or use as a context manager, or exhaust the
    iterator) — an abandoned prefetcher would otherwise pin
    ``prefetch_depth`` device-resident batches and its thread forever."""

    def __init__(
        self,
        batches: Iterator[Batch],
        sharding=None,
        prefetch_depth: int = 2,
        to_device: Optional[Callable[[Batch], Any]] = None,
        stop_event: Optional[threading.Event] = None,
        metrics: Optional[PipelineMetrics] = None,
    ):
        if prefetch_depth < 1:
            raise ValueError("prefetch_depth must be >= 1")
        self._src = batches
        self._sharding = sharding
        self.prefetch_depth = prefetch_depth
        self._buf: _queue.Queue = _queue.Queue(maxsize=prefetch_depth)
        self._to_device = to_device or self._place
        self._metrics = metrics
        self._err: Optional[BaseException] = None
        # sharing the event with the source generator (batches_from_queue's
        # ``stop``) lets close() cancel a poll loop the iterator protocol
        # alone cannot interrupt
        self._stop = stop_event if stop_event is not None else threading.Event()
        self._done = False
        # (batch_id, frames, staged, t0) of every batch staged while the
        # tracer is on, for the watcher thread; None ends it
        self._watched: Optional[_queue.SimpleQueue] = None
        self._watcher: Optional[threading.Thread] = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _place(self, batch: Batch):
        # num_valid stays the host int — counting on-device would sync
        return batch.map_arrays(lambda x: jax.device_put(x, self._sharding))

    def _put(self, item) -> bool:
        """Bounded put that aborts when close() is called."""
        while not self._stop.is_set():
            try:
                self._buf.put(item, timeout=0.05)
                return True
            except _queue.Full:
                continue
        return False

    def _run(self):
        try:
            for batch in self._src:
                mark = (self._metrics, batch.batch_id, batch.num_valid)
                with phase(PHASE_DEVICE_PUT, *mark) as ph:
                    staged = self._to_device(batch)
                if isinstance(staged, Batch):
                    staged.t_staged = ph.t1
                if TRACER.enabled:
                    self._watch(batch.batch_id, batch.num_valid, staged, ph.t0)
                with phase(PHASE_PREFETCH_FULL, *mark):
                    ok = self._put(staged)
                if not ok:
                    return  # closed — drop remaining stream
        except BaseException as e:  # surface in consumer thread
            self._err = e
        finally:
            if self._watched is not None:
                self._watched.put(None)
            self._put(None)  # stream end marker (internal)

    def _watch(self, batch_id: int, frames: int, staged, t0: float) -> None:
        """Hand a staged batch to the watcher thread (the prefetch
        thread's side; the first call starts the watcher)."""
        if self._watched is None:
            self._watched = _queue.SimpleQueue()
            self._watcher = threading.Thread(target=self._watch_h2d, daemon=True)
            self._watcher.start()
        self._watched.put((batch_id, frames, _arrays_of(staged), t0))

    def _watch_h2d(self) -> None:
        """The watcher thread: one staged batch at a time, in the order
        staged, wait until every array is on the device. Where a transfer
        outlasts the batch period the next batch's ``h2d_tail`` region
        begins when this one's ended; its ``h2d`` span is exact all the
        same as long as transfers end in the order they were issued."""
        tail = phase(PHASE_H2D_TAIL)
        while (item := self._watched.get()) is not None:
            tail.batch_id, tail.frames, arrays, t0 = item
            del item
            with tail:
                jax.block_until_ready(arrays)
            del arrays  # this thread's reference goes with the wait
            TRACER.phase_span(tail.batch_id, SPAN_H2D, t0, tail.t1, tail.frames)

    def close(self, timeout: float = 5.0):
        """Stop the prefetch thread and release buffered batches."""
        self._stop.set()
        try:
            while True:
                self._buf.get_nowait()
        except _queue.Empty:
            pass
        self._thread.join(timeout=timeout)
        if self._watcher is not None:  # _run's ``finally`` sent its end marker
            self._watcher.join(timeout=timeout)
        # wake any OTHER thread blocked in __next__ (fan-in pump threads
        # iterate from their own thread): the producer thread is gone, so
        # its end marker may have been drained above or never landed
        try:
            self._buf.put_nowait(None)
        except _queue.Full:
            pass
        self._done = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        item = self._buf.get()
        if item is None:
            self._done = True
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item


def drive_step(
    metrics: PipelineMetrics,
    step,
    batch,
    block_until_ready: bool = False,
    nbytes: Optional[int] = None,
):
    """Run one consumer step over a device batch, recording frame count,
    bytes, and step latency. ``block_until_ready`` makes the recorded
    latency a true per-batch device latency instead of dispatch time —
    the honest number for the <5 ms p50 target (BASELINE.md). Shared by
    :meth:`InfeedPipeline.run`, ``FanInPipeline.run``, and the multi-host
    loop — the latter passes ``nbytes`` explicitly (this HOST's ingest
    bytes; the global sharded array's nbytes would overcount by the
    process count).

    Two phases: ``launch`` (the step call returns) and, when blocking,
    ``device_wait``; ``metrics.step_latency`` spans both. A timed batch's
    per-frame stamps are folded between the two, where the host would
    only wait; what is the same for the whole batch is observed once,
    after the step."""
    mark = (metrics, batch.batch_id, batch.num_valid)
    with phase(PHASE_LAUNCH, *mark) as ph:
        out = step(batch)
    t0 = ph.t0
    observe_frame_stages(metrics.stages, batch)
    if block_until_ready:
        with phase(PHASE_DEVICE_WAIT, *mark) as ph:
            out = jax.block_until_ready(out)
    t1 = ph.t1
    metrics.observe_batch(
        batch.num_valid,
        t1 - t0,
        nbytes=int(getattr(batch.frames, "nbytes", 0)) if nbytes is None else nbytes,
    )
    observe_batch_done(metrics.stages, batch, t1)
    return out


class InfeedPipeline:
    """transport queue -> batcher -> device prefetch -> step fn.

    The consumer-side analog of the reference's `consume_data` loop
    (``psana_consumer.py:28-47``), but batched, prefetched, and jit-ready.
    """

    def __init__(
        self,
        queue,
        batch_size: int,
        sharding=None,
        prefetch_depth: int = 2,
        poll_interval_s: float = 0.01,
        max_wait_s: Optional[float] = None,
        metrics: Optional[PipelineMetrics] = None,
        place_on_device: bool = True,
        batcher_buffers: int = 0,
        name: Optional[str] = None,
    ):
        """``place_on_device=False`` keeps batches as host numpy arrays —
        for host-pipeline measurement or host-only consumers, where the
        device_put would be a pure extra frame-sized memcpy.

        ``name`` (optional) registers this pipeline's metrics as
        ``infeed.<name>`` in the process :class:`~psana_ray_tpu.obs.
        MetricsRegistry` (unregistered on :meth:`close`), so a
        ``--metrics_port`` endpoint in the same process exposes it."""
        if batcher_buffers > 0 and batcher_buffers < prefetch_depth + 4:
            # alive at once: prefetch_depth queued + 1 with the consumer
            # + 1 being filled + 1 deferred un-yielded in the batch
            # source (batches_from_queue releases every transport lease
            # before yielding, so a completed batch — and the tail at
            # EOS — can sit in its ready list while the next arena is
            # acquired) + 1 margin for an async/aliasing device_put
            raise ValueError(
                f"batcher_buffers={batcher_buffers} can recycle a batch "
                f"still alive downstream; need >= prefetch_depth + 4 = "
                f"{prefetch_depth + 4} (see FrameBatcher.n_buffers contract)"
            )
        self.queue = queue
        self.batch_size = batch_size
        self.metrics = metrics if metrics is not None else PipelineMetrics(queue=queue)
        self._obs_name = f"infeed.{name}" if name else None
        if self._obs_name:
            from psana_ray_tpu.obs import MetricsRegistry

            MetricsRegistry.default().register(self._obs_name, self.metrics)
        stop = threading.Event()
        self._batches = batches_from_queue(
            queue,
            batch_size,
            poll_interval_s=poll_interval_s,
            max_wait_s=max_wait_s,
            stop=stop,
            n_buffers=batcher_buffers,
            metrics=self.metrics,
        )
        self._prefetcher = DevicePrefetcher(
            self._batches,
            sharding=sharding,
            prefetch_depth=prefetch_depth,
            stop_event=stop,
            to_device=None if place_on_device else (lambda b: b),
            metrics=self.metrics,
        )

    def __iter__(self) -> Iterator[Batch]:
        return iter(self._prefetcher)

    def close(self):
        self._prefetcher.close()
        if self._obs_name:
            from psana_ray_tpu.obs import MetricsRegistry

            MetricsRegistry.default().unregister(self._obs_name)
            self._obs_name = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def run(
        self,
        step: Callable[[Batch], Any],
        on_result: Optional[Callable] = None,
        block_until_ready: bool = False,
    ) -> int:
        """Drive ``step`` over every batch until EOS; returns frames seen.

        ``step`` receives device-resident Batches; results are handed to
        ``on_result`` (if given) without forcing synchronization unless
        ``block_until_ready`` is set (which makes ``metrics.step_latency``
        a true per-batch device latency instead of dispatch time — the
        honest number for the <5 ms p50 target, BASELINE.md). The
        prefetcher is closed on exit, normal or not.

        The serving thread's phases per batch: ``infeed_wait`` (blocked
        on the prefetcher), ``launch`` and ``device_wait``
        (:func:`drive_step`), then ``on_result``, the caller's own.

        When the process's first result is out the loop logs ONE line at
        INFO (``obs.jitwatch``: what the start traced, lowered, loaded
        and compiled); a load or compile after it is a flight-recorder
        event ``recompile``."""
        n = 0
        batches = iter(self)
        try:
            while True:
                with phase(PHASE_INFEED_WAIT, self.metrics) as ph:
                    batch = next(batches, None)
                    if batch is None:
                        ph.record = False  # the stream's end, not a batch
                        break
                    ph.batch_id, ph.frames = batch.batch_id, batch.num_valid
                out = drive_step(self.metrics, step, batch, block_until_ready)
                n += batch.num_valid
                if on_result is not None:
                    on_result(out, batch)
                if not WATCH.serving:  # the first result is out: the start's account, one line
                    WATCH.first_result("InfeedPipeline.run")
        except StopStream:
            pass  # consumer-side early stop; close() below
        finally:
            self.close()
        return n
