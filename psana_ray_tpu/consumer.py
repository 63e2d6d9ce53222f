"""Consumer client: the reference ``DataReader`` surface, TPU-era semantics.

Parity with reference ``data_reader.py:4-48``:
- ``DataReader(address, queue_name, namespace)`` context manager;
- ``connect()`` — idempotent, resolves the named queue (with the
  producer-side retry semantics the reference gave only to producers);
- ``read()`` — one item, or None when momentarily empty (kept for drop-in
  familiarity) — but EOS is a typed :class:`EndOfStream`, never None;
- ``read_wait(timeout)`` — blocking read, replacing the example consumer's
  1 s poll-sleep (``psana_consumer.py:38-40``);
- dead transport raises :class:`DataReaderError` (parity:
  ``data_reader.py:36-37``);
- ``close()`` — release the connection.

``address='auto'`` resolves through the in-process :class:`Registry`;
``address='shm://...'`` / ``'tcp://host:port'`` select the cross-process /
cross-host transports.
"""

from __future__ import annotations

import time
from typing import Any, Optional

from psana_ray_tpu.config import TransportConfig
from psana_ray_tpu.obs.profiling.stagetag import TAG_DEQUEUE, set_stage, swap_stage
from psana_ray_tpu.records import EndOfStream, EosTally, FrameRecord, is_eos
from psana_ray_tpu.transport import EMPTY, RendezvousTimeout, TransportClosed


class DataReaderError(RuntimeError):
    """The transport died (parity: reference ``data_reader.py:46-48``)."""


class DataReader:
    def __init__(
        self,
        address: str = "auto",
        queue_name: Optional[str] = None,
        namespace: Optional[str] = None,
        config: Optional[TransportConfig] = None,
        streaming: bool = False,
        stream_window: int = 32,
        replay_from: Optional[str] = None,
        replay_group: Optional[str] = None,
    ):
        """``streaming=True`` (TCP transports) subscribes the data
        connection to server-push delivery with a ``stream_window``-frame
        credit window (transport.tcp streaming contract): ``read_wait``/
        ``read_batch``/``iter_records`` then drain pushed frames with no
        per-read round trip and no empty-queue polling — the pull RTT
        disappears and the credit window bounds client memory like a
        prefetch depth. Delivery stays at-least-once: frames this reader
        consumed-but-not-yet-acked redeliver to another consumer on a
        crash. Ignored (plain reads) on transports without streaming.

        ``replay_from`` (ISSUE 8, servers started with --durable_dir)
        opens the queue's retained segment-log range NON-destructively
        for a second consumer group instead of competing on the live
        queue: ``"begin"`` starts at the earliest retained record,
        ``"resume"`` at ``replay_group``'s committed offset, a digit
        string at an explicit offset. Delivered records commit the
        group's offset at the connection's implicit-ACK points, so a
        crashed replay consumer reconnects at resume — duplicates
        possible, loss never. Implies plain (pull) reads."""
        self.config = config or TransportConfig()
        self.address = address if address != "auto" else self.config.address
        self.queue_name = queue_name or self.config.queue_name
        self.namespace = namespace or self.config.namespace
        self.streaming = streaming
        self.stream_window = stream_window
        self.replay_from = (
            replay_from if replay_from is not None else
            (self.config.replay_from or None)
        )
        self.replay_group = replay_group or self.config.replay_group
        if self.replay_from is not None:
            self.streaming = False  # replay is pull-mode by design
        self._queue = None

    # -- lifecycle (parity: data_reader.py:11-29,39-44) -------------------
    def _open(self):
        import dataclasses

        from psana_ray_tpu.transport.addressing import open_queue

        cfg = dataclasses.replace(
            self.config, queue_name=self.queue_name, namespace=self.namespace
        )
        return open_queue(cfg, role="consumer", address=self.address)

    def connect(self) -> "DataReader":
        if self._queue is not None:
            return self
        try:
            self._queue = self._open()
        except RendezvousTimeout as e:
            raise DataReaderError(f"could not find queue {self.queue_name!r}: {e}") from e
        if self.replay_from is not None:
            if not hasattr(self._queue, "replay_open"):
                raise DataReaderError(
                    f"transport {self.address!r} does not support replay "
                    f"(need a tcp:// or cluster:// durable queue server)"
                )
            start = (
                self.replay_from
                if self.replay_from in ("begin", "resume")
                else int(self.replay_from)
            )
            try:
                self._queue.replay_open(start, group=self.replay_group)
            except TransportClosed as e:
                raise DataReaderError(str(e)) from e
            except RuntimeError as e:  # server refused: not durable
                raise DataReaderError(str(e)) from e
        if self.streaming and hasattr(self._queue, "stream_open"):
            try:
                self._queue.stream_open(self.stream_window)
            except TransportClosed as e:
                raise DataReaderError(str(e)) from e
        return self

    def close(self):
        q = self._queue
        self._queue = None
        if q is not None and hasattr(q, "disconnect"):
            q.disconnect()

    def __enter__(self) -> "DataReader":
        return self.connect()

    def __exit__(self, *exc):
        self.close()

    # -- reads ------------------------------------------------------------
    # Ownership note (zero-copy datapath, ISSUE 2): over the pooled TCP
    # transport a returned FrameRecord's panels may VIEW a recycled
    # receive buffer, kept checked out by ``rec.lease`` for the record's
    # lifetime (released on GC, or eagerly by the batcher's push_view).
    # Reading ``rec.panels`` while you hold the record is always safe;
    # to retain the pixels past the record, copy them (or call
    # ``rec.materialize()``).
    def read(self) -> Any:
        """Non-blocking read: FrameRecord | EndOfStream | None (empty).
        Parity: data_reader.py:31-37, with typed EOS instead of None."""
        self._check_connected()
        prev = swap_stage(TAG_DEQUEUE)
        try:
            item = self._queue.get()
        except TransportClosed as e:
            raise DataReaderError(str(e)) from e
        finally:
            set_stage(prev)
        return None if item is EMPTY else item

    def read_wait(self, timeout: Optional[float] = None) -> Any:
        """Blocking read (no 1 s poll-sleep). None only on timeout."""
        self._check_connected()
        prev = swap_stage(TAG_DEQUEUE)
        try:
            item = self._queue.get_wait(timeout=timeout)
        except TransportClosed as e:
            raise DataReaderError(str(e)) from e
        finally:
            set_stage(prev)
        return None if item is EMPTY else item

    def read_batch(self, max_items: int, timeout: Optional[float] = None) -> list:
        self._check_connected()
        prev = swap_stage(TAG_DEQUEUE)
        try:
            return self._queue.get_batch(max_items, timeout=timeout)
        except TransportClosed as e:
            raise DataReaderError(str(e)) from e
        finally:
            set_stage(prev)

    def __iter__(self):
        """Iterate FrameRecords until the stream completes (the loop the
        reference's example couldn't write correctly — psana_consumer.py:
        38-40 spins forever)."""
        return self.iter_records()

    def iter_records(self, stop=None):
        """Yield FrameRecords until the stream completes or ``stop()``
        returns True (checked between reads, so breaking never discards a
        frame a sibling consumer could have processed).

        With multiple producer runtimes feeding one queue, stops only once
        EOS markers cover every global shard (:class:`EosTally`); duplicate
        markers destined for sibling consumers are held and returned to
        the queue (never dropped, even against a momentarily full queue)."""
        self._check_connected()
        tally = EosTally()
        try:
            while not (stop is not None and stop()):
                item = self.read_wait(timeout=1.0)
                if item is None:
                    # starved while holding a sibling's marker: put it back
                    # NOW — two consumers each holding the marker the other
                    # needs would otherwise deadlock, both waiting on an
                    # empty queue with flush gated on a successful read.
                    # When we DID return markers, sleep before reading
                    # again: the flush and our next pop share one GIL
                    # slice, so without the yield we snatch our own
                    # marker back before the blocked sibling ever wakes —
                    # the measured 60+ s livelock behind the
                    # test_two_consumers_two_runtimes flake
                    if tally.flush_duplicates(self._queue):
                        time.sleep(0.05)
                    continue
                tally.flush_duplicates(self._queue)  # a slot just freed
                if is_eos(item):
                    if tally.process(item):
                        from psana_ray_tpu.obs.flight import FLIGHT

                        FLIGHT.record("eos_complete", queue=self.queue_name)
                        return
                    continue
                yield item
        finally:
            tally.flush_duplicates(self._queue, final=True)

    def size(self) -> int:
        self._check_connected()
        try:
            return self._queue.size()
        except TransportClosed as e:
            raise DataReaderError(str(e)) from e

    def open_monitor(self):
        """Open an INDEPENDENT queue handle for metrics polling.

        Never hand the data connection to a monitoring thread: over TCP
        the server treats the next opcode on a connection as the implicit
        ACK of that connection's in-flight deliveries (transport.tcp), so
        a ``size()`` probe from a heartbeat thread would confirm frames
        the main thread is still processing and forfeit crash-redelivery.
        A separate connection never GETs, so it has nothing to ACK."""
        return self._open()

    def _check_connected(self):
        if self._queue is None:
            raise DataReaderError("not connected — call connect() or use as context manager")


def main(argv=None):
    """Console consumer — the reference example (``psana_consumer.py:49-55``)
    as an installed entry point, with typed EOS termination."""
    import argparse
    import logging
    import signal
    import threading

    from psana_ray_tpu.utils.hostmem import enable_large_alloc_reuse

    enable_large_alloc_reuse()  # MB-scale frame buffers: heap reuse, no re-faulting
    p = argparse.ArgumentParser(prog="psana-ray-tpu-consumer")
    p.add_argument("consumer_id", type=int, nargs="?", default=0)
    p.add_argument("--ray_address", "--address", dest="address", default="auto")
    p.add_argument("--ray_namespace", "--namespace", dest="namespace", default="default")
    p.add_argument("--queue_name", default="shared_queue")
    p.add_argument(
        "--stream", action="store_true",
        help="subscribe the data connection to server-push streaming "
        "(TCP transports): frames are pushed as they arrive under a "
        "credit window instead of pulled one round trip at a time — "
        "RTT-independent throughput, same at-least-once redelivery",
    )
    p.add_argument(
        "--stream_window", type=int, default=32,
        help="streaming credit window (frames in flight before the "
        "server blocks on this consumer's acks); bounds consumer-side "
        "memory like a prefetch depth",
    )
    p.add_argument(
        "--replay", default=None, metavar="from=<offset|begin|resume>",
        help="durable servers (--durable_dir) only: read the queue's "
        "RETAINED segment-log range non-destructively instead of "
        "competing on the live queue — 'from=begin' replays the "
        "earliest retained record (a new model revision re-reads "
        "yesterday's run), 'from=resume' continues at --replay_group's "
        "committed offset, 'from=<N>' starts at offset N. Live "
        "consumers are undisturbed; progress commits per batch "
        "(at-least-once on crash). Implies pull-mode reads",
    )
    p.add_argument(
        "--replay_group", default="replay",
        help="consumer-group name whose committed offset --replay "
        "advances (a second group, independent of live consumption)",
    )
    p.add_argument("--max_frames", type=int, default=None)
    p.add_argument("--quiet", action="store_true", help="suppress per-frame lines")
    p.add_argument("--log_level", default="INFO")
    p.add_argument(
        "--profile_dir", default=None,
        help="capture a jax.profiler trace of the consume loop into this "
        "directory (view in TensorBoard's Profile tab)",
    )
    p.add_argument(
        "--status_interval", type=float, default=0.0,
        help="log a metrics heartbeat (PipelineMetrics.status_line: "
        "frames/s, Gbit/s, latency quantiles, queue depth) every N "
        "seconds — the consumer-side mirror of the producer's end-of-run "
        "summary; 0 = off",
    )
    from psana_ray_tpu.obs import (
        add_history_args,
        add_metrics_args,
        add_profile_args,
        add_trace_args,
    )
    from psana_ray_tpu.transport.addressing import (
        add_cluster_args,
        add_tenant_args,
        add_wire_args,
    )

    add_metrics_args(p)
    add_trace_args(p)
    add_history_args(p)
    add_profile_args(p)
    add_cluster_args(p, consumer=True)
    add_wire_args(p)
    add_tenant_args(p)
    p.add_argument(
        "--cursor_path", default=None,
        help="persist a StreamCursor (contiguous per-shard watermark of "
        "processed events, checkpoint.py) here; a restarted producer with "
        "the same --cursor_path resumes past it (at-least-once). The "
        "cursor tracks THIS consumer's progress — with multiple competing "
        "consumers give each its own file (resuming a producer from one "
        "consumer's cursor re-produces whatever the others handled: "
        "duplicates, never gaps)",
    )
    p.add_argument(
        "--cursor_stride", type=int, default=1,
        help="total producer shards feeding this stream (the cursor's "
        "watermark arithmetic needs the shard stride; must match the "
        "producer's total_shards)",
    )
    p.add_argument(
        "--cursor_save_every", type=int, default=32,
        help="persist the cursor every N processed frames (and at exit); "
        "<= 0 saves at exit only",
    )
    a = p.parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, a.log_level.upper(), logging.INFO),
        format="%(asctime)s - %(levelname)s - %(message)s",
    )
    log = logging.getLogger("consumer")
    from psana_ray_tpu.transport.addressing import (
        apply_cluster_args,
        apply_tenant_args,
        apply_wire_args,
    )

    # --cluster rewrites the address (and carries partitions/group); the
    # DataReader below sees the sharded service as just another address.
    # --wire_codec and --tenant ride the same config into open_queue
    reader_config = apply_tenant_args(
        apply_wire_args(
            apply_cluster_args(TransportConfig(address=a.address), a), a
        ),
        a,
    )
    a.address = reader_config.address

    stop = False

    def _sigint(sig, frame):  # parity: psana_consumer.py:24-26
        nonlocal stop
        stop = True

    signal.signal(signal.SIGINT, _sigint)
    n = 0

    def _should_stop():
        # checked between reads: breaking never discards an already-read
        # frame, and SIGINT exits even while starved (no yield to reach)
        return stop or (a.max_frames is not None and n >= a.max_frames)

    from psana_ray_tpu.utils.trace import trace

    cursor = None
    if a.cursor_path:
        from psana_ray_tpu.checkpoint import StreamCursor

        cursor = StreamCursor.load(a.cursor_path)
        if not cursor.positions:
            cursor.stride = a.cursor_stride
        elif cursor.stride != a.cursor_stride:
            log.error(
                "cursor %s has stride=%d but --cursor_stride=%d; refusing "
                "(wrong stride computes wrong watermarks and can skip data)",
                a.cursor_path, cursor.stride, a.cursor_stride,
            )
            return 1

    # Observability: per-frame counters always (they also feed the final
    # "end of stream" line); the heartbeat thread and the HTTP endpoint
    # only exist when their flags ask for them (zero cost disabled).
    # Started AFTER every early-return validation above, so a refused run
    # never leaks the bound port or the heartbeat thread.
    from psana_ray_tpu.obs import MetricsRegistry, start_metrics_server
    from psana_ray_tpu.obs.stages import STAGE_QUEUE_DWELL
    from psana_ray_tpu.utils.metrics import PipelineMetrics

    metrics = PipelineMetrics()
    observe_dwell = a.status_interval > 0 or a.metrics_port > 0
    MetricsRegistry.default().register("consumer", metrics)
    metrics_server = start_metrics_server(a.metrics_port, host=a.metrics_host)
    # history ring (ISSUE 13): flight-dump tails + /federate consumers
    from psana_ray_tpu.obs import configure_history_from_args, configure_profiling_from_args

    history = configure_history_from_args(a)
    # continuous profiler (ISSUE 16): --profile_hz 0 = off; the spool
    # shares --profile_dir with the jax device trace
    profiler = configure_profiling_from_args(a, "consumer")
    heartbeat_done = threading.Event()
    heartbeat = None
    if a.status_interval > 0:
        from psana_ray_tpu.obs.tracing import obs_status_suffix

        def _heartbeat():
            # the suffix shows tracing is actually ON in a live run:
            # sample rate, spans emitted so far, flight-recorder events
            while not heartbeat_done.wait(a.status_interval):
                log.info(
                    "consumer %d status: %s%s",
                    a.consumer_id, metrics.status_line(), obs_status_suffix(),
                )

        heartbeat = threading.Thread(target=_heartbeat, daemon=True, name="consumer-heartbeat")
        heartbeat.start()

    from psana_ray_tpu.obs.tracing import TRACER, configure_from_args
    from psana_ray_tpu.obs.stages import STAGE_DEQUEUE

    monitor = None
    try:
        replay_from = None
        if a.replay is not None:
            replay_from = a.replay[5:] if a.replay.startswith("from=") else a.replay
            if replay_from not in ("begin", "resume") and not replay_from.isdigit():
                log.error(
                    "--replay wants from=<offset|begin|resume>, got %r", a.replay
                )
                return 1
        with trace(a.profile_dir), DataReader(
            address=a.address, queue_name=a.queue_name, namespace=a.namespace,
            config=reader_config,
            streaming=a.stream, stream_window=a.stream_window,
            replay_from=replay_from, replay_group=a.replay_group,
        ) as reader:
            if observe_dwell or a.trace_dir:
                # depth in the heartbeat — over a DEDICATED handle, never
                # the data connection (see DataReader.open_monitor: a
                # size() probe there would ACK in-flight deliveries).
                # Tracing reuses the same handle for its clock-anchor
                # exchanges (an anchor RPC on the data connection would
                # ACK in-flight deliveries the same way)
                try:
                    monitor = reader.open_monitor()
                    metrics.attach_queue(monitor)
                except Exception as e:  # noqa: BLE001 — depth is optional
                    log.debug("queue monitor unavailable: %s", e)
            configure_from_args(a, "consumer", queue=monitor)
            try:
                for rec in reader.iter_records(stop=_should_stop):
                    t_rec = time.monotonic()
                    n += 1
                    metrics.observe_frame(rec.nbytes)
                    if observe_dwell and rec.timestamp:
                        # wall-clock dwell (producer stamp -> this read):
                        # exact same-host, approximate cross-host (NTP).
                        # A sampled frame's trace id rides the bucket as
                        # its exemplar (trace_merge --exemplar, ISSUE 13)
                        _tr = rec.trace
                        metrics.stages.observe(
                            STAGE_QUEUE_DWELL,
                            max(0.0, time.time() - rec.timestamp),
                            exemplar=_tr.trace_id
                            if _tr is not None and _tr.sampled else None,
                        )
                    if not a.quiet:
                        log.info(
                            "consumer %d: rank=%d idx=%d shape=%s energy=%.2f",
                            a.consumer_id, rec.shard_rank, rec.event_idx,
                            rec.panels.shape, rec.photon_energy,
                        )
                    if cursor is not None:
                        # advance AFTER the record is fully handled: the
                        # watermark must never run ahead of processing.
                        # ValueError = stride/shard misconfiguration —
                        # surfaced immediately, not after a wasted run
                        cursor.advance(rec.shard_rank, rec.event_idx)
                        if a.cursor_save_every > 0 and n % a.cursor_save_every == 0:
                            cursor.save(a.cursor_path)
                    rec_trace = rec.trace
                    if rec_trace is not None and rec_trace.sampled and TRACER.enabled:
                        # consumer-side span: read done -> record fully
                        # handled (log + cursor) — strictly after the
                        # server's relay span on the merged timeline
                        TRACER.span(
                            rec_trace.trace_id, STAGE_DEQUEUE,
                            t_rec, time.monotonic(),
                        )
            finally:
                if cursor is not None:
                    cursor.save(a.cursor_path)
        log.info(
            "consumer %d: end of stream after %d frames (%s)",
            a.consumer_id, n, metrics.status_line(),
        )
    except DataReaderError as e:  # parity: psana_consumer.py:41-44
        log.error("consumer %d: queue is dead (%s); exiting", a.consumer_id, e)
        return 1
    except ValueError as e:  # cursor stride/shard misconfiguration
        log.error("consumer %d: %s", a.consumer_id, e)
        return 1
    finally:
        heartbeat_done.set()
        if history is not None:
            history.stop()
        if heartbeat is not None:
            heartbeat.join(timeout=1.0)
        metrics.attach_queue(None)  # monitor handle is about to die
        if monitor is not None and hasattr(monitor, "disconnect"):
            try:
                monitor.disconnect()
            except Exception:  # noqa: BLE001 — already closing
                pass
        if metrics_server is not None:
            metrics_server.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
