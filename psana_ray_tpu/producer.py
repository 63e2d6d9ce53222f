"""Producer runtime + CLI: sharded ingest into a named, backpressured queue.

The reference's producer (``producer.py``) is an MPI program: N ranks, each
reading its psana shard and pushing framed events through a blocking RPC,
with barriers at bootstrap/shutdown and rank 0 emitting one EOS sentinel
per consumer (``producer.py:119-130``). This runtime keeps every protocol —
shard-per-worker ingest, get-or-create rendezvous, backpressure with the
same backoff envelope, barrier-then-EOS, dead-queue detection, SIGINT
handling, ``--max_steps`` — but as an explicit, testable object that runs
shards as threads in one process (TPU hosts are fed per-process; event
generation releases the GIL in numpy) or as one shard of a multi-host
deployment via ``shard_rank/num_shards``.

All 13 reference flags (``producer.py:17-33``) are covered by
:class:`PipelineConfig`; the CLI exposes them with the same names.
"""

from __future__ import annotations

import argparse
import logging
import signal
import threading
import time
from typing import List, Optional

import numpy as np

from psana_ray_tpu.config import MaskConfig, PipelineConfig, RetrievalMode, SourceConfig, TransportConfig
from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.obs.profiling.stagetag import TAG_ENQUEUE, set_stage, swap_stage
from psana_ray_tpu.obs.stages import HOP_ENQ, HOP_SRC, STAGE_ENQUEUE
from psana_ray_tpu.obs.tracing import SPAN_PRODUCE, TRACER
from psana_ray_tpu.records import EndOfStream, FrameRecord, mark_hop, narrow_panels
from psana_ray_tpu.sources import open_source
from psana_ray_tpu.transport import BackoffPolicy, Registry, TransportClosed, TransportWedged
from psana_ray_tpu.transport.addressing import open_queue
from psana_ray_tpu.utils.metrics import PipelineMetrics

logger = logging.getLogger(__name__)


class _Sender:
    """Backpressured frame sender, preferring the fastest path the
    transport offers:

    - **windowed pipelined PUT** (TCP, ``put_pipelined``): each record
      goes out immediately, up to W sequence-numbered puts in flight
      before blocking on acknowledgements — the link stays full instead
      of paying one round trip per flush, backpressure arrives as
      delayed acks from the server's blocking enqueue (no refusal/retry
      spin), and a reconnect resends exactly the unacked tail;
    - **batched puts** (``put_batch``): one round trip per N frames
      (the pre-streaming TCP path, kept for transports without the
      windowed opcode);
    - per-event puts otherwise (in-process/shm — a put is a memcpy).

    Over TCP every variant leaves via ``sendmsg`` scatter-gather
    straight from each record's panel memory (``FrameRecord.
    wire_parts``): a producer put performs ZERO payload copies."""

    def __init__(self, queue, backoff, stop_event, metrics, batch_size: int = 16):
        self.queue = queue
        self.backoff = backoff
        self.stop = stop_event
        self.metrics = metrics
        self.windowed = hasattr(queue, "put_pipelined")
        self.batch_size = (
            batch_size if (not self.windowed and hasattr(queue, "put_batch")) else 1
        )
        self.pending: List[FrameRecord] = []

    def send(self, rec) -> bool:
        """Buffer + flush when full (windowed: ship immediately, blocking
        only when the in-flight window is full). False = transport
        closed/stopped."""
        prev = swap_stage(TAG_ENQUEUE)
        try:
            if self.windowed:
                return self._send_windowed(rec)
            self.pending.append(rec)
            if len(self.pending) >= self.batch_size:
                return self.flush()
            return True
        finally:
            set_stage(prev)

    def _send_windowed(self, rec) -> bool:
        t_try = time.monotonic()
        if rec.hops is not None:
            rec.hops[HOP_ENQ] = t_try
        while not self.stop.is_set():
            try:
                # bounded slices so stop() stays responsive while the
                # window is full (server blocked on a full queue)
                if self.queue.put_pipelined(
                    rec, deadline=time.monotonic() + 0.5
                ):
                    break
            except TransportWedged:
                raise  # a crashed peer wedged the ring: error, not clean exit
            except TransportClosed:
                return False
        else:
            return False
        self.metrics.observe_frame(rec.nbytes)
        h = rec.hops
        if h is not None and HOP_SRC in h:
            self.metrics.stages.observe(STAGE_ENQUEUE, t_try - h[HOP_SRC])
        trace = rec.trace
        if trace is not None and trace.sampled and TRACER.enabled:
            t_src = h[HOP_SRC] if h and HOP_SRC in h else t_try
            TRACER.instant(trace.trace_id, SPAN_PRODUCE, t_src)
            TRACER.span(trace.trace_id, STAGE_ENQUEUE, t_src, t_try)
        return True

    def flush(self) -> bool:
        """Drain the buffer with the backpressure envelope (parity:
        producer.py:106-111). Windowed: block until every in-flight put
        is acknowledged (the durability point before EOS/barrier).
        False = transport closed/stopped (records may remain pending —
        the stream is dead either way)."""
        prev = swap_stage(TAG_ENQUEUE)
        try:
            return self._drain_buffered()
        finally:
            set_stage(prev)

    def _drain_buffered(self) -> bool:
        if self.windowed:
            while not self.stop.is_set():
                try:
                    if self.queue.flush_puts(
                        deadline=time.monotonic() + 0.5
                    ):
                        return True
                except TransportWedged:
                    raise
                except TransportClosed:
                    return False
            return False
        while self.pending:
            if self.stop.is_set():
                return False
            # enqueue hop stamp goes on BEFORE the put so an in-process
            # consumer can never pop a record that lacks it (it re-stamps
            # on each backpressure retry, so the final value is just-
            # before-the-successful-put); producer-side enqueue latency
            # (source read done -> accepted, incl. backpressure wait)
            # lands in this process's stage histogram below
            t_try = time.monotonic()
            attempt = self.pending if self.batch_size > 1 else self.pending[:1]
            for r in attempt:
                if r.hops is not None:
                    r.hops[HOP_ENQ] = t_try
            try:
                if self.batch_size > 1:
                    accepted = self.queue.put_batch(self.pending)
                else:
                    accepted = 1 if self.queue.put(self.pending[0]) else 0
            except TransportWedged:
                raise  # a crashed peer wedged the ring: error, not clean exit
            except TransportClosed:
                return False
            if accepted:
                for r in self.pending[:accepted]:
                    self.metrics.observe_frame(r.nbytes)
                    h = r.hops
                    if h is not None and HOP_SRC in h:
                        self.metrics.stages.observe(STAGE_ENQUEUE, t_try - h[HOP_SRC])
                    trace = r.trace
                    if trace is not None and trace.sampled and TRACER.enabled:
                        # producer-side spans: frame birth (instant) +
                        # enqueue (source read done -> accepted, incl.
                        # backpressure wait) — sampled frames only
                        t_src = h[HOP_SRC] if h and HOP_SRC in h else t_try
                        TRACER.instant(trace.trace_id, SPAN_PRODUCE, t_src)
                        TRACER.span(trace.trace_id, STAGE_ENQUEUE, t_src, t_try)
                del self.pending[:accepted]
                self.backoff.reset()
            else:
                self.backoff.wait()
        return True


class ProducerRuntime:
    """Drives ``num_shards`` ingest workers into one named queue."""

    def __init__(
        self,
        config: PipelineConfig,
        registry: Optional[Registry] = None,
        num_local_shards: int = 1,
        shard_rank_offset: int = 0,
        total_shards: Optional[int] = None,
        stage_timing: bool = False,
    ):
        """``stage_timing`` stamps hop timestamps on every record
        (records.mark_hop) feeding the enqueue-stage histogram and — over
        in-process transports — downstream stage decomposition. Off by
        default: the per-frame dict + monotonic stamps are only worth
        paying when something exports them (the CLI enables it with
        ``--metrics_port``)."""
        self.config = config
        self.registry = registry or Registry.default()
        self.num_local_shards = num_local_shards
        self.shard_rank_offset = shard_rank_offset
        self.total_shards = total_shards or num_local_shards
        self.stage_timing = stage_timing
        self.metrics = PipelineMetrics()
        self._queue = None
        self._barrier = threading.Barrier(num_local_shards)
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._errors: List[BaseException] = []

    # -- rendezvous (parity: producer.py:35-71) ---------------------------
    def bootstrap(self):
        if self._queue is not None:
            # idempotent: the CLI's tracer path bootstraps early (the
            # clock-anchor exchange rides the data client) and run()
            # bootstraps again — re-opening would orphan that connection
            return self._queue
        t = self.config.transport
        self._queue = open_queue(t, role="producer", registry=self.registry)
        if not self.metrics.has_queue:
            # depth in status/snapshot — unless the CLI already attached a
            # dedicated monitor handle (over TCP a scrape on the DATA
            # connection would block behind a put's reconnect backoff,
            # serialized under the client lock)
            self.metrics.attach_queue(self._queue)
        logger.info(
            "queue %r ready (namespace=%r address=%r size=%d)",
            t.queue_name, t.namespace, t.address, t.queue_size,
        )
        return self._queue

    # -- per-shard event pump (parity: produce_data, producer.py:78-130) --
    def _pump(self, local_idx: int):
        cfg = self.config
        rank = self.shard_rank_offset + local_idx
        t = cfg.transport
        try:
            start_event = self._resume_point(rank)
            source = open_source(
                cfg.source.exp,
                cfg.source.run,
                cfg.source.detector_name,
                shard_rank=rank,
                num_shards=self.total_shards,
                num_events=cfg.source.num_events,
                seed=cfg.source.seed,
                dtype=cfg.source.dtype,
                start_event=start_event,
            )
            if start_event:
                logger.info("rank %d resuming at event >= %d", rank, start_event)
            mask = self._load_mask(source)
            backoff = BackoffPolicy(t.backoff_base_s, t.backoff_cap_s, t.backoff_jitter_s)
            sender = _Sender(
                self._queue, backoff, self._stop, self.metrics, t.put_batch_size
            )
            produced = 0
            wire_dtype = t.wire_dtype  # opt-in LOSSY narrowing (ISSUE 9)
            for idx, data, energy in source.iter_indexed_events(cfg.source.mode):
                if self._stop.is_set():
                    break
                if cfg.source.max_steps is not None and produced >= cfg.source.max_steps:
                    logger.info("rank %d: reached max_steps=%d", rank, cfg.source.max_steps)
                    break
                if mask is not None:
                    data = np.where(mask, data, 0)  # parity: producer.py:92-95
                if wire_dtype:
                    # narrow BEFORE encode: half (or less) the wire bytes
                    # before the codec even runs — records.narrow_panels
                    # rounds + clips integer targets
                    data = narrow_panels(np.asarray(data), wire_dtype)
                # sampled tracing gate: None on the unsampled hot path
                # (zero allocations — counter arithmetic only)
                trace_ctx = TRACER.maybe_trace()
                rec = FrameRecord(
                    rank, int(idx), data, energy, timestamp=time.time(),
                    trace=trace_ctx,
                )
                if self.stage_timing or trace_ctx is not None:
                    mark_hop(rec, HOP_SRC)  # source read done
                if not sender.send(rec):
                    logger.warning("rank %d: queue dead, exiting", rank)
                    return  # parity: producer.py:112-114
                produced += 1
                logger.debug(
                    "rank %d produced idx=%d shape=%s energy=%.2f",
                    rank, idx, rec.panels.shape, energy,
                )
            if not sender.flush():  # tail of the batch buffer precedes EOS
                logger.warning("rank %d: queue dead at flush, exiting", rank)
                return
            # barrier so EOS follows ALL shards' data (parity: producer.py:120)
            self._barrier.wait(timeout=600)
            if local_idx == 0:
                self._emit_eos()
        except BaseException as e:  # noqa: BLE001 — recorded, re-raised in run()
            self._errors.append(e)
            logger.exception("rank %d failed", rank)
            try:
                self._barrier.abort()
            except Exception:
                pass

    def _emit_eos(self):
        """Local rank 0 puts one typed EOS per expected consumer
        (parity: producer.py:121-126, tolerating a dead queue :127-130).

        The marker carries this runtime's shard coverage so consumers with
        an :class:`EosTally` stop only when EVERY runtime feeding the queue
        has finished — the role the reference's global MPI barrier played
        (``producer.py:119-126``)."""
        t = self.config.transport
        eos = EndOfStream(
            producer_rank=self.shard_rank_offset,
            shards_done=self.num_local_shards,
            total_shards=self.total_shards,
        )
        for _ in range(t.num_consumers):
            try:
                while not self._queue.put_wait(eos, timeout=5.0):
                    if self._stop.is_set():
                        return
            except TransportWedged:
                raise  # crashed-peer wedge: surface it, don't log-and-exit
            except TransportClosed:
                logger.warning("queue died before EOS could be delivered")
                return
        FLIGHT.record(
            "eos_emitted",
            producer_rank=self.shard_rank_offset,
            consumers=t.num_consumers,
        )
        logger.info("EOS delivered to %d consumer(s)", t.num_consumers)

    def _resume_point(self, rank: int) -> int:
        """Where shard ``rank`` should (re)start: the scalar
        ``start_event`` floor, raised to the cursor's per-shard contiguous
        watermark when ``cursor_path`` names a consumer-written
        :class:`~psana_ray_tpu.checkpoint.StreamCursor`. At-least-once:
        events pending above the watermark at crash time are re-produced."""
        cfg = self.config.source
        start = cfg.start_event
        if cfg.cursor_path:
            from psana_ray_tpu.checkpoint import StreamCursor

            cursor = StreamCursor.load(cfg.cursor_path)
            if cursor.positions:
                if cursor.stride != self.total_shards:
                    # a mismatched stride would compute wrong per-shard
                    # resume points and silently SKIP events — refuse
                    raise ValueError(
                        f"cursor {cfg.cursor_path!r} was written for "
                        f"stride={cursor.stride} but this producer topology "
                        f"has total_shards={self.total_shards}"
                    )
                start = max(start, cursor.resume_point(rank))
        return start

    def _load_mask(self, source) -> Optional[np.ndarray]:
        m = self.config.mask
        mask = None
        if m.uses_bad_pixel_mask:
            mask = source.create_bad_pixel_mask()  # parity: producer.py:81
        if m.manual_mask_path:
            manual = np.load(m.manual_mask_path)  # parity: producer.py:82
            mask = manual if mask is None else (mask.astype(bool) & manual.astype(bool))
        return mask

    # -- lifecycle --------------------------------------------------------
    def run(self, block: bool = True):
        if self._queue is None:
            self.bootstrap()
        self._threads = [
            threading.Thread(target=self._pump, args=(i,), name=f"producer-shard-{i}")
            for i in range(self.num_local_shards)
        ]
        for t in self._threads:
            t.start()
        if block:
            self.join()

    def join(self):
        for t in self._threads:
            t.join()
        if self._errors:
            raise self._errors[0]

    def stop(self):
        self._stop.set()


def parse_arguments(argv=None):
    """All 13 reference flags (``producer.py:17-33``), same spellings."""
    p = argparse.ArgumentParser(prog="psana-ray-tpu-producer")
    p.add_argument("--exp", default="synthetic")
    p.add_argument("--run", type=int, default=1)
    p.add_argument("--detector_name", default="epix10k2M")
    p.add_argument("--calib", action="store_true", help="calibrated mode (else raw)")
    p.add_argument("--uses_bad_pixel_mask", action="store_true")
    p.add_argument("--manual_mask_path", default=None)
    p.add_argument("--ray_address", "--address", dest="address", default="auto")
    p.add_argument("--ray_namespace", "--namespace", dest="namespace", default="default")
    p.add_argument("--queue_name", default="shared_queue")
    p.add_argument("--queue_size", type=int, default=100)
    p.add_argument("--num_consumers", type=int, default=1)
    p.add_argument("--max_steps", type=int, default=None)
    p.add_argument("--log_level", default="INFO")
    from psana_ray_tpu.obs import (
        add_history_args,
        add_metrics_args,
        add_profile_args,
        add_trace_args,
    )
    from psana_ray_tpu.transport.addressing import add_cluster_args, add_wire_args

    add_metrics_args(p)
    add_trace_args(p)
    add_history_args(p)
    add_profile_args(p)
    add_cluster_args(p)
    add_wire_args(p, producer=True)
    p.add_argument("--num_shards", type=int, default=1, help="local ingest workers")
    p.add_argument("--num_events", type=int, default=1024, help="synthetic events")
    p.add_argument(
        "--shard_rank_offset", type=int, default=None,
        help="global shard offset of this process (default: auto from MPI/SLURM env)",
    )
    p.add_argument(
        "--total_shards", type=int, default=None,
        help="global shard count across all producer processes (default: auto)",
    )
    p.add_argument(
        "--start_event", type=int, default=0,
        help="skip events below this index in every shard (resume floor; "
        "the reference restarts from zero, SURVEY.md §5)",
    )
    p.add_argument(
        "--cursor_path", default=None,
        help="StreamCursor JSON written by a consumer (--cursor_path on "
        "psana-ray-tpu-consumer): on restart each shard resumes from its "
        "contiguous processed watermark (at-least-once)",
    )
    a = p.parse_args(argv)
    from psana_ray_tpu.transport.addressing import apply_cluster_args, apply_wire_args

    return PipelineConfig(
        source=SourceConfig(
            exp=a.exp,
            run=a.run,
            detector_name=a.detector_name,
            # reference parity: absence of --calib selects assembled-image
            # mode, not raw ADUs (reference producer.py:156-159)
            mode=RetrievalMode.CALIB if a.calib else RetrievalMode.IMAGE,
            max_steps=a.max_steps,
            num_events=a.num_events,
            start_event=a.start_event,
            cursor_path=a.cursor_path,
        ),
        mask=MaskConfig(a.uses_bad_pixel_mask, a.manual_mask_path),
        transport=apply_wire_args(
            apply_cluster_args(
                TransportConfig(
                    address=a.address,
                    namespace=a.namespace,
                    queue_name=a.queue_name,
                    queue_size=a.queue_size,
                    num_consumers=a.num_consumers,
                ),
                a,
            ),
            a,
        ),
    ), a


def detect_process_rank() -> tuple:
    """(process_rank, world_size) from the launcher environment.

    The reference gets these from ``MPI.COMM_WORLD`` (``producer.py:
    138-140``); here they come from the env vars every common launcher
    exports (Open MPI, MPICH/PMI, Slurm), so ``mpirun -n 4
    psana-ray-tpu-producer ...`` shards rank-derived with no mpi4py."""
    import os

    for rank_var, size_var in (
        ("OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE"),
        ("PMI_RANK", "PMI_SIZE"),
        ("SLURM_PROCID", "SLURM_NTASKS"),
    ):
        if rank_var in os.environ:
            return int(os.environ[rank_var]), int(os.environ.get(size_var, 1))
    return 0, 1


def shard_topology(args) -> tuple:
    """(shard_rank_offset, total_shards) for this process: explicit flags
    win; otherwise derived from the launcher rank/size so N processes x
    ``--num_shards`` local workers tile the global event space."""
    rank, world = detect_process_rank()
    offset = (
        args.shard_rank_offset
        if args.shard_rank_offset is not None
        else rank * args.num_shards
    )
    total = (
        args.total_shards if args.total_shards is not None else world * args.num_shards
    )
    return offset, total


def main(argv=None):
    from psana_ray_tpu.utils.hostmem import enable_large_alloc_reuse

    enable_large_alloc_reuse()  # MB-scale frame buffers: heap reuse, no re-faulting
    config, args = parse_arguments(argv)
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format=config.log.fmt,  # parity: producer.py:135-136
    )
    offset, total = shard_topology(args)
    runtime = ProducerRuntime(
        config,
        num_local_shards=args.num_shards,
        shard_rank_offset=offset,
        total_shards=total,
        stage_timing=args.metrics_port > 0,
    )

    def _sigint(signum, frame):  # parity: producer.py:73-76,142-143
        logger.info("SIGINT — stopping producer")
        runtime.stop()

    signal.signal(signal.SIGINT, _sigint)
    from psana_ray_tpu.obs import MetricsRegistry, start_metrics_server

    MetricsRegistry.default().register("producer", runtime.metrics)
    metrics_server = start_metrics_server(args.metrics_port, host=args.metrics_host)
    # history ring (ISSUE 13): feeds flight-dump tails + the /federate
    # endpoint's consumers; one daemon thread, --history_interval 0 = off
    from psana_ray_tpu.obs import configure_history_from_args, configure_profiling_from_args

    history = configure_history_from_args(args)
    # continuous profiler (ISSUE 16): flame sampler + per-frame cost
    # model; one daemon thread, --profile_hz 0 = off
    profiler = configure_profiling_from_args(args, "producer")
    monitor = None
    if metrics_server is not None and str(config.transport.address).startswith(
        ("tcp://", "cluster://")
    ):
        # depth for scrapes over a DEDICATED connection: on the data
        # connection a stats() probe would queue behind a put's reconnect
        # backoff under the client lock, hanging /metrics for the whole
        # outage (in-process/shm handles have no such serialization and
        # bootstrap attaches them directly)
        try:
            monitor = open_queue(
                config.transport, role="consumer", address=config.transport.address
            )
            runtime.metrics.attach_queue(monitor)
        except Exception as e:  # noqa: BLE001 — depth is optional
            logger.debug("queue monitor unavailable: %s", e)
    from psana_ray_tpu.obs.tracing import configure_from_args, exchange_anchors

    tracer = configure_from_args(args, "producer", queue=monitor)
    try:
        if tracer is not None and monitor is None:
            # clock alignment against the queue server (tcp opcode 'A'):
            # configure_from_args already exchanged over the monitor when
            # one exists; otherwise the data client speaks it too —
            # harmless pre-stream (a producer connection never holds
            # in-flight deliveries an opcode could ACK)
            runtime.bootstrap()
            exchange_anchors(runtime._queue)
        runtime.run(block=True)
    finally:
        if history is not None:
            history.stop()
        if metrics_server is not None:
            metrics_server.close()
        if monitor is not None and hasattr(monitor, "disconnect"):
            try:
                monitor.disconnect()
            except Exception:  # noqa: BLE001 — already closing
                pass
    logger.info("producer done: %s", runtime.metrics.status_line())


if __name__ == "__main__":
    main()
