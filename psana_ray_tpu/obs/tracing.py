"""Sampled per-frame distributed tracing across the pipeline's processes.
# lint: hot-path

PR 1 gave the pipeline aggregate stage histograms; this module answers the
question those cannot: *where did THIS frame spend its time* across the
producer -> queue server -> consumer -> device boundary (the per-request
trace production streaming systems pair with their counters — tf.data's
pipeline instrumentation and DALI's per-iteration view, PAPERS.md).

Three pieces:

- :class:`TraceContext` — a compact wire-format context (trace id, sample
  flag, origin host/pid) that rides the :class:`~psana_ray_tpu.records.
  FrameRecord` envelope. Sampled frames encode as schema v3 with the
  25-byte context appended after the shape; UNSAMPLED frames encode as
  plain v2, byte-identical to the pre-tracing wire format — the
  unsampled hot path pays zero allocations and zero wire bytes
  (the same gating discipline as PR 1's ``stage_timing``).
- :class:`Tracer` — the per-process span sink. Each process appends
  spans (producer: produce/enqueue; queue server: queue_dwell/relay;
  consumer: dequeue/batch/device_put/dispatch — reusing the
  :mod:`psana_ray_tpu.obs.stages` boundaries) to a bounded per-process
  JSONL spool, together with (wallclock, monotonic) clock anchors and
  peer-anchor exchanges (tcp opcode ``A``) that let the merge tool put
  three processes on one timeline.
- ``python -m psana_ray_tpu.obs.trace_merge`` reads the spools and emits
  Chrome trace-event JSON loadable in Perfetto / TensorBoard, one track
  per process, frame spans linked by trace id. The serving loops'
  phases (:func:`psana_ray_tpu.utils.trace.phase`) land in the same
  spool as ``stage.<name>`` spans, one per batch, under the batch's id;
  a frame's own spans name the batch it joined (``j``). What JAX
  traced, lowered, loaded or compiled before the first batch (and
  after: :mod:`psana_ray_tpu.obs.jitwatch`) lands there too, as
  ``jit.<kind>`` spans that name their function (``f``).

Everything here is pure stdlib (no numpy, no jax) so every process —
including the queue server — can afford the import. Recording a span is
one lock + one tuple appended to a bounded in-memory buffer: nothing is
serialized or written on the emitting thread until ``flush()`` or
``close()`` (process exit, a flight-recorder dump), so the instrument
does not cause the idle it bills (choosing-metrics guide, section 4:
"keep spans in memory and write them out when the benchmark ends").
"""

from __future__ import annotations

import atexit
import dataclasses
import gc
import itertools
import json
import os
import socket
import struct
import sys
import threading
import time
from typing import Any, Dict, Optional

from psana_ray_tpu.obs import jitwatch

__all__ = [
    "TraceContext",
    "Tracer",
    "TRACER",
    "TRACE_KEY",
    "SPAN_PRODUCE",
    "SPAN_RELAY",
    "add_trace_args",
    "configure_from_args",
    "exchange_anchors",
    "obs_status_suffix",
    "profiler_annotation",
]

# Reserved key in a record's ``hops`` dict carrying the trace id through
# the in-process batching path (the hops dict already rides the envelope;
# stage observation iterates only the HOP_* names, so the key is inert
# there).
TRACE_KEY = "trace_id"

# Span names beyond the canonical stage names (obs.stages):
SPAN_PRODUCE = "produce"  # instant: source read done (frame is born)
SPAN_RELAY = "relay"  # queue server: response serialization + send

GC_SPAN = "stage.gc"  # a generation-2 collection (obs.stages.PHASE_GC)

_FLAG_SAMPLED = 0x01


def profiler_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``name`` — a host region
    on the profiler's own timeline — or None in a process that never
    imported jax (no profile can be running there, and this module stays
    importable by the JAX-free producers and queue servers)."""
    jax = sys.modules.get("jax")
    return None if jax is None else jax.profiler.TraceAnnotation(name)


# trace_id:u64, origin_pid:u32, flags:u8, origin_host:12s (utf-8, padded)
_CTX_WIRE = struct.Struct("<QIB12s")


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """Compact per-frame trace context; rides the record envelope.

    ``trace_id`` is unique per sampled frame across the deployment
    (origin pid + counter mixed in); ``origin_host``/``origin_pid``
    identify the producing process for the merged timeline."""

    trace_id: int
    sampled: bool = True
    origin_host: str = ""
    origin_pid: int = 0

    WIRE_SIZE = _CTX_WIRE.size  # 25 bytes on sampled frames only

    def pack(self) -> Any:
        flags = _FLAG_SAMPLED if self.sampled else 0
        host = self.origin_host.encode("utf-8", "replace")[:12]
        return _CTX_WIRE.pack(
            self.trace_id & 0xFFFFFFFFFFFFFFFF, self.origin_pid & 0xFFFFFFFF,
            flags, host,
        )

    @staticmethod
    def unpack_from(buf, offset: int) -> "TraceContext":
        trace_id, pid, flags, host = _CTX_WIRE.unpack_from(buf, offset)
        return TraceContext(
            trace_id=trace_id,
            sampled=bool(flags & _FLAG_SAMPLED),
            origin_host=host.rstrip(b"\0").decode("utf-8", "replace"),
            origin_pid=pid,
        )


# Spool record tags (one JSON object per line):
#   m = meta (process identity, sample config)   a = clock anchor
#   p = peer anchor (tcp opcode 'A' exchange)    s = span   i = instant
#   d = tally, written with every flush: rows kept and dropped so far
# A span line is {"t":"s","id","n","a","b"} plus, on a frame's span, "j"
# (the id of the batch it joined) or, on a loop phase's span ("n" is
# "stage.<phase>" or "h2d", "id" the batch's), "k" (frames in the batch
# or turn) and, where the phase moved them, "y" (bytes); a "jit.<kind>"
# span (obs.jitwatch) carries "f", the jitted function's name.


class Tracer:
    """Per-process span sink with a bounded JSONL spool.

    Disabled (the default) every surface is a no-op behind ONE attribute
    check; ``maybe_trace`` on an enabled tracer allocates NOTHING for
    unsampled frames (counter arithmetic only — pinned by test and the
    hot-alloc checker's span fixtures)."""

    def __init__(self, jit_watch: Optional[jitwatch.JitWatch] = None):
        self.enabled = False
        # whose compile-path rows this tracer spools while it is on (the
        # process's, for the global tracer; none for one a test builds)
        self._jit_watch = jit_watch
        # reentrant: an allocation under the lock can start a full
        # collection, whose hook (_on_gc) records its span from inside
        self._lock = threading.RLock()
        self._every = 0  # sample 1 frame in N; 0 = off
        # frame ticker: itertools.count.__next__ is atomic in CPython, so
        # concurrent producer shard threads get UNIQUE frame numbers (and
        # therefore unique trace ids) without a hot-path lock; _count is
        # a best-effort gauge of the latest value for snapshot()
        self._ticker = itertools.count(1)
        self._count = 0
        self._id_base = 0
        self._host = socket.gethostname()
        self._pid = os.getpid()
        self._process = ""
        self._path: Optional[str] = None
        self._f = None
        # spans and instants as tuples, serialized at flush()/close()
        self._buf: list = []  # guarded-by: _lock
        self._spans = 0
        self._drops = 0
        self._max_spans = 0
        # loop phases' spans (O(batches + turns)) under a bound of their
        # own, so that the frames' rows (O(frames)) never crowd them out
        self._phase_spans = 0
        self._phase_drops = 0
        self._atexit_registered = False
        # full (generation-2) collections while tracing is on: a stop-
        # the-world pause inside whatever phase a serving thread had open
        self._gc_seconds = 0.0
        self._gc_count = 0
        self._gc_t0 = 0.0
        self._gc_ann = None

    # -- configuration ----------------------------------------------------
    def configure(
        self,
        spool_dir: str,
        sample_every: int = 100,
        process: str = "proc",
        max_spans: int = 200_000,
    ) -> "Tracer":
        """Enable tracing: sample 1 frame in ``sample_every`` (1 = every
        frame) and spool spans to ``spool_dir``. Reconfiguring closes the
        previous spool first. ``max_spans`` bounds the spool, and with it
        the spans held in memory between flushes — beyond it spans are
        dropped and counted (``spans_dropped``), never blocking the
        pipeline. The loop phases' spans (:meth:`phase_span`) count
        against a second bound of the same size: a stream of traced
        frames that fills the first leaves every phase's span in place
        (the readers of the phases refuse a spool that dropped one). What
        the process's ``jitwatch`` heard BEFORE the spool opened goes into
        it here, on the same monotonic clock, and what it hears from now
        on as it comes: a restart's trace / lower / load / compile stand
        on the process's track ahead of its first batch."""
        if sample_every <= 0:
            raise ValueError("sample_every must be >= 1 (frames per sample)")
        with self._lock:
            self._close_locked()
            os.makedirs(spool_dir, exist_ok=True)
            self._process = process
            self._pid = os.getpid()
            self._every = int(sample_every)
            self._ticker = itertools.count(1)
            self._count = 0
            self._spans = 0
            self._drops = 0
            self._phase_spans = 0
            self._phase_drops = 0
            self._max_spans = max_spans
            # unique-across-processes id space: pid in the top bits, a
            # wall-clock sub-second salt so quick restarts don't collide
            salt = int(time.time() * 1e6) & 0xFFFFF
            self._id_base = ((self._pid & 0xFFFFFFFF) << 28) ^ (salt << 8)
            self._path = os.path.join(
                spool_dir, f"{process}-{self._host}-{self._pid}.trace.jsonl"
            )
            self._f = open(self._path, "w", encoding="utf-8")
            self._buf = []
            self._f.write(self._line(
                t="m", process=process, host=self._host, pid=self._pid,
                every=self._every, start_wall=time.time(),
                start_mono=time.monotonic(),
            ) + "\n")
            self._flush_locked()  # the first clock anchor
            self._gc_seconds, self._gc_count = 0.0, 0
            if self._on_gc not in gc.callbacks:
                gc.callbacks.append(self._on_gc)
            self.enabled = True
            if not self._atexit_registered:
                self._atexit_registered = True
                atexit.register(self.close)
            if self._jit_watch is not None:
                for kind, fun, t0, t1, _ in self._jit_watch.attach(self.phase_span):
                    self.phase_span(0, kind, t0, t1, label=fun)
        return self

    @property
    def spool_path(self) -> Optional[str]:
        return self._path

    @property
    def sample_every(self) -> int:
        return self._every

    # -- hot path ---------------------------------------------------------
    def maybe_trace(self) -> Optional[TraceContext]:
        """Per-frame sampling gate (producer side). Disabled: one
        attribute check. Enabled but unsampled: counter arithmetic only —
        no allocation, no lock. Sampled: a fresh :class:`TraceContext`.

        Thread-safe without locking: the ticker hands concurrent shard
        threads unique frame numbers (atomic ``__next__``), and the
        sample config is read ONCE so a concurrent ``close()`` can never
        produce a divide-by-zero mid-frame — worst case a frame straddling
        the close is sampled into a spool that is already flushing."""
        if not self.enabled:
            return None
        every = self._every
        if every <= 0:  # racing a close(): tracing is over, not an error
            return None
        n = next(self._ticker)
        self._count = n  # best-effort gauge (snapshot/status only)
        if n % every:
            return None
        return TraceContext(
            trace_id=(self._id_base + n) & 0xFFFFFFFFFFFFFFFF,
            sampled=True,
            origin_host=self._host,
            origin_pid=self._pid,
        )

    # -- span sinks (sampled frames and loop phases) ----------------------
    def span(self, trace_id: int, name: str, t0: float, t1: float) -> None:
        """One completed span of a sampled FRAME, ``[t0, t1]`` in THIS
        process's monotonic domain (the merge tool aligns domains via the
        spooled anchors)."""
        if self.enabled:
            self._keep((trace_id, name, t0, t1, None, 0))

    def phase_span(self, batch_id: int, name: str, t0: float, t1: float,
                   frames: int = 0, nbytes: int = 0, label: str = "") -> None:
        """One completed span of a serving thread's loop PHASE
        (``utils.trace.phase``: ``stage.<name>``, or ``h2d``) under the
        batch's id, with the ``frames`` the batch or loop turn held and
        the ``nbytes`` it moved; or one ``jit.<kind>`` span of
        ``obs.jitwatch`` with the function's name as its ``label``. Kept
        under the phases' own bound."""
        if not self.enabled:
            return
        with self._lock:
            if self._phase_spans >= self._max_spans:
                self._phase_drops += 1
                return
            self._phase_spans += 1
            self._buf.append((batch_id, name, t0, t1, None, frames, nbytes, label))

    def _keep(self, row: tuple) -> None:
        """THE bounded sink of a frame's single rows: kept in memory, or
        dropped and counted beyond ``max_spans``."""
        with self._lock:
            if self._spans >= self._max_spans:
                self._drops += 1
                return
            self._spans += 1
            self._buf.append(row)

    def extend(self, rows) -> None:
        """A batch's worth of frame spans, ``(trace_id, name, t0, t1,
        batch_id, 0)`` each, under ONE lock acquisition; what does not
        fit the bound is dropped and counted."""
        if not self.enabled:
            return
        with self._lock:
            room = self._max_spans - self._spans
            if room < len(rows):
                self._drops += len(rows) - max(room, 0)
                rows = rows[: max(room, 0)]
            self._spans += len(rows)
            self._buf.extend(rows)

    def instant(self, trace_id: int, name: str, t: float) -> None:
        """A zero-duration marker (e.g. ``produce`` at source-read done)."""
        if self.enabled:
            self._keep((trace_id, name, t))

    def _on_gc(self, when: str, info: dict) -> None:
        """``gc.callbacks`` hook, installed while tracing is on: a full
        collection becomes a ``stage.gc`` region on the profiler's
        timeline and a span in the spool, and counts into
        ``gc_seconds_total``. Younger generations (sub-millisecond, many
        per second) pass with one comparison."""
        if info.get("generation") != 2:
            return
        if when == "start":
            self._gc_t0 = time.monotonic()
            ann = self._gc_ann = profiler_annotation(GC_SPAN)
            if ann is not None:
                ann.__enter__()
            return
        t1 = time.monotonic()
        ann, self._gc_ann = self._gc_ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        t0, self._gc_t0 = self._gc_t0, 0.0
        if t0:
            with self._lock:
                self._gc_seconds += t1 - t0
                self._gc_count += 1
            self.phase_span(0, GC_SPAN, t0, t1)

    # -- clock alignment --------------------------------------------------
    def record_peer_anchor(self, exchange: dict) -> None:
        """Record one ping/anchor exchange with the queue server (tcp
        opcode ``A``: local send/recv wall+mono around the server's
        wall+mono reply) — lets the merge tool align this process to the
        server's clock across hosts, bounded by the measured RTT."""
        if not self.enabled:
            return
        with self._lock:
            self._write_locked(self._line(t="p", **exchange))

    def _anchor_line(self) -> str:
        return self._line(t="a", wall=time.time(), mono=time.monotonic())

    def _write_locked(self, line: str) -> None:
        # guarded-by-caller: _lock. Control-plane lines (anchors, peer
        # exchanges: a handful per run) go straight to the file.
        if self._f is not None:
            self._f.write(line + "\n")

    @staticmethod
    def _line(**kw) -> str:
        return json.dumps(kw, separators=(",", ":"))

    # -- lifecycle --------------------------------------------------------
    def flush(self) -> None:
        """Serialize what the buffer holds to the spool, with a clock
        anchor. Off the emitting threads: ``close()``, process exit, a
        flight-recorder dump."""
        with self._lock:
            self._flush_locked()

    def _flush_locked(self) -> None:
        # guarded-by-caller: _lock
        if self._f is None:
            self._buf = []
            return
        rows, self._buf = self._buf, []
        out = [self._anchor_line()]
        for row in rows:
            if len(row) == 3:
                out.append(self._line(t="i", id=row[0], n=row[1], a=row[2]))
                continue
            tid, name, t0, t1, joined, frames, *more = row  # a phase's row ends in bytes, label
            rec = {"t": "s", "id": tid, "n": name, "a": t0, "b": t1}
            if joined is not None:
                rec["j"] = joined
            if frames:
                rec["k"] = frames
            if more and more[0]:
                rec["y"] = more[0]
            if more and more[1]:
                rec["f"] = more[1]
            out.append(self._line(**rec))
        out.append(self._line(
            t="d", spans=self._spans, dropped=self._drops,
            phase_spans=self._phase_spans, phase_dropped=self._phase_drops,
        ))
        self._f.write("\n".join(out) + "\n")
        self._f.flush()

    def close(self) -> None:
        """Flush + close the spool and disable. Safe to call repeatedly
        (registered atexit)."""
        with self._lock:
            self._close_locked()

    def _close_locked(self) -> None:
        # guarded-by-caller: _lock
        if self._f is not None:
            self._flush_locked()
            try:
                self._f.close()
            except OSError:
                pass
        self._f = None
        self.enabled = False
        self._every = 0
        if self._jit_watch is not None:
            self._jit_watch.detach(self.phase_span)
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    # -- observability of the observer ------------------------------------
    def snapshot(self) -> dict:
        """Registry source: is tracing on, at what rate, how many spans."""
        with self._lock:
            out: Dict[str, Any] = {
                "enabled": self.enabled,
                "sample_every": self._every,
                "frames_seen_total": self._count,
                "spans_total": self._spans,
                "spans_dropped_total": self._drops,
                "phase_spans_total": self._phase_spans,
                "phase_spans_dropped_total": self._phase_drops,
                "gc_collections_total": self._gc_count,
                "gc_seconds_total": round(self._gc_seconds, 6),
            }
        return out

    def status_suffix(self, flight=None) -> str:
        """Heartbeat-line suffix: sample rate, spans emitted, flight-
        recorder event count — empty when tracing is off (the line stays
        exactly as it was before this feature)."""
        if not self.enabled:
            return ""
        with self._lock:
            every, spans = self._every, self._spans + self._phase_spans
            drops = self._drops + self._phase_drops
        suffix = f" trace[1/{every} spans={spans}"
        if drops:
            suffix += f" drops={drops}"
        suffix += "]"
        if flight is not None:
            suffix += f" flight={flight.event_count}"
        return suffix


#: The process-global tracer every CLI configures (tests build their own);
#: it spools what the process's compile-path listener hears.
TRACER = Tracer(jit_watch=jitwatch.WATCH)


def exchange_anchors(queue, n: int = 3, tracer: Optional[Tracer] = None) -> int:
    """Run ``n`` ping/anchor exchanges against a queue handle that speaks
    the anchor RPC (``TcpQueueClient.anchor``) and spool them. Returns how
    many succeeded; 0 for transports without the RPC (in-process / shm —
    same-host wall clocks already agree)."""
    tr = TRACER if tracer is None else tracer
    anchor = getattr(queue, "anchor", None)
    if not tr.enabled or anchor is None:
        return 0
    done = 0
    for _ in range(n):
        try:
            tr.record_peer_anchor(anchor())
            done += 1
        except Exception:  # noqa: BLE001 — alignment is best-effort
            break
    return done


# -- CLI wiring ------------------------------------------------------------
def add_trace_args(parser) -> None:
    """The shared ``--trace_dir`` / ``--trace_sample`` / ``--flight_dir``
    trio every long-running CLI exposes (one definition, like
    ``add_metrics_args``)."""
    parser.add_argument(
        "--trace_dir", default=None,
        help="enable sampled per-frame distributed tracing: spool spans "
        "to this directory (one JSONL file per process); merge with "
        "`python -m psana_ray_tpu.obs.trace_merge <dir>` and open the "
        "result in Perfetto. Default off (zero cost)",
    )
    parser.add_argument(
        "--trace_sample", type=int, default=100,
        help="sample 1 frame in N for tracing (1 = every frame); only "
        "active with --trace_dir. Unsampled frames pay zero allocations",
    )
    parser.add_argument(
        "--flight_dir", default=None,
        help="crash flight recorder: dump the event ring + metrics "
        "snapshot + thread stacks here on stall/unhandled exception/"
        "SIGUSR2 (default: --trace_dir when set, else off)",
    )


def configure_from_args(args, process: str, queue=None) -> Optional[Tracer]:
    """CLI entry: configure the global tracer + flight recorder from the
    ``add_trace_args`` flags. Registers both as metrics-registry sources
    (``trace`` / ``flight``) so /metrics shows tracing is on. ``queue``
    (optional, a TCP client or monitor handle) seeds the clock alignment
    with peer-anchor exchanges. Returns the tracer, or None when tracing
    stays off."""
    trace_dir = getattr(args, "trace_dir", None)
    flight_dir = getattr(args, "flight_dir", None) or trace_dir
    out = None
    if trace_dir:
        TRACER.configure(
            trace_dir, sample_every=max(1, args.trace_sample), process=process
        )
        out = TRACER
    from psana_ray_tpu.obs.flight import FLIGHT

    if flight_dir:
        FLIGHT.install(flight_dir, process=process)
    if trace_dir or flight_dir:
        from psana_ray_tpu.obs.registry import MetricsRegistry

        reg = MetricsRegistry.default()
        if trace_dir:
            reg.register("trace", TRACER)
        reg.register("flight", FLIGHT)
    if out is not None and queue is not None:
        exchange_anchors(queue)
    return out


def obs_status_suffix() -> str:
    """One-call heartbeat suffix over the global tracer + flight recorder
    (the consumer/sfx ``--status_interval`` lines append this). Durable-
    storage breadcrumbs (ISSUE 8: segment rollover, spill entry/exit,
    recovery scans, torn-tail repairs, replay opens/gaps) get their own
    bracket whenever any fired in this process — empty otherwise, so
    memory-only runs keep their exact pre-durability heartbeat lines."""
    from psana_ray_tpu.obs.flight import FLIGHT

    out = TRACER.status_suffix(FLIGHT)
    rolls = FLIGHT.count_of("segment_rollover")
    spills = FLIGHT.count_of("spill_enter")
    recoveries = FLIGHT.count_of("recovery_scan", "durable_reexpose")
    torn = FLIGHT.count_of("torn_tail_repair")
    replays = FLIGHT.count_of("replay_open", "replay_gap")
    if rolls or spills or recoveries or torn or replays:
        out += (
            f" durable[roll={rolls} spill={spills} recover={recoveries}"
            f" torn={torn} replay={replays}]"
        )
    return out
