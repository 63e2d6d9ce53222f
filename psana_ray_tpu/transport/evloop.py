# lint: hot-path
"""Event-loop TCP queue server: one epoll loop, thousands of streamed
consumers (ISSUE 6).

The thread-per-connection server (removed in ISSUE 7 after one release
behind ``mode="threads"``) was fine at tens of consumers and dead at
thousands: a thread stack (plus an ack-reader thread per streamed
subscriber), GIL contention across serve threads, and lock convoys on
the shared queue maps. PR 5's server-push streaming already removed the
request/response coupling, so the relay is shaped like an event loop —
this module is that loop, and since ISSUE 7 it is THE server.

Design:

- ONE thread runs a ``selectors.DefaultSelector`` (epoll on Linux)
  readiness loop: non-blocking accept, non-blocking incremental reads,
  non-blocking scatter-gather writes with EPOLLOUT-driven partial-send
  resumption. Thread count is independent of connection count; memory
  is O(connections x small struct).
- Each connection is a :class:`_EvConn` state machine over all 22
  opcodes of the wire protocol (the opcode constants and
  part-gathering helpers are imported from ``transport.tcp``, so the
  wire format cannot fork). Reads land incrementally: control
  fields into a per-connection reused scratch buffer, payloads straight
  into pooled ``recv_into`` leases (the zero-copy datapath of ISSUE 2
  is unchanged — a PUT's pooled buffer is the very memory a later
  push/GET response streams from).
- Blocking waits become deferred state, not parked threads: a 'D'
  (bounded get-batch) against an empty queue, a 'U' (bounded put)
  against a full queue, a 'W' (windowed put) enqueue under
  backpressure, and a stream with an exhausted credit window all park
  the connection as a *waiter* on its queue. Waiters are served by the
  pump when queue state changes (an in-loop enqueue/dequeue, a
  RingBuffer change listener poking the loop's waker pipe, or — for
  backings without listeners, e.g. shm rings fed by other processes —
  a short poll tick), and bounded waits expire off a timer heap.
- Delivery contract parity: popped items ride ``conn.in_flight`` until
  the next opcode (implicit ACK) or BYE, and re-enqueue at queue head
  when the connection dies first; stream pushes ride the per-connection
  unacked window and redeliver the exact unacked tail on death.
  At-least-once, duplicates possible, silent loss never — the same
  words as the threaded server because it is the same contract.

While a connection has a deferred op outstanding, its reads pause (one
outstanding request per connection — anything already pipelined waits
in the kernel buffer) with a 1-byte ``MSG_PEEK`` probe keeping EOF
detection alive, mirroring the threaded server's ``_peer_hung_up``
probe during blocking enqueues.

Everything here must stay non-blocking: the ``event-loop-blocking``
lint checker roots its call graph at :meth:`EventLoop.run` and bans
``time.sleep``, the blocking send/recv helpers, bare ``acquire()``,
unbounded joins and unbounded ``Condition.wait`` from everything
reachable.
"""

from __future__ import annotations

import heapq
import json
import os
import selectors
import socket
import struct
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.obs.registry import federation_payload as _metrics_rpc_payload
from psana_ray_tpu.obs.tracing import TRACER
from psana_ray_tpu.transport.registry import TransportClosed
from psana_ray_tpu.transport.ring import EMPTY
from psana_ray_tpu.transport.codec import (
    CODEC_NONE,
    CODEC_STATS,
    decode_payload as _decode,
    encode_for_wire as _wire_encode,
    negotiate_codec,
    payload_nbytes as _parts_nbytes,
)
from psana_ray_tpu.storage.durable import SpilledRecord
from psana_ray_tpu.storage.log import COMMIT_DELIVERED
from psana_ray_tpu.transport.splice import (
    FileSpan,
    SPLICE,
    fallback_errno as _splice_fallback_errno,
    sendfile_capable as _sendfile_capable,
)
from psana_ray_tpu.transport.workers import MIGRATE_GRACE_S, MIGRATE_RETRY_S
from psana_ray_tpu.transport.tcp import (
    _MAX_PAYLOAD,
    _OP_ANCHOR,
    _OP_BYE,
    _OP_CLOSE,
    _OP_CLUSTER,
    _OP_CODEC,
    _OP_COMMIT,
    _OP_GET,
    _OP_GET_BATCH,
    _OP_GET_BATCH_WAIT,
    _OP_OPEN,
    _OP_PUT,
    _OP_PUT_BATCH,
    _OP_PROMOTE,
    _OP_PUT_SEQ,
    _OP_PUT_WAIT,
    _OP_REPLAY,
    _OP_REPL_APPEND,
    _OP_REPL_OPEN,
    _OP_SIZE,
    _OP_STATS,
    _OP_STREAM,
    _OP_STREAM_ACK,
    _SENDMSG_IOV,
    _SERVER_WAIT_CAP_S,
    _REPL_NO_FLOOR,
    _ST_CLOSED,
    _ST_ERR,
    _ST_NO,
    _ST_OK,
    _emit_relay_spans,
    _gather_parts,
    _queue_stats_payload,
    _refuse_conn,
    _stamp_relay_arrival,
    STREAM,
)

# Pump cadence for queues WITHOUT a change listener (shm rings fed by
# other processes): waiters are re-checked this often. Queues with a
# listener (RingBuffer) poke the waker pipe on every change, so their
# tick is only a safety net.
POLL_TICK_S = 0.02
LISTENED_TICK_S = 0.25
IDLE_TICK_S = 0.5
# liveness re-probe cadence for parked connections whose reads are
# paused behind pipelined bytes — the same 0.5 s dead-peer detection
# slice the threaded server's _peer_hung_up loop used
PROBE_INTERVAL_S = 0.5
# max frames popped per stream-waiter visit — fairness bound so one
# wide-window subscriber cannot monopolize a pump pass
_STREAM_POP_MAX = 64

# weighted deficit round-robin (ISSUE 12): frames of deficit each
# tenant earns per replenish round, per unit of weight. Small enough
# that weight shares converge within a few hundred frames; large
# enough that a weight-1 tenant still fills a whole max-size batch
_WDRR_QUANTUM = 8
_TENANT_DEFAULT = "default"
_TENANT_WEIGHT_MAX = 64


class _Wdrr:
    """Per-queue weighted-deficit state for the stream pump: streams
    sharing a queue are served in arrival rotation, but each pop is
    capped by the connection's TENANT deficit. A replenish round hands
    out ``_WDRR_QUANTUM`` frames PER WAITING STREAM CONNECTION, split
    across tenants in proportion to weight — so a tenant's share is
    weight-proportional no matter how many sockets or credits it
    brings (one greedy tenant cannot starve the rest), while the
    round's total volume scales with the fleet (1024 single-tenant
    subscribers keep the pre-ISSUE-12 per-pass throughput: their one
    shared budget is 1024 x quantum, not 1 x). Loop-thread-only state:
    no lock."""

    __slots__ = ("deficit",)

    def __init__(self):
        self.deficit: Dict[str, float] = {}

    def allowance(self, tenant: str) -> float:
        return self.deficit.get(tenant, 0.0)

    def charge(self, tenant: str, n: int) -> None:
        self.deficit[tenant] = self.deficit.get(tenant, 0.0) - n

    def all_dry(self, tenant_weights: Dict[str, int]) -> bool:
        """No waiting tenant can pop even one frame — time for a round."""
        return all(self.deficit.get(t, 0.0) < 1.0 for t in tenant_weights)

    def replenish(self, tenant_weights: Dict[str, int], n_conns: int) -> None:
        """A new round: ``quantum * n_conns`` total frames of deficit,
        split by weight share, capped at two rounds of credit (bursts
        must not bank unbounded catch-up); tenants that left are
        dropped."""
        if not tenant_weights:
            return
        for t in list(self.deficit):
            if t not in tenant_weights:
                del self.deficit[t]
        total = float(_WDRR_QUANTUM * max(1, n_conns))
        sum_w = sum(tenant_weights.values())
        for t, w in tenant_weights.items():
            earn = max(1.0, total * w / sum_w)
            self.deficit[t] = min(
                2.0 * earn, max(0.0, self.deficit.get(t, 0.0)) + earn
            )


def _stream_tenant_weights(get_waiters) -> Tuple[Dict[str, int], int]:
    """(tenant -> weight, live stream-conn count) over one queue's
    waiters (several connections may share a tenant; the LARGEST
    advertised weight wins — a tenant's share is per tenant, not per
    socket)."""
    out: Dict[str, int] = {}
    n = 0
    for conn in get_waiters:
        if conn.stream is None or conn.closed:
            continue
        n += 1
        w = out.get(conn.tenant, 0)
        if conn.weight > w:
            out[conn.tenant] = conn.weight
    return out, n


class EvLoopTelemetry:
    """Loop-health gauges for the event-loop server (obs source
    ``evloop``): connection counts, admission refusals, and loop lag —
    how long one dispatch pass holds the loop and how late bounded-wait
    timers fire. One process-wide instance (:data:`EVLOOP`), registered
    in the default MetricsRegistry on first loop start."""

    def __init__(self):
        self._lock = threading.Lock()
        self._registered = False  # guarded-by: _lock
        self.connections = 0  # guarded-by: _lock
        self.connections_peak = 0  # guarded-by: _lock
        self.accepted_total = 0  # guarded-by: _lock
        self.refused_total = 0  # guarded-by: _lock
        self.loops_total = 0  # guarded-by: _lock
        self.dispatch_ms_last = 0.0  # guarded-by: _lock
        self.dispatch_ms_max = 0.0  # guarded-by: _lock
        self.dispatch_ms_ewma = 0.0  # guarded-by: _lock
        self.timer_lag_ms_max = 0.0  # guarded-by: _lock
        # busy fraction = time-in-dispatch / (dispatch + select): the
        # loop-saturation signal ROADMAP item 4's elasticity controller
        # keys on — 1.0 means the loop never reaches select() idle-wait
        self.dispatch_s_total = 0.0  # guarded-by: _lock
        self.select_s_total = 0.0  # guarded-by: _lock
        self.busy_frac_ewma = 0.0  # guarded-by: _lock

    def ensure_registered(self):
        with self._lock:
            if self._registered:
                return
            self._registered = True
        try:
            from psana_ray_tpu.obs import MetricsRegistry

            MetricsRegistry.default().register("evloop", self)
        except Exception:  # obs optional: transport must work without it
            pass

    def conn_opened(self):
        with self._lock:
            self.accepted_total += 1
            self.connections += 1
            if self.connections > self.connections_peak:
                self.connections_peak = self.connections

    def conn_closed(self):
        with self._lock:
            self.connections -= 1

    def refused(self):
        with self._lock:
            self.refused_total += 1

    def loop_pass(self, dispatch_ms: float, select_ms: float = 0.0):
        with self._lock:
            self.loops_total += 1
            self.dispatch_ms_last = dispatch_ms
            if dispatch_ms > self.dispatch_ms_max:
                self.dispatch_ms_max = dispatch_ms
            self.dispatch_ms_ewma += 0.05 * (dispatch_ms - self.dispatch_ms_ewma)
            self.dispatch_s_total += dispatch_ms * 1e-3
            self.select_s_total += select_ms * 1e-3
            span_ms = dispatch_ms + select_ms
            if span_ms > 0.0:
                frac = dispatch_ms / span_ms
                self.busy_frac_ewma += 0.05 * (frac - self.busy_frac_ewma)

    def timer_lag(self, lag_ms: float):
        with self._lock:
            if lag_ms > self.timer_lag_ms_max:
                self.timer_lag_ms_max = lag_ms

    def stats(self) -> dict:
        with self._lock:
            return {
                "connections": self.connections,
                "connections_peak": self.connections_peak,
                "accepted_total": self.accepted_total,
                "refused_total": self.refused_total,
                "loops_total": self.loops_total,
                "dispatch_ms_last": round(self.dispatch_ms_last, 3),
                "dispatch_ms_max": round(self.dispatch_ms_max, 3),
                "dispatch_ms_ewma": round(self.dispatch_ms_ewma, 3),
                "timer_lag_ms_max": round(self.timer_lag_ms_max, 3),
                "busy_frac": round(
                    self.dispatch_s_total
                    / (self.dispatch_s_total + self.select_s_total)
                    if (self.dispatch_s_total + self.select_s_total) > 0.0
                    else 0.0,
                    6,
                ),
                "busy_frac_ewma": round(self.busy_frac_ewma, 6),
            }

    # obs registry source protocol
    def snapshot(self) -> dict:
        return self.stats()


EVLOOP = EvLoopTelemetry()


class _StreamState:
    """Per-connection stream-mode state ('M'): the credit window and the
    unacked redelivery tail that the threaded server kept in a dedicated
    serve thread + ack-reader thread, folded into the connection."""

    __slots__ = ("window", "seq", "acked", "unacked", "queue_closed")

    def __init__(self, window: int):
        self.window = window
        self.seq = 0
        self.acked = 0
        self.unacked: deque = deque()  # (seq, item) in push order
        self.queue_closed = False

    def budget(self) -> int:
        return self.window - (self.seq - self.acked)


class _QueueState:
    """Loop-side view of one backing queue: who is waiting on it."""

    __slots__ = (
        "queue", "get_waiters", "put_waiters", "ra_waiters", "repl",
        "listened", "unlisten", "wdrr",
    )

    def __init__(self, queue):
        self.queue = queue
        self.get_waiters: deque = deque()  # 'D' waiters + stream conns
        self.put_waiters: deque = deque()  # 'U'/'W' waiters, FIFO
        # replicated-ack-floor waiters (ISSUE 11): puts already logged
        # and enqueued whose producer ack is HELD until the follower has
        # logged them (pending kind "RA"); FIFO == offset order
        self.ra_waiters: deque = deque()
        self.repl = None  # the queue's ReplicationSender, cached
        self.listened = False
        self.unlisten = None  # callable removing the change listener
        # per-tenant weighted-deficit budgets for the stream pump
        self.wdrr = _Wdrr()


class _QueueClosedSignal(Exception):
    """Internal: the backing queue raised TransportClosed mid-pump."""


class _EvConn:
    """One connection's state machine: incremental reads, an outbound
    scatter-gather write queue, the in-flight delivery window, and
    (when subscribed) the stream credit window."""

    __slots__ = (
        "loop", "sock", "srv", "queue", "in_flight", "out", "out_bytes",
        "closing", "closed", "stream", "replay", "replica", "pending",
        "op_gen", "codec", "tenant", "weight",
        "_out_enq_total", "_out_releases",
        "_hdr", "_hdr_mv", "_target", "_need", "_got", "_cb", "_lease",
        "_want_read", "_want_write", "_mask", "_sendmsg",
        "_qb_remaining", "_qb_items", "_pw_wait_s", "_w_seq",
        "_r_from", "_v_off", "_v_floor", "_open_ns", "_open_nm",
        "_open_buf", "_no_splice", "_migration",
    )

    def __init__(self, loop: "EventLoop", sock: socket.socket, srv):
        self.loop = loop
        self.sock = sock
        self.srv = srv
        self.queue = srv.queue  # rebound by OPEN; default-queue back-compat
        # popped-but-unconfirmed deliveries: cleared at the next opcode
        # (implicit ACK), re-enqueued if the connection dies first — the
        # same delivery contract as the threaded server
        self.in_flight: List[Any] = []
        self.out: deque = deque()  # memoryview parts awaiting send
        self.out_bytes = 0
        self.closing = False  # flush remaining out bytes, then close
        self.closed = False
        self.stream: Optional[_StreamState] = None
        # negotiated wire codec ('Z', ISSUE 9): frame payloads SENT on
        # this connection compress with it (relay pass-through reuses a
        # record's cached compressed bytes when the codec matches);
        # receives are tag-driven and need no per-connection state
        self.codec = None
        # fair-share identity (ISSUE 12): set by the tenant=<name>:<w>
        # capability field on the 'Z' exchange; connections that never
        # hello share the default tenant's budget (pre-ISSUE-12 parity)
        self.tenant = _TENANT_DEFAULT
        self.weight = 1
        # compressed staging leases awaiting flush: (enqueued-bytes
        # mark, lease) released once the outbound byte counter passes
        # the mark — a lease must outlive its queued memoryview
        self._out_enq_total = 0
        self._out_releases: deque = deque()
        # durable replay cursor ('R'): when set, this connection's reads
        # serve the log non-destructively instead of popping the queue
        self.replay = None
        # replica mode ('H', ISSUE 11): when set (a _ReplicaEntry), this
        # connection is an owner's replication link — it carries only
        # 'V' appends downstream and their cumulative acks back
        self.replica = None
        self.pending: Optional[dict] = None  # deferred 'D'/'U'/'W' state
        self.op_gen = 0  # staleness guard for timer-heap entries
        self._hdr = bytearray(64)  # reused control-field scratch
        self._hdr_mv = memoryview(self._hdr)
        self._target: Optional[memoryview] = None
        self._need = 0
        self._got = 0
        self._cb = None
        self._lease = None  # pooled lease a payload is landing in
        self._want_read = False
        self._want_write = False
        self._mask = 0
        self._sendmsg = getattr(sock, "sendmsg", None)
        self._qb_remaining = 0
        self._qb_items: List[Any] = []
        self._pw_wait_s = 0.0
        self._w_seq = 0
        self._r_from = 0
        self._v_off = 0
        self._v_floor = 0
        self._open_ns = ""
        self._open_nm = ""
        self._open_buf = b""
        # set when THIS socket refused os.sendfile (TLS wrapper, exotic
        # family): spilled records materialize instead of queueing
        # spans that would each fail at the pump
        self._no_splice = False
        # multi-worker handoff in progress (ISSUE 17): {"target",
        # "ctx", "deadline"} while this connection waits to ship to the
        # queue's owning worker — reads pause, queued bytes flush first
        self._migration = None

    # -- read engine ------------------------------------------------------
    def _arm(self, mv: memoryview, cb, lease=None) -> None:
        self._lease = lease
        self._target = mv
        self._need = mv.nbytes
        self._got = 0
        self._cb = cb

    def _expect(self, n: int, cb) -> None:
        self._arm(self._hdr_mv[:n], cb)

    def _expect_payload(self, n: int, cb) -> None:
        if n > _MAX_PAYLOAD:
            raise ConnectionError(
                f"payload length {n} exceeds wire maximum {_MAX_PAYLOAD}"
            )
        lease = self.srv._pool.lease(n)
        self._arm(lease.mv, cb, lease=lease)

    def _await_op(self) -> None:
        self._expect(1, self._on_op)

    def on_readable(self) -> None:
        if self.closed or self.closing:
            return
        if self.pending is not None:
            self._probe_while_pending()
            return
        while True:
            if self._got < self._need:
                try:
                    k = self.sock.recv_into(self._target[self._got:])
                except (BlockingIOError, InterruptedError):
                    return
                if k == 0:
                    raise ConnectionError("peer closed")
                self._got += k
                if self._got < self._need:
                    continue
            cb = self._cb
            self._cb = None
            cb()
            if self.closed or self.closing or self.pending is not None:
                return
            if self._cb is None:  # handler did not arm a next read
                return

    def _probe_while_pending(self) -> None:
        """Readable while a deferred op is outstanding: either EOF (the
        peer died mid-wait — cancel the op, drop the never-enqueued
        frame, exactly like the threaded server's liveness probe) or
        pipelined bytes that must wait their turn — pause read interest
        (level-triggered epoll would spin otherwise) and schedule a
        liveness re-probe so a peer that dies AFTER pipelining is still
        detected within the probe interval, matching the threaded
        server's 0.5 s `_peer_hung_up` slices; without it a crashed
        windowed producer would pin the parked frame's lease forever
        and late-enqueue on top of its own reconnect resend."""
        try:
            k = self.sock.recv_into(self._hdr_mv[:1], 1, socket.MSG_PEEK)
        except (BlockingIOError, InterruptedError):
            return
        if k == 0:
            raise ConnectionError("peer closed while op deferred")
        self._set_interest(read=False)
        self.loop.add_liveness_probe(self)

    # -- write engine -----------------------------------------------------
    def send_parts(self, parts, release=None) -> None:
        """Queue parts for sending. ``release`` (a lease or list of
        leases backing compressed parts) is released once every byte
        queued SO FAR has left for the kernel — never while a queued
        memoryview still references the lease's buffer.

        A :class:`FileSpan` part (the kernel pass-through path) queues
        AS ITSELF — it must not pass through ``_gather_parts``, which
        would try to take a memoryview of it; byte runs between spans
        still gather/coalesce as before."""
        run: List[Any] = []
        for p in parts:
            if type(p) is FileSpan:
                if run:
                    self._enqueue_bufs(run)
                    run = []
                self.out.append(p)
                self.out_bytes += p.nbytes
                self._out_enq_total += p.nbytes
            else:
                run.append(p)
        if run:
            self._enqueue_bufs(run)
        if release is not None:
            for lease in release if isinstance(release, list) else (release,):
                self._out_releases.append((self._out_enq_total, lease))
        self.flush_out()

    def _enqueue_bufs(self, parts) -> None:
        for m in _gather_parts(parts):
            self.out.append(m)
            self.out_bytes += m.nbytes
            self._out_enq_total += m.nbytes

    def _send_control(self, b: bytes) -> None:
        self.send_parts([b])

    def flush_out(self) -> None:
        if self.closed:
            return
        try:
            while self.out:
                if type(self.out[0]) is FileSpan:
                    self._pump_span(self.out[0])
                    continue
                if self._sendmsg is not None:
                    bufs = []
                    for m in self.out:
                        if type(m) is FileSpan:
                            break  # spans splice alone, next loop pass
                        bufs.append(m)
                        if len(bufs) >= _SENDMSG_IOV:
                            break
                    sent = self._sendmsg(bufs)
                else:  # platform fallback: one part per send
                    sent = self.sock.send(self.out[0])
                if sent <= 0:
                    raise ConnectionError("peer closed during send")
                self.out_bytes -= sent
                while sent:
                    m = self.out[0]
                    if sent >= m.nbytes:
                        sent -= m.nbytes
                        self.out.popleft()
                    else:
                        self.out[0] = m[sent:]
                        sent = 0
        except (BlockingIOError, InterruptedError):
            pass
        # release compressed staging leases whose bytes have fully left
        sent_total = self._out_enq_total - self.out_bytes
        while self._out_releases and self._out_releases[0][0] <= sent_total:
            self._out_releases.popleft()[1].release()
        if not self.out and self.closing:
            self.loop.kill_conn(self, None, requeue=False)
            return
        if not self.out and self._migration is not None:
            # queued response bytes have fully left: the deferred
            # worker handoff can ship the fd now
            self.loop._try_migrate(self)
            return
        self._set_interest(write=bool(self.out))

    def _pump_span(self, span) -> None:
        """Move the head FileSpan's bytes file->socket with
        ``os.sendfile`` — the payload never enters the interpreter. On
        a non-blocking socket sendfile returns short or raises
        BlockingIOError (caught by flush_out, like a short sendmsg); a
        can't-splice-here errno downgrades THIS span (and this
        connection) to the sendmsg path by materializing the remaining
        bytes in place — degrade, never die."""
        try:
            sent = os.sendfile(
                self.sock.fileno(), span.fileno(), span.pos, span.nbytes
            )
        except (BlockingIOError, InterruptedError):
            raise
        except OSError as e:
            if _splice_fallback_errno(e):
                self._no_splice = True
                SPLICE.note_fallback(f"sendfile_errno_{e.errno}")
                self.out[0] = memoryview(span.materialize())
                return
            raise ConnectionError(f"sendfile failed: {e!r}") from e
        if sent <= 0:
            raise ConnectionError("peer closed during sendfile")
        self.out_bytes -= sent
        SPLICE.note_sendfile(sent)
        if sent >= span.nbytes:
            self.out.popleft()
            SPLICE.note_frame()
        else:
            span.advance(sent)

    # -- selector interest ------------------------------------------------
    def _set_interest(self, read: Optional[bool] = None, write: Optional[bool] = None) -> None:
        if read is not None:
            self._want_read = read
        if write is not None:
            self._want_write = write
        mask = (selectors.EVENT_READ if self._want_read else 0) | (
            selectors.EVENT_WRITE if self._want_write else 0
        )
        if mask == self._mask or self.closed:
            return
        sel = self.loop._sel
        if self._mask == 0:
            sel.register(self.sock, mask, self)
        elif mask == 0:
            sel.unregister(self.sock)
        else:
            sel.modify(self.sock, mask, self)
        self._mask = mask

    # -- deferred ops -----------------------------------------------------
    def park(self, kind: str, **state) -> None:
        self.pending = dict(state, kind=kind)
        self.op_gen += 1

    def unpark(self) -> None:
        self.pending = None
        self.op_gen += 1
        self._await_op()
        self._set_interest(read=True)

    # -- opcode dispatch --------------------------------------------------
    def _ack_in_flight(self) -> None:
        """The implicit-ACK point: a durable queue advances (and
        persists) its committed floor here; a replay cursor commits its
        group's position. Memory-only queues no-op — delivery semantics
        are unchanged where there is no log."""
        if self.in_flight:
            ack = getattr(self.queue, "ack_delivered", None)
            if ack is not None:
                ack(self.in_flight)
        if self.replay is not None:
            self.replay.commit()

    def _on_op(self) -> None:
        op = self._hdr[0]
        # previous response fully read by the peer (it can only send the
        # next request after reading the last response) — implicit ACK
        self._ack_in_flight()
        self.in_flight = []
        if self.replica is not None:
            # a replica-link connection carries only appends and BYE
            if op == _OP_REPL_APPEND[0]:
                self._expect(20, self._va_hdr)
                return
            if op == _OP_BYE[0]:
                self._begin_close()
                return
            raise ConnectionError(
                f"bad opcode {op:#04x} on replica connection"
            )
        if self.stream is not None:
            # a streamed connection carries only acks and BYE upstream
            if op == _OP_STREAM_ACK[0]:
                self._expect(8, self._on_stream_ack)
                return
            if op == _OP_BYE[0]:
                self._finish_stream(clean=True)
                self._begin_close()
                return
            raise ConnectionError(
                f"bad opcode {op:#04x} on streamed connection"
            )
        wctx = self.srv.worker_ctx
        if (
            wctx is not None
            and self.queue is self.srv.queue
            and wctx.worker_id != wctx.default_owner
            and op not in _WORKER_LOCAL_OPS
        ):
            # this worker does not own the DEFAULT queue and the op
            # touches it: ship the connection to the owner. Exactly one
            # byte (the opcode) has been consumed — it rides in the
            # context; anything the client pipelined behind it is still
            # in the kernel socket buffer and travels with the fd.
            self.loop.migrate_conn(
                self, wctx.default_owner, {"kind": "op", "op": op}
            )
            return
        name = _OPS.get(op)
        if name is None:
            self._send_control(_ST_ERR)
            self._begin_close()
            return
        getattr(self, name)()

    def _begin_close(self) -> None:
        """Clean close: flush any queued response bytes, then close
        without redelivery (the peer said goodbye / protocol-erred)."""
        if self.out:
            self.closing = True
            self._set_interest(read=False, write=True)
        else:
            self.loop.kill_conn(self, None, requeue=False)

    # -- responses --------------------------------------------------------
    def _encode_item_parts(self, item):
        """codec.encode_for_wire under this connection's negotiated
        codec — the returned staging lease is handed to
        send_parts(release=...) so it outlives the queued bytes. See
        the helper for the lease/pass-through contract.

        A :class:`SpilledRecord` (lazy durable spill, ISSUE 17) short-
        circuits on an uncompressed connection: its on-disk payload IS
        the raw wire payload, so the response becomes a FileSpan the
        flush pump moves with sendfile — zero Python payload bytes.
        Compressed connections (the span can't be compressed kernel-
        side) and splice-refusing sockets materialize, which is exactly
        the pre-ISSUE-17 eager spill read."""
        if type(item) is SpilledRecord:
            if (
                self.codec is None
                and not self._no_splice
                and _sendfile_capable()
            ):
                span = item.payload_span()
                if span is not None:
                    f, pos, nbytes = span
                    return [FileSpan(f, pos, nbytes)], None
                # offset aged out of retention between unbox and send —
                # can't happen while the floor pin holds, but degrade
                # loudly rather than die if the contract ever breaks
                SPLICE.note_fallback("span_unretained")
            item = item.materialize()
        return _wire_encode(item, self.codec, self.srv._pool)

    def _respond_item(self, item) -> None:
        parts, clease = self._encode_item_parts(item)
        head = _ST_OK + struct.pack("<I", _parts_nbytes(parts))
        self.send_parts([head, *parts], release=clease)

    def _respond_batch(self, items) -> None:
        self.in_flight = list(items)
        parts: List[Any] = [_ST_OK, struct.pack("<I", len(self.in_flight))]
        leases: List[Any] = []
        try:
            for item in self.in_flight:
                item_parts, clease = self._encode_item_parts(item)
                if clease is not None:
                    leases.append(clease)
                parts.append(struct.pack("<I", _parts_nbytes(item_parts)))
                parts.extend(item_parts)
        except BaseException:
            # a mid-loop failure (allocation under pressure) must not
            # strand earlier items' staging leases: nothing was queued
            # yet, so ownership is still ours
            for clease in leases:
                clease.release()
            raise
        t_send0 = time.monotonic() if TRACER.enabled else 0.0
        self.send_parts(parts, release=leases or None)
        if TRACER.enabled:
            _emit_relay_spans(self.in_flight, t_send0)

    def _take_item(self):
        """Decode the just-received payload zero-copy off its lease.
        ``lazy=True``: a COMPRESSED frame is validated (corruption
        still dies here, where the requeue contract runs) but not
        decompressed — the relay's common case re-sends the cached
        compressed bytes verbatim and never pays codec CPU; panels
        inflate on first touch for every other destination."""
        lease = self._lease
        self._lease = None
        try:
            return _decode(lease.mv, lease=lease, lazy=True)
        except BaseException:
            lease.release()
            raise

    # -- opcode handlers --------------------------------------------------
    def _op_put(self) -> None:
        self._expect(4, self._put_hdr)

    def _put_hdr(self) -> None:
        (n,) = struct.unpack_from("<I", self._hdr)
        self._expect_payload(n, self._put_payload)

    def _try_put(self, item):
        """``queue.put`` with refusals surfaced as ANSWERS: a queue
        exception beyond TransportClosed (e.g. a durable queue rejecting
        a record larger than segment_bytes, or a disk fault) must error
        THIS request — killing the connection instead would make a
        windowed producer resend the identical poison record on every
        reconnect until its retries exhaust with a misleading
        connection-death error. Returns ``(ok, offset)`` — ``offset`` is
        the durable log offset (None for memory queues), the replicated
        ack floor's gate key — or ``(None, None)`` when a refusal was
        already answered."""
        try:
            put_offset = getattr(self.queue, "put_offset", None)
            if put_offset is not None:
                return put_offset(item)
            return self.queue.put(item), None
        except TransportClosed:
            self._send_control(_ST_CLOSED)
        except Exception:  # noqa: BLE001 — answer, don't kill the conn
            self._send_control(_ST_ERR)
        return None, None

    def _answer_put(self, parts, offset, parked: bool = False) -> None:
        """Send a successful put's reply — or HOLD it until the queue's
        replication follower has logged ``offset`` (the replicated ack
        floor, ISSUE 11: a frame is ACKed to the producer only once the
        follower has it; the sender's ack-advance pokes the loop and
        :meth:`EventLoop._pump_rack` releases the reply). ``parked``:
        the caller is resolving an existing deferred op (pump path), so
        an immediate answer must unpark instead of re-arming reads."""
        repl = self.loop.repl_sender(self.queue)
        if offset is not None and repl is not None and not repl.reached(offset):
            self.pending = {"kind": "RA", "parts": parts, "offset": offset}
            self.op_gen += 1
            self.loop.add_rack_waiter(self)
            return
        self.send_parts(parts)
        if parked:
            self.unpark()
        else:
            self._await_op()

    def _put_payload(self) -> None:
        item = self._take_item()
        if TRACER.enabled:
            _stamp_relay_arrival(item)
        if self.srv._draining:
            self._send_control(_ST_CLOSED)
        else:
            ok, offset = self._try_put(item)
            if ok:
                self.loop.queue_touched(self.queue)
                self._answer_put([_ST_OK], offset)
                return
            if ok is not None:
                self._send_control(_ST_NO)
        self._await_op()

    def _op_get(self) -> None:
        try:
            if self.replay is not None:
                items = self.replay.next_batch(1)
                item = items[0] if items else EMPTY
            else:
                item = self.queue.get()
        except TransportClosed:
            self._send_control(_ST_CLOSED)
        else:
            if item is EMPTY:
                self._send_control(_ST_NO)
            else:
                self.in_flight = [item]  # held until the next opcode
                t_send0 = time.monotonic() if TRACER.enabled else 0.0
                self._respond_item(item)
                if TRACER.enabled:
                    _emit_relay_spans(self.in_flight, t_send0)
                self.loop.queue_touched(self.queue)
        self._await_op()

    def _op_get_batch(self) -> None:
        self._expect(4, self._gb_hdr)

    def _gb_hdr(self) -> None:
        (max_items,) = struct.unpack_from("<I", self._hdr)
        try:
            items = self._read_batch(min(max_items, 4096))
        except TransportClosed:
            self._send_control(_ST_CLOSED)
        else:
            self._respond_batch(items)
            if items:
                self.loop.queue_touched(self.queue)
        self._await_op()

    def _read_batch(self, max_items: int) -> List[Any]:
        """Non-blocking read: the replay cursor when subscribed, the
        live queue otherwise."""
        if self.replay is not None:
            return self.replay.next_batch(max_items)
        return self.queue.get_batch(max_items, timeout=0.0)

    def _op_get_batch_wait(self) -> None:
        self._expect(8, self._gbw_hdr)

    def _gbw_hdr(self) -> None:
        max_items, wait_ms = struct.unpack_from("<II", self._hdr)
        max_items = min(max_items, 4096)
        wait_s = min(wait_ms / 1000.0, _SERVER_WAIT_CAP_S)
        try:
            items = self._read_batch(max_items)
        except TransportClosed:
            self._send_control(_ST_CLOSED)
            self._await_op()
            return
        if items or wait_s <= 0:
            self._respond_batch(items)
            if items:
                self.loop.queue_touched(self.queue)
            self._await_op()
            return
        # empty queue: the wait becomes timer + waiter state, not a
        # parked thread — served by the pump or expired by the timer
        self.park("D", max_items=max_items)
        self.loop.add_get_waiter(self, time.monotonic() + wait_s)

    def _op_put_wait(self) -> None:
        self._expect(8, self._pw_hdr)

    def _pw_hdr(self) -> None:
        wait_ms, n = struct.unpack_from("<II", self._hdr)
        self._pw_wait_s = min(wait_ms / 1000.0, _SERVER_WAIT_CAP_S)
        self._expect_payload(n, self._pw_payload)

    def _pw_payload(self) -> None:
        item = self._take_item()
        if TRACER.enabled:
            _stamp_relay_arrival(item)
        if self.srv._draining:
            self._send_control(_ST_CLOSED)
            self._await_op()
            return
        ok, offset = self._try_put(item)
        if ok is None:
            self._await_op()
            return
        if ok:
            self.loop.queue_touched(self.queue)
            self._answer_put([_ST_OK], offset)
            return
        if self._pw_wait_s <= 0:
            self._send_control(_ST_NO)
            self._await_op()
            return
        self.park("U", item=item)
        self.loop.add_put_waiter(self, time.monotonic() + self._pw_wait_s)

    def _op_put_seq(self) -> None:
        self._expect(12, self._ws_hdr)

    def _ws_hdr(self) -> None:
        seq, n = struct.unpack_from("<QI", self._hdr)
        self._w_seq = seq
        self._expect_payload(n, self._ws_payload)

    def _ws_payload(self) -> None:
        item = self._take_item()
        if TRACER.enabled:
            _stamp_relay_arrival(item)
        if self.srv._draining:
            self._send_control(_ST_CLOSED)
            self._await_op()
            return
        ok, offset = self._try_put(item)
        if ok is None:
            self._await_op()
            return
        if ok:
            self.loop.queue_touched(self.queue)
            self._answer_put(
                [_ST_OK + struct.pack("<Q", self._w_seq)], offset
            )
            return
        # backpressure: the ack is delayed until space frees — deferred
        # state with NO deadline (that delay IS the backpressure signal)
        self.park("W", item=item, seq=self._w_seq)
        self.loop.add_put_waiter(self, None)

    def _op_put_batch(self) -> None:
        self._expect(4, self._qb_count)

    def _qb_count(self) -> None:
        (count,) = struct.unpack_from("<I", self._hdr)
        self._qb_remaining = count
        self._qb_items = []
        self._qb_next()

    def _qb_next(self) -> None:
        if self._qb_remaining <= 0:
            self._qb_finish()
            return
        self._qb_remaining -= 1
        self._expect(4, self._qb_len)

    def _qb_len(self) -> None:
        (n,) = struct.unpack_from("<I", self._hdr)
        self._expect_payload(n, self._qb_payload)

    def _qb_payload(self) -> None:
        self._qb_items.append(self._take_item())
        self._qb_next()

    def _qb_finish(self) -> None:
        batch, self._qb_items = self._qb_items, []
        if TRACER.enabled:
            for item in batch:
                _stamp_relay_arrival(item)
        if self.srv._draining:
            self._send_control(_ST_CLOSED)
            self._await_op()
            return
        accepted = 0
        high = None  # highest durable offset (offsets are monotonic)
        for item in batch:
            ok, offset = self._try_put(item)
            if ok is None:  # refusal already answered ('X'/'E')
                self._await_op()
                return
            if not ok:
                break  # full: accepted prefix only (FIFO)
            accepted += 1
            if offset is not None:
                high = offset
        if accepted:
            self.loop.queue_touched(self.queue)
        self._answer_put([_ST_OK + struct.pack("<I", accepted)], high)

    def _op_stream(self) -> None:
        self._expect(4, self._stream_hdr)

    def _stream_hdr(self) -> None:
        (window,) = struct.unpack_from("<I", self._hdr)
        if self.replay is not None:
            # replay is pull-mode by design: stream seqs and cursor
            # offsets would need a second mapping for commit-on-ack —
            # rejected loudly rather than committed wrongly
            raise ConnectionError("stream subscribe on a replay connection")
        window = max(1, min(int(window), 4096))
        self.stream = _StreamState(window)
        STREAM.opened(window)
        FLIGHT.record("stream_open", port=self.srv.port, window=window)
        self.loop.add_stream(self)
        self._await_op()  # from here: only 'K'/'F' upstream

    def _on_stream_ack(self) -> None:
        (seq,) = struct.unpack_from("<Q", self._hdr)
        st = self.stream
        if seq > st.acked:
            st.acked = seq
            STREAM.acked_msg()
        acked_items = []
        while st.unacked and st.unacked[0][0] <= st.acked:
            # credit returned: lease may free
            acked_items.append(st.unacked.popleft()[1])
        if acked_items:
            STREAM.pruned(len(acked_items))
            # the stream's explicit cumulative ack is a durable queue's
            # commit point, same as the implicit next-opcode ACK
            ack = getattr(self.queue, "ack_delivered", None)
            if ack is not None:
                ack(acked_items)
        self.loop.queue_touched(self.queue)  # new credits: pump may push
        self._await_op()

    def push_stream_items(self, items) -> None:
        st = self.stream
        t_send0 = time.monotonic() if TRACER.enabled else 0.0
        parts: List[Any] = []
        leases: List[Any] = []
        try:
            for item in items:
                st.seq += 1
                st.unacked.append((st.seq, item))
                item_parts, clease = self._encode_item_parts(item)
                if clease is not None:
                    leases.append(clease)
                parts.append(
                    _ST_OK
                    + struct.pack("<QI", st.seq, _parts_nbytes(item_parts))
                )
                parts.extend(item_parts)
        except BaseException:
            for clease in leases:  # nothing queued yet: still ours
                clease.release()
            raise
        self.send_parts(parts, release=leases or None)
        STREAM.pushed(len(items))
        if TRACER.enabled:
            _emit_relay_spans(items, t_send0)

    def _finish_stream(self, clean: bool) -> None:
        """Stream teardown bookkeeping: prune what the final cumulative
        ack covered, redeliver the rest (requeue at head) unless the
        queue itself closed — exactly the threaded ``_serve_stream``
        finally-block."""
        st, self.stream = self.stream, None
        if st is None:
            return
        acked_items = []
        while st.unacked and st.unacked[0][0] <= st.acked:
            acked_items.append(st.unacked.popleft()[1])
        if acked_items:
            STREAM.pruned(len(acked_items))
            ack = getattr(self.queue, "ack_delivered", None)
            if ack is not None:  # final cumulative ack commits too
                ack(acked_items)
        lost = [item for (_s, item) in st.unacked]
        st.unacked.clear()
        if lost:
            STREAM.pruned(len(lost))
            if not st.queue_closed:
                STREAM.redelivered_n(len(lost))
                FLIGHT.record(
                    "stream_redelivery", count=len(lost), clean_bye=clean
                )
                self.loop.requeue_items(self.queue, lost)
        STREAM.closed(st.window)

    def _op_size(self) -> None:
        try:
            n = self.queue.size()
        except TransportClosed:
            self._send_control(_ST_CLOSED)
        else:
            self.send_parts([_ST_OK + struct.pack("<I", n)])
        self._await_op()

    def _op_stats(self) -> None:
        payload = json.dumps(_queue_stats_payload(self.queue)).encode()
        self.send_parts([_ST_OK + struct.pack("<I", len(payload)), payload])
        self._await_op()

    def _op_anchor(self) -> None:
        self._expect(16, self._anchor_reply)

    def _anchor_reply(self) -> None:
        # client wall+mono read for RTT symmetry; answer with our pair
        self.send_parts(
            [_ST_OK + struct.pack("<dd", time.time(), time.monotonic())]
        )
        self._await_op()

    def _op_close(self) -> None:
        try:
            self.queue.close()
        except TransportClosed:
            self._send_control(_ST_CLOSED)
        else:
            self._send_control(_ST_OK)
            self.loop.queue_touched(self.queue)
        self._await_op()

    def _op_bye(self) -> None:
        # clean goodbye: the previous response is ACKed (in_flight was
        # already cleared when this opcode arrived)
        self._begin_close()

    def _op_cluster(self) -> None:
        self._expect(4, self._cluster_len)

    def _cluster_len(self) -> None:
        (n,) = struct.unpack_from("<I", self._hdr)
        if n > 1 << 20:  # control-plane JSON: a MB is already absurd
            raise ConnectionError(f"cluster RPC payload {n} bytes")
        # dedicated exact-size buffer: group RPCs are rare control plane
        self._open_buf = bytearray(n)
        self._arm(memoryview(self._open_buf), self._cluster_finish)

    def _cluster_finish(self) -> None:
        if self._open_buf[:13] == b'{"op": "ping"':
            # link-rate probe fast path (ISSUE 15, --wire_codec auto):
            # the client times its padded REQUEST through the link, so
            # the answer must cost O(1) — parsing a 640 KB pad here
            # would bill codec-decision bandwidth for JSON decode time
            # and make every fast LAN look slow
            payload = json.dumps(
                {"ok": True, "nbytes": len(self._open_buf)}
            ).encode()
            self.send_parts(
                [_ST_OK + struct.pack("<I", len(payload)), payload]
            )
            self._await_op()
            return
        try:
            req = json.loads(self._open_buf.decode())
            if req.get("op") == "metrics":
                # federation pull (ISSUE 13): the whole metrics-registry
                # snapshot, host-tagged, over the EXISTING control
                # surface — no new opcode, and a pre-ISSUE-13 peer
                # answers {"ok": False, "error": "missing group"}, which
                # the collector surfaces as a loudly-degraded peer (the
                # 'Z' old-peer precedent)
                resp = _metrics_rpc_payload()
            elif req.get("op") == "ping":
                # non-prefix ping spellings still answer (the fast path
                # above handles the probe's canonical byte layout)
                resp = {"ok": True, "nbytes": len(self._open_buf)}
            else:
                resp = self.srv.groups.handle(req)
        except Exception as e:  # noqa: BLE001 — a bad RPC must not kill the loop
            resp = {"ok": False, "error": repr(e)}
        payload = json.dumps(resp).encode()
        self.send_parts([_ST_OK + struct.pack("<I", len(payload)), payload])
        self._await_op()

    # -- durable log opcodes ('R'/'J', ISSUE 8) ---------------------------
    def _op_replay(self) -> None:
        self._expect(10, self._replay_hdr)

    def _replay_hdr(self) -> None:
        self._r_from, glen = struct.unpack_from("<QH", self._hdr)
        self._open_buf = bytearray(glen)
        self._arm(memoryview(self._open_buf), self._replay_finish)

    def _replay_finish(self) -> None:
        group = self._open_buf.decode() or "replay"
        open_replay = getattr(self.queue, "open_replay", None)
        if open_replay is None:  # memory-only queue: no retained range
            self._send_control(_ST_NO)
            self._await_op()
            return
        self.replay = open_replay(group, self._r_from)
        self.send_parts([
            _ST_OK
            + struct.pack(
                "<QQ", self.replay.position, self.replay.log.next_offset
            )
        ])
        self._await_op()

    def _op_commit(self) -> None:
        self._expect(10, self._commit_hdr)

    def _commit_hdr(self) -> None:
        self._r_from, glen = struct.unpack_from("<QH", self._hdr)
        self._open_buf = bytearray(glen)
        self._arm(memoryview(self._open_buf), self._commit_finish)

    def _commit_finish(self) -> None:
        offset, group = self._r_from, self._open_buf.decode()
        if self.replay is not None:
            if offset == COMMIT_DELIVERED:
                self.replay.commit()
            else:
                self.replay.commit(through=offset)
            self._send_control(_ST_OK)
            self._await_op()
            return
        commit = getattr(self.queue, "commit_offset", None)
        if commit is None or not group or offset == COMMIT_DELIVERED:
            # no log / no named group / the delivered sentinel without a
            # replay cursor: nothing to commit against
            self._send_control(_ST_NO)
        else:
            commit(offset, group)
            self._send_control(_ST_OK)
        self._await_op()

    # -- wire-compression negotiation ('Z', ISSUE 9) ----------------------
    def _op_codec(self) -> None:
        self._expect(2, self._codec_len)

    def _codec_len(self) -> None:
        (n,) = struct.unpack_from("<H", self._hdr)
        if n > 4096:  # a codec-name list is tens of bytes
            raise ConnectionError(f"codec negotiation payload {n} bytes")
        self._open_buf = bytearray(n)
        self._arm(memoryview(self._open_buf), self._codec_finish)

    def _codec_finish(self) -> None:
        # the 'Z' advert mixes codec NAMES with capability FIELDS
        # (key=value, ISSUE 12); fields are peeled off here and the
        # codec picker sees only names — a field it predates is simply
        # an unknown name to an older picker, which skips it (that is
        # what makes the hello rideable on the existing exchange)
        names = []
        for entry in self._open_buf.decode().split(","):
            entry = entry.strip()
            key, sep, value = entry.partition("=")
            if not sep:
                names.append(entry)
                continue
            if key == "tenant":
                tenant, _, w = value.partition(":")
                self.tenant = tenant or _TENANT_DEFAULT
                try:
                    self.weight = max(
                        1, min(_TENANT_WEIGHT_MAX, int(w))
                    ) if w else 1
                except ValueError:
                    self.weight = 1
                FLIGHT.record(
                    "tenant_hello", port=self.srv.port,
                    tenant=self.tenant, weight=self.weight,
                )
            # unknown capability keys are ignored: a newer client must
            # degrade gracefully against this server, not die
        chosen = negotiate_codec(names)
        self.codec = chosen
        name = chosen.name if chosen is not None else CODEC_NONE
        CODEC_STATS.negotiated(name)
        FLIGHT.record(
            "codec_negotiated", port=self.srv.port, codec=name, server=True
        )
        nb = name.encode()
        self.send_parts([_ST_OK + struct.pack("<H", len(nb)) + nb])
        self._await_op()

    # -- replication opcodes ('H'/'V'/'Y', ISSUE 11) ----------------------
    def _op_repl_open(self) -> None:
        self._expect(2, self._ro_ns_len)

    def _ro_ns_len(self) -> None:
        (n,) = struct.unpack_from("<H", self._hdr)
        self._open_buf = bytearray(n)
        self._arm(memoryview(self._open_buf), self._ro_ns_done)

    def _ro_ns_done(self) -> None:
        self._open_ns = self._open_buf.decode()
        self._expect(2, self._ro_nm_len)

    def _ro_nm_len(self) -> None:
        (n,) = struct.unpack_from("<H", self._hdr)
        self._open_buf = bytearray(n)
        self._arm(memoryview(self._open_buf), self._ro_finish)

    def _ro_finish(self) -> None:
        nm = self._open_buf.decode()
        repl = self.srv.replication
        entry = (
            repl.replica_open(self._open_ns, nm) if repl is not None else None
        )
        if entry is None:
            # cannot host this replica: no replication manager, the
            # queue is mounted LIVE on this server, or the replica was
            # already promoted — the fencing answer a zombie owner must
            # treat as "stop replicating"
            self._send_control(_ST_NO)
        else:
            self.replica = entry
            FLIGHT.record(
                "replica_subscribe", port=self.srv.port,
                queue=f"{self._open_ns}/{nm}", tail=entry.log.next_offset,
            )
            self.send_parts(
                [_ST_OK + struct.pack("<Q", entry.log.next_offset)]
            )
        self._await_op()

    def _va_hdr(self) -> None:
        self._v_off, self._v_floor = struct.unpack_from("<QQ", self._hdr)
        (n,) = struct.unpack_from("<I", self._hdr, 16)
        self._expect_payload(n, self._va_payload)

    def _va_payload(self) -> None:
        item = self._take_item()
        try:
            ok = self.srv.replication.replica_append(
                self.replica, self._v_off, self._v_floor, item
            )
        except Exception:  # noqa: BLE001 — a replica disk fault answers
            ok = False  # 'E' (breadcrumbed in storage); the loop lives
        finally:
            release = getattr(item, "release", None)
            if release is not None:
                release()  # the record is in the mmap now (or refused)
        if ok:
            self.send_parts([_ST_OK + struct.pack("<Q", self._v_off)])
        else:
            self._send_control(_ST_ERR)
        self._await_op()

    def _op_promote(self) -> None:
        self._expect(2, self._pr_ns_len)

    def _pr_ns_len(self) -> None:
        (n,) = struct.unpack_from("<H", self._hdr)
        self._open_buf = bytearray(n)
        self._arm(memoryview(self._open_buf), self._pr_ns_done)

    def _pr_ns_done(self) -> None:
        self._open_ns = self._open_buf.decode()
        self._expect(2, self._pr_nm_len)

    def _pr_nm_len(self) -> None:
        (n,) = struct.unpack_from("<H", self._hdr)
        self._open_buf = bytearray(n)
        self._arm(memoryview(self._open_buf), self._pr_finish)

    def _pr_finish(self) -> None:
        nm = self._open_buf.decode()
        repl = self.srv.replication
        rng = repl.promote(self._open_ns, nm) if repl is not None else None
        if rng is None:
            self._send_control(_ST_NO)  # no replica here: queue starts empty
        else:
            self.send_parts([_ST_OK + struct.pack("<QQ", rng[0], rng[1])])
        self._await_op()

    def _op_open(self) -> None:
        self._expect(2, self._open_ns_len)

    def _open_ns_len(self) -> None:
        (ns_len,) = struct.unpack_from("<H", self._hdr)
        # name fields are u16-length control strings; a dedicated exact-
        # size buffer (OPEN runs once per connection, off the hot path)
        self._open_buf = bytearray(ns_len)
        self._arm(memoryview(self._open_buf), self._open_ns_done)

    def _open_ns_done(self) -> None:
        self._open_ns = self._open_buf.decode()
        self._expect(2, self._open_nm_len)

    def _open_nm_len(self) -> None:
        (nm_len,) = struct.unpack_from("<H", self._hdr)
        self._open_buf = bytearray(nm_len)
        self._arm(memoryview(self._open_buf), self._open_nm_done)

    def _open_nm_done(self) -> None:
        self._open_nm = self._open_buf.decode()
        self._expect(4, self._open_finish)

    def _open_finish(self) -> None:
        (maxsize,) = struct.unpack_from("<I", self._hdr)
        wctx = self.srv.worker_ctx
        if wctx is not None:
            owner = wctx.owner_of(self._open_ns, self._open_nm)
            if owner != wctx.worker_id:
                # the named queue's state lives on exactly one worker
                # (rendezvous-pinned): ship the connection there; the
                # adopter performs the open and answers the client
                self.loop.migrate_conn(self, owner, {
                    "kind": "open",
                    "ns": self._open_ns,
                    "nm": self._open_nm,
                    "maxsize": maxsize,
                })
                return
        self.queue = self.srv.open_named(
            self._open_ns, self._open_nm, maxsize or None
        )
        self._send_control(_ST_OK)
        self._await_op()


_OPS: Dict[int, str] = {
    _OP_PUT[0]: "_op_put",
    _OP_GET[0]: "_op_get",
    _OP_SIZE[0]: "_op_size",
    _OP_CLOSE[0]: "_op_close",
    _OP_GET_BATCH[0]: "_op_get_batch",
    _OP_GET_BATCH_WAIT[0]: "_op_get_batch_wait",
    _OP_PUT_BATCH[0]: "_op_put_batch",
    _OP_PUT_WAIT[0]: "_op_put_wait",
    _OP_PUT_SEQ[0]: "_op_put_seq",
    _OP_STREAM[0]: "_op_stream",
    _OP_OPEN[0]: "_op_open",
    _OP_STATS[0]: "_op_stats",
    _OP_ANCHOR[0]: "_op_anchor",
    _OP_CLUSTER[0]: "_op_cluster",
    _OP_REPLAY[0]: "_op_replay",
    _OP_COMMIT[0]: "_op_commit",
    _OP_CODEC[0]: "_op_codec",
    _OP_REPL_OPEN[0]: "_op_repl_open",
    _OP_PROMOTE[0]: "_op_promote",
    _OP_BYE[0]: "_op_bye",
}

#: ops any worker serves LOCALLY even when it does not own the default
#: queue: codec/tenant hello, cluster metadata + anchors (per-worker
#: answers by design), replica-link setup (refused with --workers at the
#: CLI), BYE. OPEN routes later, at _open_finish, once the name is read.
#: Derived from the dispatch table by handler name so this set is not a
#: second send-side reference to the opcode constants (the wire-protocol
#: lint counts those as senders).
_WORKER_LOCAL_OPS = frozenset(
    op for op, handler in _OPS.items()
    if handler in (
        "_op_open", "_op_codec", "_op_cluster", "_op_anchor",
        "_op_repl_open", "_op_promote", "_op_bye",
    )
)


class EventLoop:
    """The one loop: accepts, reads, writes, fires bounded-wait timers
    and pumps queue waiters — for a :class:`~psana_ray_tpu.transport.
    tcp.TcpQueueServer` constructed with ``mode="evloop"``."""

    def __init__(self, server):
        self._srv = server
        self._sel = selectors.DefaultSelector()
        self._conns: set = set()
        self._queues: Dict[int, _QueueState] = {}
        self._timers: List[tuple] = []  # heap: (deadline, tie, conn, gen)
        self._timer_tie = 0
        # waker: listener callbacks / shutdown poke this pipe so the
        # selector wakes immediately instead of at the next tick
        self._waker_r, self._waker_w = socket.socketpair()
        self._waker_r.setblocking(False)
        self._waker_w.setblocking(False)
        self._waker_buf = bytearray(512)
        self._waker_mv = memoryview(self._waker_buf)
        self._ACCEPT = object()
        self._WAKER = object()
        self._ADOPT = object()
        self._loop_tid: Optional[int] = None

    # -- cross-thread pokes ----------------------------------------------
    def wake(self) -> None:
        # The loop's own queue ops fire the RingBuffer listeners too —
        # a self-poke would cost two syscalls plus a spurious zero-wait
        # select pass PER FRAME. The loop is by definition awake when it
        # is the caller, and _pump_all runs at the end of every pass, so
        # only other threads need the pipe.
        if threading.get_ident() == self._loop_tid:
            return
        try:
            self._waker_w.send(b"w")
        except (BlockingIOError, InterruptedError, OSError):
            pass  # pipe full = a wakeup is already pending; closed = exiting

    # -- queue-state plumbing --------------------------------------------
    def _qs(self, queue) -> _QueueState:
        qs = self._queues.get(id(queue))
        if qs is None:
            qs = _QueueState(queue)
            self._queues[id(queue)] = qs
            repl = getattr(self._srv, "replication", None)
            if repl is not None:
                # the queue's ReplicationSender (mounted at open_named
                # time, strictly before any connection binds) — the
                # replicated-ack-floor gate key
                qs.repl = repl.sender_for(queue)
            add = getattr(queue, "add_listener", None)
            if add is not None:
                try:
                    add(self.wake)
                    qs.listened = True
                    remove = getattr(queue, "remove_listener", None)
                    if remove is not None:
                        qs.unlisten = lambda: remove(self.wake)
                except Exception:
                    qs.listened = False
        return qs

    def queue_touched(self, queue) -> None:
        """An in-loop op changed this queue's state; the per-iteration
        pump pass will serve its waiters (this is just a cheap no-op
        hook kept for readability and future per-queue dirty tracking)."""

    def add_get_waiter(self, conn: _EvConn, deadline: Optional[float]) -> None:
        self._qs(conn.queue).get_waiters.append(conn)
        if deadline is not None:
            self._add_timer(deadline, conn)

    def add_put_waiter(self, conn: _EvConn, deadline: Optional[float]) -> None:
        self._qs(conn.queue).put_waiters.append(conn)
        if deadline is not None:
            self._add_timer(deadline, conn)

    def add_stream(self, conn: _EvConn) -> None:
        self._qs(conn.queue).get_waiters.append(conn)

    def add_rack_waiter(self, conn: _EvConn) -> None:
        """Park a producer whose reply waits on the replicated ack
        floor (pending kind "RA"); no deadline — the sender's degrade
        grace bounds the wait when the follower link is down."""
        self._qs(conn.queue).ra_waiters.append(conn)

    def repl_sender(self, queue):
        """The queue's ReplicationSender, or None when unreplicated."""
        return self._qs(queue).repl

    def add_liveness_probe(self, conn: _EvConn) -> None:
        """Re-check a parked, read-paused connection for EOF every
        PROBE_INTERVAL_S: re-arming read interest makes the next select
        pass run the MSG_PEEK probe again (which re-pauses and
        reschedules if the pipelined bytes are still waiting)."""
        self._add_timer(
            time.monotonic() + PROBE_INTERVAL_S, conn, kind="probe"
        )

    def _add_timer(self, deadline: float, conn: _EvConn, kind: str = "op") -> None:
        self._timer_tie += 1
        heapq.heappush(
            self._timers, (deadline, self._timer_tie, conn, conn.op_gen, kind)
        )

    # -- redelivery -------------------------------------------------------
    def requeue_items(self, queue, items) -> None:
        """Head-requeue via the shared recovery path. Backings without
        ``put_front`` (shm rings) take the timed-retry path, which can
        block — hand those to a short-lived helper thread so the loop
        never parks (connection death is rare; the thread is bounded by
        the recovery timeout and daemonic)."""
        if not items:
            return
        if getattr(queue, "put_front", None) is not None:
            self._srv._requeue(queue, items)  # non-blocking head placement
        else:
            threading.Thread(
                target=self._srv._requeue, args=(queue, items),
                daemon=True, name="tcp-evloop-requeue",
            ).start()

    # -- connection lifecycle --------------------------------------------
    def kill_conn(self, conn: _EvConn, cause, requeue: bool = True) -> None:
        if conn.closed:
            return
        conn.closed = True
        if conn._mask:
            try:
                self._sel.unregister(conn.sock)
            except (KeyError, ValueError, OSError):
                pass
            conn._mask = 0
        try:
            conn.sock.close()
        except OSError:
            pass
        self._conns.discard(conn)
        EVLOOP.conn_closed()
        if conn._lease is not None:  # payload died mid-read
            conn._lease.release()
            conn._lease = None
        while conn._out_releases:  # compressed parts died queued
            conn._out_releases.popleft()[1].release()
        # a parked 'U'/'W' item was never enqueued: drop it — the client
        # is dead (its windowed-put resend redelivers on reconnect), and
        # enqueueing now would stack a duplicate on top of that resend
        conn.pending = None
        conn._qb_items = []
        if conn.replay is not None:
            # cursor-based delivery: nothing to requeue — records the
            # dead client read but never committed simply redeliver when
            # its group re-opens at RESUME
            conn.in_flight = []
            conn.replay = None
        if requeue:
            if conn.in_flight:
                self.requeue_items(conn.queue, conn.in_flight)
                conn.in_flight = []
            conn._finish_stream(clean=False)
        else:
            if conn.stream is not None:
                conn._finish_stream(clean=True)

    # -- the loop ---------------------------------------------------------
    def run(self) -> None:
        srv = self._srv
        self._loop_tid = threading.get_ident()
        EVLOOP.ensure_registered()
        SPLICE.ensure_registered()
        try:
            srv._sock.setblocking(False)
        except OSError:
            return  # shutdown() closed the socket before we got here
        self._sel.register(srv._sock, selectors.EVENT_READ, self._ACCEPT)
        self._sel.register(self._waker_r, selectors.EVENT_READ, self._WAKER)
        if srv.worker_ctx is not None:
            # the adoption socket: sibling workers ship connections
            # whose queues this worker owns (ISSUE 17)
            self._sel.register(
                srv.worker_ctx.sock, selectors.EVENT_READ, self._ADOPT
            )
        # stage-tag the dispatch half of each pass so the continuous
        # profiler bills server CPU to "dispatch" (bound once here: the
        # loop body must not pay an import)
        from psana_ray_tpu.obs.profiling.stagetag import TAG_DISPATCH, TAG_UNTAGGED, set_stage

        try:
            while not srv._stop.is_set():
                t_sel = time.monotonic()
                events = self._sel.select(self._select_timeout())
                t0 = time.monotonic()
                set_stage(TAG_DISPATCH)
                for key, mask in events:
                    data = key.data
                    if data is self._ACCEPT:
                        self._accept()
                    elif data is self._WAKER:
                        self._drain_waker()
                    elif data is self._ADOPT:
                        self._adopt_conns()
                    else:
                        self._dispatch_conn(data, mask)
                self._fire_timers()
                self._pump_all()
                set_stage(TAG_UNTAGGED)
                EVLOOP.loop_pass(
                    (time.monotonic() - t0) * 1000.0, (t0 - t_sel) * 1000.0
                )
        finally:
            self._teardown()

    def _dispatch_conn(self, conn: _EvConn, mask: int) -> None:
        try:
            if mask & selectors.EVENT_WRITE:
                conn.flush_out()
            if mask & selectors.EVENT_READ and not conn.closed:
                conn.on_readable()
        except (ConnectionError, OSError) as e:
            self.kill_conn(conn, e)
        except Exception as e:  # noqa: BLE001 — one bad conn must not kill the loop
            self.kill_conn(conn, e)

    def _accept(self) -> None:
        srv = self._srv
        while True:
            try:
                sock, _ = srv._sock.accept()
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                return
            n_active = len(self._conns)
            if srv.max_conns and n_active >= srv.max_conns:
                EVLOOP.refused()
                try:
                    sock.setblocking(False)
                except OSError:
                    pass
                _refuse_conn(sock, srv.port, n_active, srv.max_conns)
                continue
            sock.setblocking(False)
            try:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
            conn = _EvConn(self, sock, srv)
            self._conns.add(conn)
            with srv._conns_lock:  # shutdown() parity sweep sees them too
                srv._conns = [c for c in srv._conns if c.fileno() != -1]
                srv._conns.append(sock)
            EVLOOP.conn_opened()
            conn._await_op()
            conn._set_interest(read=True)

    # -- multi-worker connection handoff (ISSUE 17) -----------------------
    def migrate_conn(self, conn: _EvConn, target: int, ctx: dict) -> None:
        """Begin shipping ``conn`` to worker ``target``: freeze reads,
        flush any queued response bytes, then send the fd + context
        over the adoption socket. The negotiated per-connection state
        (codec, tenant) rides in the context so the adopter rebuilds an
        indistinguishable connection."""
        ctx = dict(ctx)
        ctx["codec"] = conn.codec.name if conn.codec is not None else None
        if conn.tenant != _TENANT_DEFAULT or conn.weight != 1:
            ctx["tenant"] = conn.tenant
            ctx["weight"] = conn.weight
        conn._migration = {
            "target": int(target),
            "ctx": ctx,
            "deadline": time.monotonic() + MIGRATE_GRACE_S,
        }
        conn._set_interest(read=False)
        if conn.out:
            conn._set_interest(write=True)  # flush_out ships when drained
            return
        self._try_migrate(conn)

    def _try_migrate(self, conn: _EvConn) -> None:
        """One handoff attempt. A refusal (owner's adoption buffer full,
        owner mid-respawn) retries on a timer within the grace window;
        past it the connection dies WITH redelivery — the client's
        reconnect envelope plus durable re-expose make that lossless."""
        if conn.closed or conn._migration is None:
            return
        mig = conn._migration
        try:
            self._srv.worker_ctx.send_conn(
                mig["target"], conn.sock, mig["ctx"]
            )
        except OSError as e:
            now = time.monotonic()
            if now >= mig["deadline"]:
                FLIGHT.record(
                    "migrate_gave_up", target=mig["target"],
                    err=e.__class__.__name__,
                )
                self.kill_conn(conn, e, requeue=True)
                return
            if not mig.get("retried"):
                mig["retried"] = True
                FLIGHT.record(
                    "migrate_retry", target=mig["target"],
                    err=e.__class__.__name__,
                )
            self._add_timer(now + MIGRATE_RETRY_S, conn, kind="migrate")
            return
        FLIGHT.record(
            "conn_migrated", target=mig["target"],
            kind=mig["ctx"].get("kind"),
        )
        # the in-flight datagram holds its own reference to the fd;
        # closing our copy here is the normal no-redelivery teardown
        # (nothing is in flight at a migration point by construction)
        conn._migration = None
        self.kill_conn(conn, None, requeue=False)

    def _adopt_conns(self) -> None:
        """Drain the adoption socket: each datagram is a connection fd
        plus its context from a sibling worker. Rebuild the _EvConn
        exactly as _accept would, restore negotiated state, then either
        finish the routed OPEN or replay the consumed opcode byte."""
        srv = self._srv
        wctx = srv.worker_ctx
        for sock, ctx in wctx.recv_conns():
            conn = None
            try:
                sock.setblocking(False)
                try:
                    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                except OSError:
                    pass
                conn = _EvConn(self, sock, srv)
                name = ctx.get("codec")
                if name:
                    conn.codec = negotiate_codec([name])
                conn.tenant = ctx.get("tenant", _TENANT_DEFAULT)
                try:
                    conn.weight = max(1, int(ctx.get("weight", 1)))
                except (TypeError, ValueError):
                    conn.weight = 1
                self._conns.add(conn)
                with srv._conns_lock:  # shutdown() parity sweep
                    srv._conns = [c for c in srv._conns if c.fileno() != -1]
                    srv._conns.append(sock)
                EVLOOP.conn_opened()
                FLIGHT.record(
                    "conn_adopted", worker=wctx.worker_id,
                    kind=ctx.get("kind"),
                )
                if ctx.get("kind") == "open":
                    conn._open_ns = ctx.get("ns", "")
                    conn._open_nm = ctx.get("nm", "")
                    conn.queue = srv.open_named(
                        conn._open_ns, conn._open_nm,
                        ctx.get("maxsize") or None,
                    )
                    conn._send_control(_ST_OK)
                    conn._await_op()
                else:
                    # the migrating worker consumed exactly the opcode
                    # byte: replay it through the normal dispatcher (we
                    # own the target queue, so it cannot re-route)
                    conn._hdr[0] = int(ctx.get("op", 0))
                    conn._on_op()
                if not conn.closed:
                    conn._set_interest(read=True)
            except (ConnectionError, OSError) as e:
                if conn is not None:
                    self.kill_conn(conn, e)
                else:
                    try:
                        sock.close()
                    except OSError:
                        pass
            except Exception as e:  # noqa: BLE001 — one bad adoption must not kill the loop
                if conn is not None:
                    self.kill_conn(conn, e)

    def _drain_waker(self) -> None:
        while True:
            try:
                k = self._waker_r.recv_into(self._waker_mv)
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                return
            if k == 0:
                return

    def _select_timeout(self) -> float:
        now = time.monotonic()
        t = IDLE_TICK_S
        if self._timers:
            t = min(t, max(0.0, self._timers[0][0] - now))
        waiting = unlistened = False
        for qs in self._queues.values():
            if qs.get_waiters or qs.put_waiters or qs.ra_waiters:
                waiting = True
                if not qs.listened:
                    unlistened = True
                    break
        if unlistened:
            t = min(t, POLL_TICK_S)
        elif waiting:
            t = min(t, LISTENED_TICK_S)
        return t

    def _fire_timers(self) -> None:
        now = time.monotonic()
        while self._timers and self._timers[0][0] <= now:
            deadline, _tie, conn, gen, tkind = heapq.heappop(self._timers)
            if conn.closed:
                continue
            if tkind == "migrate":
                # worker-handoff retry: independent of pending/op_gen
                # (a migrating connection has neither) — must be
                # checked BEFORE the pending-is-None guard below
                if conn._migration is not None and not conn.out:
                    self._try_migrate(conn)
                continue
            if conn.pending is None or gen != conn.op_gen:
                continue  # already served / superseded
            if tkind == "probe":
                # parked with reads paused: re-arm read interest so the
                # next select pass re-runs the EOF probe
                conn._set_interest(read=True)
                continue
            EVLOOP.timer_lag((now - deadline) * 1000.0)
            kind = conn.pending["kind"]
            try:
                if kind == "D":
                    # one last non-blocking look, then the empty answer
                    try:
                        items = conn._read_batch(conn.pending["max_items"])
                    except TransportClosed:
                        conn._send_control(_ST_CLOSED)
                        conn.unpark()
                        continue
                    conn._respond_batch(items)
                    conn.unpark()
                elif kind == "U":
                    conn._send_control(_ST_NO)
                    conn.unpark()
                # "W" carries no deadline: backpressure, not timeout
            except (ConnectionError, OSError) as e:
                self.kill_conn(conn, e)

    # -- the pump: serve waiters when queue state may have changed --------
    def _pump_all(self) -> None:
        for qs in list(self._queues.values()):
            if not (qs.get_waiters or qs.put_waiters or qs.ra_waiters):
                continue
            try:
                progressed = True
                while progressed:
                    progressed = (
                        self._pump_get(qs)
                        | self._pump_put(qs)
                        | self._pump_rack(qs)
                    )
            except _QueueClosedSignal:
                self._queue_closed(qs)

    def _pump_get(self, qs: _QueueState) -> bool:
        did = False
        gw = qs.get_waiters
        if gw:
            # cheap emptiness probe first: the pump runs on every loop
            # pass, and an idle queue must cost a depth check, not a
            # get_batch per waiter per tick (round-trip-economy parity
            # with the threaded server's single blocking get_batch).
            # size() alone is not a liveness probe — RingBuffer.size()
            # answers 0 on a CLOSED queue — so check closed explicitly
            # (waiting streams must see 'X' promptly). Replay waiters
            # read the LOG cursor, not the queue, so an empty live
            # queue must not short-circuit past them.
            try:
                if getattr(qs.queue, "closed", False):
                    raise _QueueClosedSignal
                if not qs.queue.size() and not any(
                    c.replay is not None for c in gw if not c.closed
                ):
                    return False
            except TransportClosed:
                raise _QueueClosedSignal from None
        # WDRR round bookkeeping (ISSUE 12): when EVERY waiting stream
        # tenant's deficit is dry, start a new round up front (the
        # common single-tenant case replenishes once and serves a full
        # pass, pre-ISSUE-12 throughput)
        weights, n_stream = _stream_tenant_weights(gw)
        if weights and qs.wdrr.all_dry(weights):
            qs.wdrr.replenish(weights, n_stream)
        visits = len(gw)
        # streams skipped ONLY because their tenant's WDRR deficit ran
        # dry this round (credit-blocked or empty-queue skips don't
        # count): when that is the only reason nothing moved, a new
        # round replenishes every waiting tenant and the pump re-runs
        blocked_on_allowance = False
        while visits and gw:
            visits -= 1
            conn = gw[0]
            if conn.closed:
                gw.popleft()
                continue
            if conn.replay is not None:
                # replay waiter ('D' park): serve from the cursor
                if conn.pending is None or conn.pending.get("kind") != "D":
                    gw.popleft()
                    continue
                try:
                    items = conn.replay.next_batch(conn.pending["max_items"])
                except TransportClosed:
                    raise _QueueClosedSignal from None
                if not items:
                    gw.rotate(-1)  # caught up: the timer answers empty
                    continue
                try:
                    conn._respond_batch(items)
                    gw.popleft()
                    conn.unpark()
                except (ConnectionError, OSError) as e:
                    self.kill_conn(conn, e)
                did = True
                continue
            if conn.stream is not None:
                allow = qs.wdrr.allowance(conn.tenant)
                if allow < 1.0:
                    # tenant budget exhausted this WDRR round: other
                    # tenants' streams go first (weighted fair-share)
                    blocked_on_allowance = True
                    gw.rotate(-1)
                    continue
                # per-VISIT cap at quantum * weight: within a shared
                # tenant budget, rotation (serve-rotate + blocked-rotate
                # is a full cycle with two conns) would otherwise hand
                # the whole round to whichever conn sits first — each
                # visit takes one quantum so same-tenant conns split
                # their tenant's round evenly
                want = min(
                    conn.stream.budget(), _STREAM_POP_MAX, int(allow),
                    _WDRR_QUANTUM * conn.weight,
                )
                if want <= 0:
                    gw.rotate(-1)  # window full: wait for credits
                    continue
            elif conn.pending is not None and conn.pending.get("kind") == "D":
                want = conn.pending["max_items"]
            else:
                gw.popleft()  # served by a timer / superseded
                continue
            try:
                items = qs.queue.get_batch(min(want, 4096), timeout=0.0)
            except TransportClosed:
                raise _QueueClosedSignal from None
            except Exception as e:  # noqa: BLE001 — a corrupt spill read
                # must cost this waiter an error answer, not the loop
                gw.popleft()
                try:
                    conn._send_control(_ST_ERR)
                    if conn.stream is None:
                        conn.unpark()
                except (ConnectionError, OSError):
                    self.kill_conn(conn, e)
                did = True
                continue
            if not items:
                if any(c.replay is not None for c in gw if not c.closed):
                    gw.rotate(-1)  # let replay waiters behind us run
                    continue
                break  # queue empty: every remaining get-waiter waits
            try:
                if conn.stream is not None:
                    qs.wdrr.charge(conn.tenant, len(items))
                    conn.push_stream_items(items)
                    gw.rotate(-1)  # round-robin fairness across streams
                else:
                    conn._respond_batch(items)
                    gw.popleft()
                    conn.unpark()
            except (ConnectionError, OSError) as e:
                # the waiter died with items popped: standard redelivery
                self.kill_conn(conn, e)
            did = True
        if not did and blocked_on_allowance:
            # frames exist but every stream that could still serve was
            # allowance-blocked (a credit-stalled tenant may be sitting
            # on unspent deficit, which all_dry above would wait on
            # forever): force a new round. Reporting progress makes
            # _pump_all re-run this pump with fresh budgets — the next
            # pass either serves frames or finds nothing but
            # credit/emptiness blocks (allowances now >= 1, so the
            # blocked flag stays down and the loop ends)
            weights, n_stream = _stream_tenant_weights(gw)
            qs.wdrr.replenish(weights, n_stream)
            did = bool(weights)
        return did

    def _pump_put(self, qs: _QueueState) -> bool:
        did = False
        pw = qs.put_waiters
        while pw:
            conn = pw[0]
            if conn.closed or conn.pending is None or conn.pending.get(
                "kind"
            ) not in ("U", "W"):
                pw.popleft()
                continue
            try:
                put_offset = getattr(qs.queue, "put_offset", None)
                if put_offset is not None:
                    ok, offset = put_offset(conn.pending["item"])
                else:
                    ok, offset = qs.queue.put(conn.pending["item"]), None
            except TransportClosed:
                raise _QueueClosedSignal from None
            except Exception as e:  # noqa: BLE001 — e.g. a durable queue
                # refusing an oversized record (ValueError from the
                # segment log): answer THIS conn with a protocol error
                # instead of letting the exception escape _pump_all and
                # take the whole loop (and every connection) down
                pw.popleft()
                try:
                    conn._send_control(_ST_ERR)
                    conn.unpark()
                except (ConnectionError, OSError):
                    self.kill_conn(conn, e)
                did = True
                continue
            if not ok:
                break  # still full: FIFO — nobody behind may jump the line
            pw.popleft()
            if conn.pending["kind"] == "W":
                parts = [_ST_OK + struct.pack("<Q", conn.pending["seq"])]
            else:
                parts = [_ST_OK]
            try:
                # the reply may re-park on the replicated ack floor
                # (pending flips U/W -> RA); parked=True resumes reads
                # on the immediate-answer path
                conn._answer_put(parts, offset, parked=True)
            except (ConnectionError, OSError) as e:
                self.kill_conn(conn, e)
            did = True
        return did

    def _pump_rack(self, qs: _QueueState) -> bool:
        """Release producer replies whose offsets the follower has
        logged — or all of them once the sender degraded (follower link
        down past the grace window). FIFO is offset order, so an
        unreached head means nobody behind is reachable either."""
        did = False
        rw = qs.ra_waiters
        while rw:
            conn = rw[0]
            if conn.closed or conn.pending is None or conn.pending.get(
                "kind"
            ) != "RA":
                rw.popleft()
                continue
            if qs.repl is not None and not qs.repl.reached(
                conn.pending["offset"]
            ):
                break
            rw.popleft()
            parts = conn.pending["parts"]
            try:
                conn.send_parts(parts)
                conn.unpark()
            except (ConnectionError, OSError) as e:
                self.kill_conn(conn, e)
            did = True
        return did

    def _queue_closed(self, qs: _QueueState) -> None:
        """The backing queue raised TransportClosed mid-pump: answer
        every waiter with 'X' (bounded waits resume the connection;
        streams end — the threaded server's stream loop did the same)."""
        while qs.get_waiters:
            conn = qs.get_waiters.popleft()
            if conn.closed:
                continue
            try:
                if conn.stream is not None:
                    conn.stream.queue_closed = True
                    conn._send_control(_ST_CLOSED)  # the stream is over
                    conn._finish_stream(clean=False)
                    conn._begin_close()
                else:
                    conn._send_control(_ST_CLOSED)
                    conn.unpark()
            except (ConnectionError, OSError) as e:
                self.kill_conn(conn, e)
        while qs.put_waiters:
            conn = qs.put_waiters.popleft()
            if conn.closed or conn.pending is None:
                continue
            try:
                conn._send_control(_ST_CLOSED)
                conn.unpark()
            except (ConnectionError, OSError) as e:
                self.kill_conn(conn, e)
        while qs.ra_waiters:
            # replicated-ack waiters: their frames WERE accepted and
            # logged before the close — release the truthful OK reply
            # rather than holding it against a floor that may never
            # advance on a closed queue
            conn = qs.ra_waiters.popleft()
            if conn.closed or conn.pending is None or conn.pending.get(
                "kind"
            ) != "RA":
                continue
            try:
                conn.send_parts(conn.pending["parts"])
                conn.unpark()
            except (ConnectionError, OSError) as e:
                self.kill_conn(conn, e)

    def _teardown(self) -> None:
        for conn in list(self._conns):
            # server stopping: redeliver in-flight/unacked to the queues
            # (parity with the threaded server, whose dying serve
            # threads requeue on the forced disconnect)
            self.kill_conn(conn, None, requeue=True)
        for qs in self._queues.values():
            if qs.unlisten is not None:
                try:
                    qs.unlisten()
                except Exception:
                    pass
        for s in (self._waker_r, self._waker_w):
            try:
                self._sel.unregister(s)
            except (KeyError, ValueError, OSError):
                pass
            try:
                s.close()
            except OSError:
                pass
        try:
            self._sel.unregister(self._srv._sock)
        except (KeyError, ValueError, OSError):
            pass
        self._sel.close()
