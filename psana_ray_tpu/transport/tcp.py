"""Cross-host transport: a TCP queue server + client with the transport
contract.

The reference's cross-node data plane is Ray's object store + actor RPC
(SURVEY.md §5 "Distributed communication backend"). Here the cross-host
hop is an explicit length-prefixed TCP protocol over any local queue
(RingBuffer or ShmRingBuffer): producers on ingest nodes connect and PUT,
consumers on TPU hosts connect and GET. One server per queue — the same
single-serialization-point design as the reference's actor, without the
object-store copy.

Wire protocol (all little-endian):
    request:  op:u8 ('P'|'G'|'S'|'C') + [P only] len:u32 + payload
              'B' (get-batch) + max_items:u32
              'D' (get-batch, bounded server-side wait) + max_items:u32
                  + timeout_ms:u32 — the server blocks up to the timeout
                  (capped at ``_SERVER_WAIT_CAP_S``) for the FIRST item,
                  so a momentarily empty queue costs one round trip per
                  cap interval instead of one per client poll tick
              'Q' (put-batch) + count:u32 + count x (len:u32 + payload)
              'U' (put, bounded server-side wait) + timeout_ms:u32
                  + len:u32 + payload — the server blocks for queue
                  space up to the (capped) timeout before answering
                  '1'/'0', the producer-side mirror of 'D'
              'W' (windowed put) + seq:u64 + len:u32 + payload —
                  pipelined: the client does NOT wait for the response
                  before the next request; see streaming contract below
              'M' (stream subscribe) + credits:u32 — switch this
                  connection to server-push delivery; see below
              'K' (stream ack) + seq:u64 — cumulative consumption ack
                  on a streamed connection (credit replenish)
              'O' (open) + ns_len:u16 + ns + name_len:u16 + name
                         + maxsize:u32
              'T' (stats) — queue-health RPC: depth, high-water mark,
                  put/get counters, liveness ages of the bound queue
              'A' (anchor) — clock ping/anchor exchange (the stats RPC's
                  tracing sibling): client sends its wall:f64 + mono:f64,
                  server replies with its own pair; the client records
                  the exchange so the trace merge tool (obs.trace_merge)
                  can align this host's clock to the server's, bounded
                  by the measured RTT
              'N' (cluster/group RPC) + len:u32 + JSON — consumer-group
                  coordination (join/heartbeat/leave/drained/info against
                  the server's :class:`psana_ray_tpu.cluster.coordinator.
                  GroupRegistry`); by convention clients send it to the
                  FIRST server of the cluster address list
              'R' (replay-open) + from:u64 + group_len:u16 + group —
                  durable queues only (ISSUE 8): switch this
                  connection's READS to a non-destructive cursor over
                  the queue's retained segment-log range for the named
                  consumer group (live consumers undisturbed). ``from``
                  is an offset or a sentinel (u64 max = begin/earliest
                  retained, u64 max-1 = resume at the group's committed
                  offset). Subsequent G/B/D serve from the cursor;
                  delivered records are committed for the group at the
                  connection's implicit-ACK points, so crash-redelivery
                  is re-open at resume
              'J' (commit-offset) + offset:u64 + group_len:u16 + group —
                  durable queues only: persist the group's committed
                  offset (offset u64 max = "everything delivered to this
                  connection's replay cursor so far"); '0' when the
                  bound queue has no log
              'Z' (capability exchange) + len:u16 + comma-separated
                  entries — wire-compression negotiation (ISSUE 9) plus
                  per-connection capability FIELDS (ISSUE 12): plain
                  entries are codec names the client can decode, in
                  preference order; entries of the form ``key=value``
                  are capability fields (currently
                  ``tenant=<name>[:<weight>]`` — the tenant identity +
                  fair-share weight the event loop's weighted
                  deficit-round-robin stream pump serves this
                  connection under). The server picks the first codec
                  it also implements (or "none") and BOTH sides apply
                  it to frame payloads on THIS connection from the
                  next message on (payload tag 'C', transport/codec.py;
                  a frame that expands under the codec still ships raw
                  — compression is an encoding, never a requirement).
                  Servers predating a capability field ignore it (the
                  codec picker skips entries it does not recognize);
                  clients that never negotiate see byte-identical wire
                  traffic to pre-codec peers
              'H' (replica-subscribe) + ns_len:u16 + ns + name_len:u16
                  + name — replication (ISSUE 11): switch this
                  connection to REPLICA mode for the named queue's
                  replica log on a durable server. The response carries
                  the replica's current tail so the owner's shipper
                  resumes exactly there; from here the connection
                  carries only 'V' appends and 'F'. '0' when this
                  server cannot host the replica (no --durable_dir, the
                  queue is mounted live here, or the replica was
                  already promoted — the fencing answer a zombie owner
                  sees after a failover)
              'V' (replica-append) + offset:u64 + floor:u64 + len:u32
                  + payload — one chain-replicated record at an
                  explicit log offset (the owner's offset space is
                  mirrored verbatim; divergence reconciles by
                  truncate-to-offset, gaps by reset — both
                  breadcrumbed). ``floor`` piggybacks the owner's live
                  committed offset (u64 max = none) so a promoted
                  replica re-exposes only the unacked window.
                  Windowed like 'W': the owner pipelines appends and
                  reads cumulative '1'+offset acks; the acked offset IS
                  the replicated ack floor gating producer acks on the
                  owner. 'E' = refused (promoted/fenced or disk fault)
              'Y' (promote) + ns_len:u16 + ns + name_len:u16 + name —
                  failover: finalize the named replica log on this
                  server (fence further 'V' appends, flush, release the
                  mapping) so the next OPEN mounts it as the LIVE
                  durable queue, serving the replicated backlog and
                  retained range. Answers the retained range; '0' when
                  no replica exists here (the queue starts empty)
              'F' (bye) — no response; acks the last delivery and ends
                  the connection cleanly (see delivery contract below)
    response: status:u8 ('1' ok | '0' full/empty | 'X' closed | 'E' error)
              + [G ok] len:u32 + payload   + [S] size:u32
              + [B/D ok] count:u32 + count x (len:u32 + payload)
              + [Q ok] accepted:u32
              + [W ok] seq:u64 (the acknowledged put's sequence number)
              + [T ok] len:u32 + JSON stats object
              + [A ok] wall:f64 + mono:f64
              + [N ok] len:u32 + JSON group-state object
              + [R ok] start:u64 + end:u64 (resolved cursor start and
                the log tail at open time; the cursor follows the tail)
              + [Z ok] len:u16 + chosen codec name ("none" = stay raw)
              + [H ok] tail:u64 (the replica log's next offset)
              + [V ok] offset:u64 (cumulative replicated-ack floor)
              + [Y ok] start:u64 + end:u64 (the promoted retained range)
    stream push (server -> client, after 'M'):
              status:u8 ('1') + seq:u64 + len:u32 + payload per frame;
              'X' when the bound queue closes (the stream is over)

Delivery contract (PART OF THE WIRE PROTOCOL, not a server detail): the
server holds each GET/B/D delivery as in-flight until the SAME
connection's next opcode arrives (implicit ACK — a client can only send
its next request after fully reading the previous response) or BYE acks
it on clean disconnect. This assumes ONE outstanding request per
connection: a pipelining client that sends request N+1 before reading
response N would silently forfeit in-flight protection (the early opcode
acks a delivery the client has not read). Duplicates are therefore
possible on crash/retry (at-least-once), silent loss is not. Duplicated
control records are benign: EndOfStream markers tally idempotently
(coverage is keyed by ``producer_rank`` —
:class:`psana_ray_tpu.records.EosTally`), and FrameRecord duplicates
carry their ``(shard_rank, event_idx)`` provenance for downstream dedup.

Streaming contract (ISSUE 5): the request/response exchange above pays
one full RTT per round trip under exactly one outstanding request, so on
any real link throughput is RTT-bound (~1/RTT frames/s/connection at
queue-limited batch sizes). Two connection modes deliberately REPLACE
the implicit next-request ACK with explicit sequence/credit ACKs so the
link can stay full of in-flight work:

- ``STREAM`` ('M'): the client subscribes with an initial credit count
  W; the server pushes queued frames as they arrive — scatter-gather,
  straight from the queued record's pooled lease — tagging each with a
  per-connection sequence number and decrementing credits, and blocks
  once W pushes are unacknowledged. The client replenishes credits with
  cumulative 'K' acks as it CONSUMES (it acks everything previously
  returned when it comes back for more — the same point the implicit
  ACK fired in request/response mode), so the credit window bounds
  client-side memory exactly like a prefetch depth. Pushed-but-unacked
  frames are held server-side and RE-ENQUEUED (head placement) when the
  connection dies — at-least-once crash-redelivery, exactly as
  in-flight GETs. A streamed connection carries ONLY pushes downstream
  and 'K'/'F' upstream; any other opcode on it (a second 'M' included)
  is a protocol error.
- windowed PUT ('W'): up to W sequence-numbered puts in flight before
  the client blocks reading statuses. The server enqueues each (waiting
  for space — backpressure arrives as delayed acks) and answers
  '1'+seq. On reconnect the client resends the entire unacknowledged
  tail, in order, before anything else touches the fresh connection —
  duplicates possible, holes never.

Client threading: :class:`TcpQueueClient` serializes every exchange under
one lock, satisfying the one-outstanding-request rule; during an outage a
reconnecting call holds that lock through the backoff cycle, so OTHER
threads sharing the client (e.g. a monitor calling ``size()``) block for
up to the full reconnect envelope — use one client per thread where that
matters.

The batch opcodes exist so a cross-host consumer drains N records per
round trip instead of reintroducing the reference's one-RPC-per-event
bottleneck (reference ``data_reader.py:35``, SURVEY.md §3.1) over the
network hop.

The OPEN opcode makes one server a *cluster registry of named queues* —
Ray-GCS parity for the only transport that crosses hosts (reference
``shared_queue.py:33-38`` registers the actor by (namespace, name);
``data_reader.py:20`` resolves it the same way). OPEN get-or-creates the
(namespace, queue_name) queue server-side and binds this connection to
it; connections that never send OPEN use the server's default queue
(back-compat with single-queue deployments). Named queues are detached:
they live until the server process stops, regardless of which client
created them (parity: ``lifetime="detached"``, ``shared_queue.py:35``).

Payloads reuse the shm codec (records wire format / tagged pickle).

Zero-copy datapath (ISSUE 2): frame payloads are never materialized as
fresh bytes on either side of the socket. Sends go out via
``socket.sendmsg`` scatter-gather straight from the record's panel
memory (``FrameRecord.wire_parts``); receives land via ``recv_into`` in
recycled leases from the process :class:`~psana_ray_tpu.utils.bufpool.
BufferPool` and decode as VIEWS of that memory, with the lease riding
the record until the payload is copied onward (``FrameBatcher.
push_view``) or the record dies. The server's relay path is therefore
alloc-free and copy-free per brokered frame at steady state: a PUT's
pooled buffer is the very memory a later GET response streams from.
This composes with the delivery contract below — an in-flight record's
lease is released only when the record itself is dropped after the
implicit ACK (or re-enqueued intact on connection death), never while
redelivery could still need the payload.

In-flight items are never dropped on a consumer crash: if the connection
dies between the queue pop and the response write, the server re-enqueues
the popped item(s).

Server architecture (ISSUE 6): the server IS a single selectors/epoll
readiness loop (:mod:`psana_ray_tpu.transport.evloop`) driving a
per-connection state machine over all 22 opcodes — memory O(connections
x small struct), thread count independent of connection count, blocking
waits ('W'/'U'/'D', stream credit stalls) held as timer/deferred-
callback state instead of parked threads. The legacy thread-per-
connection implementation was retained one release behind
``mode="threads"`` and has been REMOVED (ISSUE 7); the wire bytes and
delivery contract are pinned by test_wire_zero_copy / test_tcp /
test_tcp_stream and the wire-opcode checker. This module keeps the
protocol definition (opcode constants, framing helpers) and the client.

Cluster (ISSUE 7): N servers become one logical queue service through
:mod:`psana_ray_tpu.cluster` — a logical queue shards into partitions,
each an ordinary named queue here (``<queue>#p<N>`` via OPEN), placed by
rendezvous hashing over the live server set; :class:`psana_ray_tpu.
cluster.client.ClusterClient` wraps one TcpQueueClient per partition
and presents this module's transport contract unchanged.
"""

from __future__ import annotations

import json
import random
import socket
import struct
import threading
import time
from collections import deque
from typing import Any, List, Optional

from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.obs.profiling.stagetag import TAG_ENQUEUE, set_stage, swap_stage
from psana_ray_tpu.obs.stages import HOP_ENQ, STAGE_QUEUE_DWELL
from psana_ray_tpu.obs.tracing import SPAN_RELAY, TRACER
from psana_ray_tpu.records import mark_hop
from psana_ray_tpu.transport.registry import TransportClosed
from psana_ray_tpu.transport.ring import EMPTY, RingBuffer
from psana_ray_tpu.transport.codec import (
    CODEC_NONE,
    CODEC_STATS,
    available_codecs,
    decode_payload as _decode,
    encode_for_wire as _wire_encode,
    get_codec,
    payload_nbytes as _parts_nbytes,
)
from psana_ray_tpu.utils.bufpool import BufferPool
from psana_ray_tpu.utils.metrics import probe_queue_stats

_OP_PUT = b"P"
_OP_GET = b"G"
_OP_SIZE = b"S"
_OP_CLOSE = b"C"
_OP_GET_BATCH = b"B"
_OP_GET_BATCH_WAIT = b"D"
_OP_PUT_BATCH = b"Q"
_OP_PUT_WAIT = b"U"
_OP_PUT_SEQ = b"W"
_OP_STREAM = b"M"
_OP_STREAM_ACK = b"K"
_OP_OPEN = b"O"
_OP_STATS = b"T"
_OP_ANCHOR = b"A"
_OP_CLUSTER = b"N"
_OP_REPLAY = b"R"
_OP_COMMIT = b"J"
_OP_CODEC = b"Z"
_OP_REPL_OPEN = b"H"
_OP_REPL_APPEND = b"V"
_OP_PROMOTE = b"Y"
_OP_BYE = b"F"
_ST_OK = b"1"
_ST_NO = b"0"
_ST_CLOSED = b"X"
_ST_ERR = b"E"
# 'V' replica-append floor field sentinel: no committed floor to
# piggyback (nothing consumed on the owner yet)
_REPL_NO_FLOOR = (1 << 64) - 1

# The longest one bounded-wait request ('D'/'U' timeout field) may defer
# server-side: long enough that an idle consumer costs ~one round trip
# per interval, short enough that drain/shutdown and connection-death
# detection stay timely.
_SERVER_WAIT_CAP_S = 2.0
# default credit window (frames in flight) for stream subscriptions and
# the windowed-put pipeline — bounds client memory like a prefetch depth
DEFAULT_STREAM_WINDOW = 32


class StreamTelemetry:
    """Credit/in-flight-window accounting for the streaming transport
    (obs source ``stream``): how full the credit windows run, how much
    sits unacknowledged, and how often crash-redelivery fired. One
    process-wide instance (:data:`STREAM`), registered in the default
    MetricsRegistry on first streaming use."""

    def __init__(self):
        self._lock = threading.Lock()
        self._registered = False  # guarded-by: _lock
        self.streams_opened = 0  # guarded-by: _lock
        self.frames_pushed = 0  # guarded-by: _lock
        self.acks = 0  # ack messages seen (client+server side)  # guarded-by: _lock
        self.redelivered = 0  # frames requeued off dead streams  # guarded-by: _lock
        self.inflight = 0  # pushed-not-yet-acked, all server streams  # guarded-by: _lock
        self.inflight_peak = 0  # guarded-by: _lock
        self.credit_window = 0  # sum of active subscriptions' windows  # guarded-by: _lock
        self.put_window_depth = 0  # client-side unacked windowed puts  # guarded-by: _lock
        self.put_window_peak = 0  # guarded-by: _lock
        self.put_resent = 0  # windowed puts resent after reconnect  # guarded-by: _lock

    def ensure_registered(self):
        with self._lock:
            if self._registered:
                return
            self._registered = True
        try:
            from psana_ray_tpu.obs import MetricsRegistry

            MetricsRegistry.default().register("stream", self)
        except Exception:  # obs optional: transport must work without it
            pass

    def opened(self, window: int):
        self.ensure_registered()
        with self._lock:
            self.streams_opened += 1
            self.credit_window += window

    def closed(self, window: int):
        with self._lock:
            self.credit_window -= window

    def pushed(self, n: int):
        with self._lock:
            self.frames_pushed += n
            self.inflight += n
            if self.inflight > self.inflight_peak:
                self.inflight_peak = self.inflight

    def pruned(self, n: int):
        with self._lock:
            self.inflight -= n

    def acked_msg(self):
        with self._lock:
            self.acks += 1

    def redelivered_n(self, n: int):
        with self._lock:
            self.redelivered += n

    def put_depth(self, depth: int):
        self.ensure_registered()
        with self._lock:
            self.put_window_depth = depth
            if depth > self.put_window_peak:
                self.put_window_peak = depth

    def resent(self, n: int):
        with self._lock:
            self.put_resent += n

    def stats(self) -> dict:
        with self._lock:
            return {
                "streams_opened": self.streams_opened,
                "frames_pushed_total": self.frames_pushed,
                "acks_total": self.acks,
                "redelivered_total": self.redelivered,
                "inflight": self.inflight,
                "inflight_peak": self.inflight_peak,
                "credit_window": self.credit_window,
                "put_window_depth": self.put_window_depth,
                "put_window_peak": self.put_window_peak,
                "put_resent_total": self.put_resent,
            }

    # obs registry source protocol
    def snapshot(self) -> dict:
        return self.stats()


STREAM = StreamTelemetry()



def _queue_stats_payload(queue) -> dict:
    """JSON-safe stats for any backing queue: full ``stats()`` when the
    backing provides it (RingBuffer, ShmRingBuffer), depth-only otherwise.
    A dead queue reports ``closed`` instead of erroring the whole RPC."""
    try:
        return probe_queue_stats(queue)
    except TransportClosed:
        return {"closed": True}
    except Exception as e:  # noqa: BLE001 — stats must not kill serving
        return {"error": repr(e)}


def _recv_into(sock: socket.socket, mv: memoryview) -> None:
    """Fill ``mv`` exactly from ``sock`` with ``recv_into`` — the wire
    payload lands in caller-owned (pooled) memory with ZERO intermediate
    bytes objects and linear cost. THE one receive primitive of this
    module: every read, control or payload, goes through here."""
    got = 0
    n = len(mv)
    while got < n:
        k = sock.recv_into(mv[got:])
        if not k:
            raise ConnectionError("peer closed")
        got += k


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    """Exactly ``n`` bytes for CONTROL fields (opcodes, lengths — a few
    bytes). Frame payloads must use :func:`_recv_into` on a pooled
    buffer instead. Linear: fills one preallocated buffer in place (the
    old chunked ``recv()`` + accumulate pattern re-copied the prefix on
    every chunk)."""
    buf = bytearray(n)
    _recv_into(sock, memoryview(buf))
    return bytes(buf)


# sendmsg scatter-gather: bounded iovec count per call (Linux IOV_MAX is
# 1024; staying far below keeps each call cheap to assemble) with partial
# sends resumed mid-part. Falls back to sendall-per-part where sendmsg is
# unavailable (non-POSIX).
_SENDMSG_IOV = 64
# consecutive parts at or below this size are joined before sending:
# copying a run of few-byte control fields (opcodes, lengths, record
# headers) is free and keeps the iovec count low for small-record
# batches, while frame payloads above it always pass through zero-copy
_COALESCE_MAX = 4096


def _gather_parts(parts) -> List[memoryview]:
    """Normalize a scatter-gather part list for sending: empty parts are
    dropped, runs of tiny control parts (opcodes, lengths, record
    headers) are coalesced up to ``_COALESCE_MAX``, frame-sized payloads
    pass through as zero-copy memoryviews. Shared by the blocking
    :func:`_sendmsg_all` sender and the event-loop server's non-blocking
    outbound write queue (:mod:`psana_ray_tpu.transport.evloop`), so the
    bytes on the wire are identical in both modes."""
    bufs: List[memoryview] = []
    small: List[memoryview] = []

    def _flush_small():
        if not small:
            return
        bufs.append(small[0] if len(small) == 1 else memoryview(b"".join(small)))
        small.clear()

    for p in parts:
        m = p if isinstance(p, memoryview) else memoryview(p)
        if not m.nbytes:
            continue
        if m.nbytes <= _COALESCE_MAX:
            small.append(m)
            if sum(s.nbytes for s in small) >= _COALESCE_MAX:
                _flush_small()
        else:
            _flush_small()
            bufs.append(m)
    _flush_small()
    return bufs


def _sendmsg_all(sock: socket.socket, parts) -> None:
    """Send every buffer in ``parts`` without concatenating the large
    ones — the scatter-gather complement of :func:`_recv_into`. A 4.3 MB
    frame goes from the record's own panel memory to the kernel in one
    hop; the old ``b"".join`` path paid a frame-sized copy per message.
    Runs of tiny control parts are coalesced (see ``_COALESCE_MAX``)."""
    bufs = _gather_parts(parts)
    if not hasattr(sock, "sendmsg"):  # platform fallback: copy-free per part
        for m in bufs:
            sock.sendall(m)
        return
    i = 0
    while i < len(bufs):
        sent = sock.sendmsg(bufs[i : i + _SENDMSG_IOV])
        if sent <= 0:
            raise ConnectionError("peer closed during sendmsg")
        while sent > 0:
            m = bufs[i]
            if sent >= m.nbytes:
                sent -= m.nbytes
                i += 1
            else:
                bufs[i] = m[sent:]
                sent = 0


# Upper bound on one tagged payload (u32 on the wire allows 4 GiB): a
# corrupt or hostile length field must not size a pool lease — the
# largest real frame (jungfrau4M f64) is ~67 MB, so 256 MB is generous.
# Oversized lengths surface as ConnectionError so the server's in-flight
# requeue path runs (the stream is desynced; the connection must die).
_MAX_PAYLOAD = 256 * 1024 * 1024


def _recv_payload(sock: socket.socket, n: int, pool: BufferPool):
    """Receive an ``n``-byte tagged payload into a pooled buffer and
    decode it. Frame records come back ZERO-COPY (panels view the pooled
    buffer, lease attached — see records.decode); other payloads release
    the lease at decode. On any failure the lease goes straight back."""
    if n > _MAX_PAYLOAD:
        raise ConnectionError(
            f"payload length {n} exceeds wire maximum {_MAX_PAYLOAD}"
        )
    lease = pool.lease(n)
    try:
        _recv_into(sock, lease.mv)
        return _decode(lease.mv, lease=lease)
    except BaseException:
        lease.release()  # idempotent: double-release after decode is safe
        raise


# -- relay-side tracing (sampled frames only; gated on TRACER.enabled) ----
def _stamp_relay_arrival(item) -> None:
    """Mark a sampled frame's arrival at the relay (server PUT decode) —
    the start of its queue-dwell span. The stamp lives in the record's
    process-local hops dict, which survives the in-memory queue hop to
    the GET that delivers it (shm-backed queues re-encode and lose it;
    the merge timeline shows dwell as the producer->consumer gap there)."""
    trace = getattr(item, "trace", None)
    if trace is not None and trace.sampled:
        mark_hop(item, HOP_ENQ)


def _emit_relay_spans(items, t_send0: float) -> None:
    """After a GET/B response went out: per sampled frame, a
    ``queue_dwell`` span (relay arrival -> response start) and a
    ``relay`` span (response serialization + send)."""
    t_done = time.monotonic()
    for item in items:
        trace = getattr(item, "trace", None)
        if trace is None or not trace.sampled:
            continue
        hops = getattr(item, "hops", None)
        t_arrived = hops.get(HOP_ENQ) if hops else None
        if t_arrived is not None:
            TRACER.span(trace.trace_id, STAGE_QUEUE_DWELL, t_arrived, t_send0)
        TRACER.span(trace.trace_id, SPAN_RELAY, t_send0, t_done)


# -- server mode -----------------------------------------------------------
# "evloop" is THE server: ONE selectors/epoll readiness loop serves
# every connection through per-connection state machines — O(connections
# x small struct) memory, thread count independent of connection count
# (ISSUE 6; implementation in transport/evloop.py). The legacy
# thread-per-connection mode ("threads") was retained one release behind
# this knob and removed in ISSUE 7.
DEFAULT_SERVER_MODE = "evloop"
_SERVER_MODES = ("evloop",)


def _resolve_server_mode(mode: Optional[str]) -> str:
    import os

    m = mode or os.environ.get("PSANA_TCP_SERVER_MODE") or DEFAULT_SERVER_MODE
    if m not in _SERVER_MODES:
        raise ValueError(
            f"unknown server mode {m!r}; expected one of {_SERVER_MODES} "
            f"(the legacy thread-per-connection mode was removed one "
            f"release after the event-loop server became the default)"
        )
    return m


def _refuse_conn(conn: socket.socket, port: int, active: int, limit: int):
    """Admission control: accept-then-refuse with a clean ``_ST_ERR``
    payload instead of letting an accept storm OOM the relay. The
    refused client's next ``_status()`` read surfaces it as a protocol
    error immediately (no hang, no half-open connection)."""
    FLIGHT.record("conn_refused", port=port, active=active, max_conns=limit)
    try:
        conn.send(_ST_ERR)
    except OSError:
        pass
    try:
        conn.close()
    except OSError:
        pass


class TcpQueueServer:
    """Serve queues over TCP: one default queue plus any number of named
    queues that clients OPEN by (namespace, queue_name) — see the module
    docstring. Start with ``serve_background()``.

    The serving architecture is one epoll readiness loop with
    per-connection state machines for all 22 opcodes, blocking waits as
    timer/deferred state (:mod:`psana_ray_tpu.transport.evloop`) —
    scales to thousands of streamed subscribers with O(1) threads. The
    legacy thread-per-connection mode was removed (ISSUE 7); ``mode``
    remains as a guard that rejects anything but ``"evloop"``.

    ``max_conns`` (0 = unlimited) refuses connections past the limit
    with a clean ``_ST_ERR`` instead of accepting unboundedly. The
    server also hosts the cluster consumer-group coordinator state
    (``groups`` — :class:`psana_ray_tpu.cluster.coordinator.
    GroupRegistry`) behind the 'N' RPC; it is inert unless a cluster
    client elects this server as its coordinator."""

    def __init__(
        self,
        queue=None,
        host: str = "0.0.0.0",
        port: int = 0,
        maxsize: int = 100,
        queue_factory=None,
        pool: Optional[BufferPool] = None,
        mode: Optional[str] = None,
        max_conns: int = 0,
        group_store_path: Optional[str] = None,
        replication=None,
        reuseport: bool = False,
        worker_ctx=None,
    ):
        self.queue = queue if queue is not None else RingBuffer(maxsize)
        # multi-process data plane (ISSUE 17): a transport.workers.
        # WorkerContext makes this server ONE of N forked evloop workers
        # sharing the port via SO_REUSEPORT — the loop registers its
        # adoption socket and routes queue ops to partition owners over
        # SCM_RIGHTS fd migration. None = classic single-process server.
        self.worker_ctx = worker_ctx
        self._maxsize = maxsize
        # recv-buffer pool for the relay path: every PUT payload lands in
        # a recycled lease and is decoded zero-copy, so a brokered frame
        # costs no allocation per hop (the lease returns to the pool when
        # the frame's delivery is acknowledged and the record dies)
        self._pool = pool if pool is not None else BufferPool.default()
        # factory for OPENed queues: (namespace, name, maxsize) -> queue.
        # Default in-process rings; a server may hand out shm-backed rings
        # instead so local clients can bypass TCP (queue_server.py --shm)
        self._queue_factory = queue_factory or (
            lambda ns, name, maxsize: RingBuffer(maxsize, name=f"{ns}__{name}")
        )
        self._queues = {}  # (namespace, name) -> queue  # guarded-by: _queues_lock
        self._queues_lock = threading.Lock()
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        if reuseport:
            # N worker processes each bind their own listener to the
            # SAME port; the kernel shards incoming CONNECTIONS across
            # them (queue partitioning is the workers' fd-migration
            # job, not the kernel's)
            self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        self._draining = False
        self._threads: List[threading.Thread] = []
        self._conns: List[socket.socket] = []  # guarded-by: _conns_lock
        self._conns_lock = threading.Lock()
        self.mode = _resolve_server_mode(mode)
        self.max_conns = int(max_conns)
        self._loop = None  # the EventLoop driving this server
        # consumer-group coordinator state (cluster 'N' RPC). Imported
        # lazily: psana_ray_tpu.cluster's client half imports this module.
        # With a store path (queue_server --durable_dir) the control
        # state snapshots to disk and a coordinator restart recovers
        # groups instead of emptying them (ISSUE 8).
        from psana_ray_tpu.cluster.coordinator import GroupRegistry

        self.groups = GroupRegistry(store_path=group_store_path)
        # chain replication (ISSUE 11): a cluster.replication.
        # ReplicationManager makes this server BOTH an owner that ships
        # its durable queues' segment logs to their follower ('V' over a
        # dedicated link, producer acks gated on the replicated floor)
        # AND a follower hosting passive replica logs ('H'/'V' inbound,
        # 'Y' promote on failover) — None = unreplicated, zero new cost
        self.replication = replication
        if replication is not None:
            replication.attach(self)

    def open_named(self, namespace: str, queue_name: str, maxsize: Optional[int] = None):
        """Get-or-create the named queue (the OPEN opcode server-side;
        also callable in-process, e.g. for a host-local consumer of a
        queue remote producers feed over TCP)."""
        key = (namespace, queue_name)
        with self._queues_lock:
            q = self._queues.get(key)
            if q is None:
                if self.replication is not None:
                    # an OPEN of a queue this server holds a REPLICA of
                    # is a failover landing here: finalize the replica
                    # log first (fence + unmap) so the durable factory's
                    # recovery scan mounts the replicated backlog —
                    # defense in depth behind the explicit 'Y' promote
                    self.replication.ensure_promoted(namespace, queue_name)
                q = self._queue_factory(namespace, queue_name, maxsize or self._maxsize)
                self._queues[key] = q
                if self.replication is not None:
                    # owner half: if this server is in the partition's
                    # chain with a next link, start shipping its log
                    self.replication.queue_mounted(namespace, queue_name, q)
                FLIGHT.record("queue_opened", namespace=namespace, name=queue_name)
            return q

    def has_named_queue(self, namespace: str, queue_name: str) -> bool:
        """Is ``(namespace, queue_name)`` mounted LIVE here? (The
        replica-subscribe refusal check: a server never hosts a passive
        replica of a queue it is serving.)"""
        with self._queues_lock:
            return (namespace, queue_name) in self._queues

    def named_queues(self) -> List[tuple]:
        with self._queues_lock:
            return sorted(self._queues)

    def queues_by_name(self) -> dict:
        """``{label: queue}`` over the default + every named queue —
        the stall detector's dynamic watch population (labels are
        ``default`` and ``<namespace>/<queue_name>``)."""
        with self._queues_lock:
            out = {f"{ns}/{nm}": q for (ns, nm), q in self._queues.items()}
        out["default"] = self.queue
        return out

    def stats_all(self) -> dict:
        """``{label: stats dict}`` for every queue — the server's
        registry source (``--metrics_port`` on queue_server)."""
        out = {}
        for label, q in self.queues_by_name().items():
            out[label] = _queue_stats_payload(q)
        return out

    def all_queues(self) -> List[Any]:
        with self._queues_lock:  # snapshot: OPENs race with shutdown
            return [self.queue, *self._queues.values()]

    def begin_drain(self):
        """Stop accepting PUTs on every queue (producers see the dead-queue
        signal and exit cleanly) while GETs keep serving — the graceful
        half of teardown: consumers drain in-flight frames instead of
        losing them to an abrupt ``close_all`` (the reference's ``ray
        stop`` kills the actor with whatever the deque still holds).
        Propagates to the backing queues themselves so producers that
        BYPASS TCP (shm-backed deployments, queue_server --shm) are
        refused too, not just the ones speaking the wire protocol."""
        FLIGHT.record("begin_drain", port=self.port)
        self._draining = True
        for q in self.all_queues():
            drain = getattr(q, "begin_drain", None)
            if drain is not None:
                try:
                    drain()
                except Exception:
                    pass

    @property
    def draining(self) -> bool:
        return self._draining

    def depth(self) -> int:
        """Total items still queued across the default + named queues."""
        total = 0
        for q in self.all_queues():
            try:
                total += q.size()
            except Exception:
                pass
        return total

    def close_all(self):
        """Close the default + every named queue (server teardown: every
        blocked client must observe a dead transport, ``ray stop`` parity)."""
        FLIGHT.record("close_all", port=self.port)
        for q in self.all_queues():
            try:
                q.close()
            except Exception:
                pass

    def serve_background(self) -> "TcpQueueServer":
        from psana_ray_tpu.transport.evloop import EventLoop

        self._loop = EventLoop(self)
        t = threading.Thread(
            target=self._loop.run, daemon=True, name="tcp-evloop"
        )
        t.start()
        self._accept_thread = t
        self._threads.append(t)
        return self

    def _requeue(self, queue, items):
        """Put back items popped but never delivered (the client connection
        died mid-response) via the shared recovery path: queue HEAD so they
        precede any EOS markers already enqueued (a tally-driven consumer
        would otherwise stop without reading them), timed tail retries with
        a logged drop for backings without ``put_front`` (shm ring)."""
        from psana_ray_tpu.transport.recovery import return_to_queue

        if items:
            FLIGHT.record("requeue_in_flight", count=len(items))
        return_to_queue(queue, items, what="in-flight frame")

    def shutdown(self):
        self._stop.set()
        # evloop mode: kick the selector out of its wait so _stop is
        # observed immediately (no 0.2 s poll to lean on)
        if self._loop is not None:
            self._loop.wake()
        # join the accept loop BEFORE closing: a thread blocked inside
        # accept() keeps the listening socket alive past close(), so a
        # supervisor rebinding the same port immediately would race it
        # (the loop polls _stop every 0.2 s)
        t = getattr(self, "_accept_thread", None)
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)
        if self.replication is not None:
            # stop the shipping senders + coordinator sync and unmap the
            # replica logs AFTER the loop is down (no more 'V' appends)
            self.replication.shutdown()
        try:
            self._sock.close()
        except OSError:
            pass
        # close accepted connections too: an ESTABLISHED conn keeps the
        # port busy and would block a supervisor restarting the service on
        # the same address (clients reconnect-with-backoff and re-dial it)
        with self._conns_lock:
            conns, self._conns = self._conns, []
        for c in conns:
            # SHUT_RDWR first: close() alone does not interrupt a serve
            # thread blocked in recv() (the kernel file description stays
            # alive), which would leave a zombie thread answering a client
            # that should be reconnecting to the supervisor's new server
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass


class TcpQueueClient:
    """Client with the transport contract (put/get/size/get_wait/...).

    Transient connection failures (network blip, server restart under a
    supervisor) are RECONNECTED with exponential backoff and the
    interrupted operation retried once on the fresh connection — a named
    binding (OPEN) is replayed first, so the client lands on the same
    (namespace, queue_name) queue. Delivery across failures is
    AT-LEAST-ONCE, never silent loss: the server holds popped items as
    in-flight until the client's next request implicitly acknowledges the
    response (or BYE does, on clean disconnect), and re-enqueues them
    when the connection dies first — so a retried GET re-reads anything
    the dead connection had in the air, and a crashed client's unacked
    frames go to another consumer (possibly twice; records carry
    ``(shard_rank, event_idx)`` provenance for downstream dedup, and
    producer PUT retries are at-least-once the same way). Only RAW socket
    failures reconnect; an explicit server refusal (closed/draining
    queue) is a protocol answer, not an outage.

    A server that stays dead through every reconnect attempt surfaces as
    :class:`TransportClosed` from every contract method — the same signal
    a gracefully closed queue sends — so consumers' dead-transport
    handling (``DataReaderError``, batcher tail-flush) works for both
    (parity role: ``RayActorError``, reference ``data_reader.py:36-37``)."""

    def __init__(
        self,
        host: str,
        port: int,
        timeout_s: float = 30.0,
        namespace: Optional[str] = None,
        queue_name: Optional[str] = None,
        maxsize: int = 0,
        reconnect_tries: int = 4,
        reconnect_base_s: float = 0.5,
        pool: Optional[BufferPool] = None,
        put_window: int = DEFAULT_STREAM_WINDOW,
        codec: Optional[str] = None,
        tenant: Optional[str] = None,
        tenant_weight: int = 1,
    ):
        """``codec`` opts this connection into wire compression (ISSUE
        9): ``"auto"`` (ISSUE 15) DECIDES per connection from a brief
        link-rate probe at connect — compression on when the measured
        link is slower than the codec break-even rate (tunnels), off on
        fast LANs where the codec only burns CPU — re-decided on every
        reconnect, with a ``codec_auto_decision`` flight breadcrumb
        either way; a name (or comma list) advertises exactly those;
        None/"none" (the default) skips negotiation entirely — wire
        bytes stay byte-identical to pre-codec clients. The SERVER
        picks the codec (opcode 'Z'); an old server that answers the
        opcode with a protocol error degrades this client to
        uncompressed, loudly (flight breadcrumb), not fatally.

        ``tenant`` (ISSUE 12) names this connection's fair-share tenant
        and ``tenant_weight`` (1-64) its weight; both ride the same 'Z'
        capability exchange as ``key=value`` entries, so a tenant hello
        costs zero new opcodes and an old server that refuses 'Z'
        degrades the hello away with the codec (the connection then
        serves under the default tenant, loudly breadcrumbed, never
        fatally)."""
        self.host, self.port = host, port
        self._timeout_s = timeout_s
        # pooled receive staging: GET/B payloads land via recv_into in
        # recycled leases and decode zero-copy (consumer-side copy count
        # drops to the single batch-arena copy; see FrameBatcher.push_view)
        self._pool = pool if pool is not None else BufferPool.default()
        self._reconnect_tries = reconnect_tries
        self._reconnect_base_s = reconnect_base_s
        self._binding: Optional[tuple] = None  # (ns, name, maxsize) to replay
        # durable replay subscription to re-establish on reconnect:
        # (position sentinel, group) — always RESUME, so the server's
        # committed offset carries the position across drops
        self._replay_args: Optional[tuple] = None
        self._lock = threading.Lock()
        # streaming / windowed-put state — initialized BEFORE the dial so
        # _reconnect (reachable from __init__) can consult it safely.
        # _stream: once subscribed, this connection carries only pushes
        # and acks; request/response ops route to a lazy side channel.
        self._stream: Optional["TcpStreamReader"] = None
        self._side: Optional["TcpQueueClient"] = None
        # windowed pipelined PUT: monotonically numbered, unacked tail
        # kept for resend-on-reconnect (duplicates possible, holes never)
        self._put_seq = 0  # guarded-by: _lock
        self._put_unacked: deque = deque()  # (seq, item)  # guarded-by: _lock
        self._put_window = max(1, int(put_window))
        # wire compression (ISSUE 9): the advertised codec list, the
        # NEGOTIATED codec object (None = uncompressed), and the
        # old-peer latch that stops renegotiation storms on reconnect
        self._codec_arg = codec
        self._codec_names: Optional[List[str]] = None
        # "auto" (ISSUE 15, the parked ISSUE 9 follow-up): the codec is
        # DECIDED at connect from a brief link-rate probe — off on fast
        # LANs where the codec CPU only costs, on through slow tunnels
        # where the bandwidth win dominates — and RE-DECIDED on every
        # reconnect (the link may have changed). Explicit names still
        # mean exactly what they say.
        self._codec_auto = codec == "auto"
        if codec and codec != CODEC_NONE and not self._codec_auto:
            names = [n.strip() for n in codec.split(",") if n.strip()]
            for n in names:
                get_codec(n)  # fail fast on unknown names
            self._codec_names = names
        self._codec = None  # guarded-by: _lock
        self._codec_refused = False  # guarded-by: _lock
        # tenant hello (ISSUE 12): capability fields appended to the 'Z'
        # advert. Validated here so a malformed name fails fast instead
        # of desyncing the comma-separated wire list.
        self._hello_fields: List[str] = []
        if tenant is not None:
            if not tenant or any(c in tenant for c in ",=:\n"):
                raise ValueError(
                    f"tenant name {tenant!r} may not be empty or contain "
                    f"',' '=' ':' or newlines (it rides a comma-separated "
                    f"capability list)"
                )
            w = int(tenant_weight)
            if not 1 <= w <= 64:
                raise ValueError(
                    f"tenant_weight must be in [1, 64], got {tenant_weight}"
                )
            self._hello_fields.append(f"tenant={tenant}:{w}")
        self.tenant = tenant
        # the INITIAL dial goes through the same backoff machinery as
        # mid-stream drops: a consumer starting while the server is mid-
        # restart under a supervisor must wait it out, not crash with a
        # raw ConnectionRefusedError that dead-transport handlers (which
        # catch TransportClosed) don't recognize
        try:
            self._sock = socket.create_connection((host, port), timeout=timeout_s)
            self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except (ConnectionError, socket.timeout, OSError) as e:
            self._reconnect(e)  # raises TransportClosed when exhausted
        if namespace is not None or queue_name is not None:
            self.open(namespace or "default", queue_name or "default", maxsize)
        if self._codec_auto:
            with self._lock:
                try:
                    self._decide_auto_codec_raw()
                except (ConnectionError, socket.timeout, OSError) as e:
                    self._reconnect(e)  # re-probes + renegotiates itself
        if self._codec_names or self._hello_fields:
            self._negotiate()

    def open(self, namespace: str, queue_name: str, maxsize: int = 0):
        """Bind this connection to the server-side queue named
        ``(namespace, queue_name)``, get-or-creating it (``maxsize`` is
        used only on create; 0 = server default). Ray-GCS named-actor
        parity (reference ``shared_queue.py:33-38``, ``data_reader.py:20``)."""
        with self._lock:
            # binding stored under the lock: _reconnect reads it mid-
            # replay and a racing rebind must never hand it a torn value
            self._binding = (namespace, queue_name, maxsize)
            # no _retrying here: _reconnect itself replays the binding, so
            # the usual retry-the-exchange step would send a second OPEN
            try:
                self._open_raw(namespace, queue_name, maxsize)
            except (ConnectionError, socket.timeout, OSError) as e:
                self._reconnect(e)  # raises TransportClosed when it can't

    def _open_raw(self, namespace: str, queue_name: str, maxsize: int):
        # guarded-by-caller: _lock
        ns, nm = namespace.encode(), queue_name.encode()
        self._sock.sendall(
            _OP_OPEN
            + struct.pack("<H", len(ns)) + ns
            + struct.pack("<H", len(nm)) + nm
            + struct.pack("<I", maxsize)
        )
        self._status()

    # -- wire-compression negotiation (opcode 'Z', ISSUE 9) ---------------
    def _negotiate(self):
        with self._lock:
            try:
                self._negotiate_raw()
            except (ConnectionError, socket.timeout, OSError) as e:
                self._reconnect(e)  # renegotiates itself on success

    def _negotiate_raw(self):
        """One 'Z' exchange on the current socket. A peer that predates
        the opcode answers protocol-error (and drops the connection):
        that DEGRADES this client to uncompressed — latched, so
        reconnects stop re-asking — instead of failing the transport.
        Caller holds ``self._lock``."""
        # guarded-by-caller: _lock
        if self._codec_refused:
            return
        # codec names first (the server picks the first it knows), then
        # the capability fields; with no codecs the explicit "none"
        # keeps the server's pick unambiguous
        advert = [*(self._codec_names or [CODEC_NONE]), *self._hello_fields]
        names = ",".join(advert).encode()
        self._sock.sendall(_OP_CODEC + struct.pack("<H", len(names)) + names)
        try:
            self._status()
        except RuntimeError:
            # old peer: 'E' answer, connection about to close server-side.
            # Degrade to uncompressed; the next op reconnects normally.
            self._codec = None
            self._codec_refused = True
            FLIGHT.record(
                "codec_refused", host=self.host, port=self.port
            )
            return
        (n,) = struct.unpack("<H", _recv_exact(self._sock, 2))
        try:
            chosen = _recv_exact(self._sock, n).decode()
            self._codec = get_codec(chosen)
        except ValueError:
            # buggy peer/proxy: a name we never advertised (or not even
            # UTF-8). Same contract as the old-peer refusal: degrade to
            # uncompressed and latch, never fail the transport.
            self._codec = None
            self._codec_refused = True
            FLIGHT.record(
                "codec_refused", host=self.host, port=self.port
            )
            return
        CODEC_STATS.negotiated(chosen)
        FLIGHT.record(
            "codec_negotiated", host=self.host, port=self.port, codec=chosen
        )

    # -- link-rate probe + auto codec decision (ISSUE 15) ------------------
    # Bandwidth below which wire compression wins on this build: the
    # pure-numpy codec moves ~200 MB/s at ~3x on detector frames, so the
    # break-even link is ~rate x (1 - 1/ratio) ~ 133 MB/s; 125 keeps a
    # margin on the codec side (a borderline LAN stays raw — the codec
    # only costs CPU there). PSANA_AUTO_CODEC_MB_S overrides.
    AUTO_CODEC_THRESHOLD_MB_S = 125.0
    # Padded control-RPC size per bandwidth probe: large enough that the
    # transfer time dominates RTT on any link slow enough to matter,
    # small enough to stay far under the 1 MB control-plane cap. Three
    # probes ship back to back and the MEDIAN decides — a token-bucket
    # burst (or warm TCP window) can fake one fast sample, a scheduler
    # blip one slow sample; the median survives either.
    AUTO_CODEC_PROBE_BYTES = 640 * 1024

    def _probe_link_raw(self) -> tuple:
        """Measure (link MB/s, RTT s) on the current socket: RTT from
        two 'A' anchor exchanges (min), bandwidth from timing padded 'N'
        ping RPCs through the link (the server must read the whole
        request before answering, so elapsed ~ RTT + bytes/bandwidth).
        Runs only at connect/reconnect time, pre-stream — nothing is in
        flight to desync. Caller holds ``self._lock``."""
        # guarded-by-caller: _lock
        sock = self._sock
        rtt = float("inf")
        for _ in range(2):
            t0 = time.monotonic()
            sock.sendall(_OP_ANCHOR + struct.pack("<dd", time.time(), t0))
            self._status()
            _recv_exact(sock, 16)
            rtt = min(rtt, time.monotonic() - t0)
        # hand-assembled so the bytes match the server's O(1) ping
        # prefix fast path (evloop._cluster_finish) — a json.dumps of a
        # 640 KB string costs client time the measurement would absorb
        body = (
            b'{"op": "ping", "pad": "'
            + b"x" * self.AUTO_CODEC_PROBE_BYTES
            + b'"}'
        )
        samples = []
        for _ in range(3):
            t0 = time.monotonic()
            sock.sendall(_OP_CLUSTER + struct.pack("<I", len(body)) + body)
            self._status()
            (n,) = struct.unpack("<I", _recv_exact(sock, 4))
            _recv_exact(sock, n)
            elapsed = time.monotonic() - t0
            samples.append(len(body) / max(elapsed - rtt, 1e-6) / 1e6)
        # median of three: a token-bucket burst can fake ONE fast sample
        # (the bucket drains under the first probe), a scheduler blip
        # can fake ONE slow one — the median survives either
        return sorted(samples)[1], rtt

    def _decide_auto_codec_raw(self) -> None:
        """One-shot ``codec="auto"`` decision for THIS connection: probe
        the link, compare against the codec break-even rate, and set the
        advert the next 'Z' exchange carries. A probe the peer refuses
        (protocol error from an odd proxy) decides FOR compression —
        the bandwidth-conservative fallback — and never fails the
        transport. Caller holds ``self._lock``."""
        # guarded-by-caller: _lock
        import os

        mb_s = rtt = None
        try:
            mb_s, rtt = self._probe_link_raw()
        except (ConnectionError, socket.timeout, OSError):
            raise  # real socket death: the caller's reconnect owns it
        except Exception:  # noqa: BLE001 — a refused probe decides, not dies
            pass
        try:
            threshold = float(
                os.environ.get(
                    "PSANA_AUTO_CODEC_MB_S", self.AUTO_CODEC_THRESHOLD_MB_S
                )
            )
        except ValueError:  # a typo'd override decides at the default,
            threshold = self.AUTO_CODEC_THRESHOLD_MB_S  # never fails connect
        slow = mb_s is None or mb_s < threshold
        self._codec_names = (available_codecs() or None) if slow else None
        if self._codec_names is None:
            # decided OFF: drop any previously negotiated codec NOW —
            # with nothing to advertise no 'Z' follows, and a stale
            # codec object would keep compressing onto a fresh
            # connection that never negotiated
            self._codec = None
        FLIGHT.record(
            "codec_auto_decision",
            host=self.host, port=self.port,
            link_mb_s=round(mb_s, 1) if mb_s is not None else None,
            rtt_ms=round(rtt * 1e3, 2) if rtt is not None else None,
            threshold_mb_s=threshold,
            codec_on=bool(self._codec_names),
        )

    def _encode_for_wire(self, item):
        """codec.encode_for_wire under this connection's negotiated
        codec — every put path calls this under the client lock (the
        negotiated codec is per-connection state a racing reconnect
        may flip). See the helper for the lease/pass-through
        contract."""
        # guarded-by-caller: _lock
        return _wire_encode(item, self._codec, self._pool)

    @property
    def codec_name(self) -> Optional[str]:
        """The negotiated wire codec's name, or None when raw."""
        with self._lock:
            codec = self._codec
        return getattr(codec, "name", None) if codec is not None else None

    def _reconnect(self, cause: BaseException, deadline: Optional[float] = None):
        """Re-dial with exponential backoff and replay the named binding.
        Raises TransportClosed when every attempt fails — or when
        ``deadline`` (time.monotonic()) passes, so timeout-bearing callers
        (get_wait/put_wait/get_batch) keep their latency contract instead
        of blocking through the full backoff cycle. Caller holds
        ``self._lock`` (except from __init__, where no peer exists yet
        and the windowed/stream state is still empty)."""
        # guarded-by-caller: _lock
        import time

        # flight-recorder breadcrumb: reconnect storms are the leading
        # indicator in most wedged-run postmortems
        FLIGHT.record(
            "reconnect", host=self.host, port=self.port, cause=repr(cause)
        )
        sock = getattr(self, "_sock", None)
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        delay = self._reconnect_base_s
        last: BaseException = cause
        for attempt in range(self._reconnect_tries):
            now = time.monotonic()
            if deadline is not None and now >= deadline:
                break
            if attempt:  # back off BETWEEN dials — never after the last
                # FULL JITTER (uniform over [0, envelope)): the envelope
                # doubles per attempt but the actual sleep is randomized
                # — a deterministic schedule makes every client that
                # watched the same server die redial in LOCKSTEP, and
                # after an owner death that stampede lands squarely on
                # the freshly promoted follower (ISSUE 11); the spread
                # is pinned by test_replication.py
                sleep_s = random.uniform(0.0, delay)
                if deadline is not None:
                    sleep_s = min(sleep_s, max(0.0, deadline - now))
                time.sleep(sleep_s)
                delay = min(delay * 2, 5.0)
                if deadline is not None and time.monotonic() >= deadline:
                    break
            dial_timeout = self._timeout_s
            if deadline is not None:
                dial_timeout = max(0.05, min(dial_timeout, deadline - time.monotonic()))
            try:
                self._sock = socket.create_connection(
                    (self.host, self.port), timeout=dial_timeout
                )
                self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                if self._binding is not None:
                    self._open_raw(*self._binding)
                if (
                    self._codec_auto
                    and not self._codec_refused
                    and deadline is None
                ):
                    # "auto" is a per-CONNECTION decision: the fresh
                    # link may be a different link (failover through a
                    # tunnel, a recovered LAN) — re-probe, re-decide.
                    # NOT under a caller deadline: the ~2 MB probe
                    # cannot fit a clipped dial timeout on exactly the
                    # slow links it exists for (the previous decision
                    # carries; the next deadline-less reconnect
                    # re-decides). Reset the dial timeout first — the
                    # probe must run under the patient one.
                    self._sock.settimeout(self._timeout_s)
                    self._decide_auto_codec_raw()
                if self._codec_names or self._hello_fields:
                    # renegotiate BEFORE any payload-bearing replay: the
                    # windowed resend below must know whether this
                    # connection compresses (an old-peer refusal latches
                    # and the resend simply goes out raw), and the
                    # tenant hello must re-bind the fresh connection's
                    # fair-share identity before it carries traffic
                    self._negotiate_raw()
                if self._replay_args is not None:
                    # re-open the replay cursor at the group's committed
                    # offset: everything unconfirmed redelivers (dupes
                    # possible, holes never)
                    pos, rg = self._replay_args
                    g = rg.encode()
                    self._sock.sendall(
                        _OP_REPLAY + struct.pack("<QH", pos, len(g)) + g
                    )
                    if self._status() == _ST_OK:
                        _recv_exact(self._sock, 16)
                    else:
                        # the server came back WITHOUT a log for this
                        # queue: continuing would silently turn this
                        # non-destructive replay reader into a live
                        # consumer (popping frames live consumers own).
                        # Fail the transport loudly instead.
                        FLIGHT.record(
                            "replay_resubscribe_refused",
                            host=self.host, port=self.port,
                        )
                        try:
                            self._sock.close()
                        except OSError:
                            pass
                        raise TransportClosed(
                            f"replay re-subscription refused by "
                            f"{self.host}:{self.port} — the restarted "
                            f"server has no segment log for this queue; "
                            f"refusing to degrade into a live consumer"
                        )
                # windowed-put resend invariant: the entire unacked tail
                # goes out FIRST, in sequence order, before any new
                # request touches the fresh connection — the server may
                # see duplicates (at-least-once) but never a hole
                if self._put_unacked:
                    self._resend_put_window()
                # a streamed connection re-subscribes with its original
                # credit window; frames the dead connection had in the
                # air were re-enqueued server-side and redeliver here
                if self._stream is not None:
                    self._sock.sendall(
                        _OP_STREAM + struct.pack("<I", self._stream.window)
                    )
                    self._stream.reset_after_reconnect()
                    FLIGHT.record(
                        "stream_resubscribe", host=self.host, port=self.port
                    )
                # the clipped dial timeout bounded THIS handshake; the
                # connection it produced must run under the configured
                # timeout, or every later server-side blocking wait
                # (opcode 'D' parks up to the caller's own deadline)
                # outlives the poisoned recv timeout and reads as a
                # fresh death — reconnect storm, then TransportClosed
                # on a perfectly healthy server
                self._sock.settimeout(self._timeout_s)
                return
            except (ConnectionError, socket.timeout, OSError) as e:
                last = e
        deadline_hit = deadline is not None and time.monotonic() >= deadline
        raise TransportClosed(
            f"connection to queue server {self.host}:{self.port} died and "
            f"reconnect attempts failed (tries={self._reconnect_tries}"
            f"{', caller deadline hit' if deadline_hit else ''}): {last}"
        ) from last

    def _retrying(self, do, deadline: Optional[float] = None):
        """Run one request/response exchange; on a RAW socket failure,
        reconnect (bounded by ``deadline`` when given) and retry the
        exchange once. TransportClosed from ``_status`` (server's explicit
        refusal) passes straight through. Caller holds ``self._lock``.

        Pending windowed-put acks are fully drained FIRST: their
        responses precede this exchange's in the byte stream, so a
        request issued over an outstanding window would read a put ack
        as its own status and desync the connection."""
        # guarded-by-caller: _lock
        if self._put_unacked and not self._drain_put_acks(0, deadline):
            raise TransportClosed(
                f"windowed puts to {self.host}:{self.port} still "
                f"unacknowledged at the caller's deadline"
            )
        try:
            return do()
        except (ConnectionError, socket.timeout, OSError) as e:
            self._reconnect(e, deadline)  # raises TransportClosed when it can't
            try:
                return do()
            except (ConnectionError, socket.timeout, OSError) as e2:
                raise TransportClosed(
                    f"connection to queue server {self.host}:{self.port} "
                    f"died again right after a successful reconnect: {e2}"
                ) from e2

    # -- windowed pipelined PUT (opcode 'W') ------------------------------
    def _resend_put_window(self):
        """Resend the whole unacknowledged tail on a fresh connection, in
        sequence order (the windowed-put resend invariant — see the
        module docstring's streaming contract). Called from _reconnect
        with the new socket already dialed and the binding replayed."""
        # guarded-by-caller: _lock
        for seq, item in list(self._put_unacked):
            parts, clease = self._encode_for_wire(item)
            try:
                head = _OP_PUT_SEQ + struct.pack(
                    "<QI", seq, _parts_nbytes(parts)
                )
                _sendmsg_all(self._sock, [head, *parts])
            finally:
                if clease is not None:
                    clease.release()
        n = len(self._put_unacked)
        if n:
            STREAM.resent(n)
            FLIGHT.record(
                "put_window_resend", count=n, host=self.host, port=self.port
            )

    def _drain_put_acks(self, max_unacked: int, deadline: Optional[float]) -> bool:
        """Read windowed-put acks until at most ``max_unacked`` remain
        in flight (False when ``deadline`` expires first — nothing is
        lost; the tail stays queued for resend).

        An OVERDUE ack is BACKPRESSURE, not death: the server delays
        acks while its queue is full (the 'W' handler's blocking
        enqueue), for arbitrarily long — so a quiet wire keeps waiting
        in bounded slices instead of reconnecting (a reconnect here
        would resend the whole window into the already-full queue:
        duplicate amplification on every timeout, triggered by ordinary
        backpressure). Only a broken connection (EOF/reset) reconnects
        and resends, and that reconnect runs the FULL backoff envelope
        regardless of ``deadline`` — a supervisor restart mid-window
        must not kill the stream; the deadline bounds waiting, not
        availability recovery. An explicit 'X' raises TransportClosed.
        Caller holds ``self._lock``."""
        # guarded-by-caller: _lock
        while len(self._put_unacked) > max_unacked:
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                return False
            slice_s = self._timeout_s
            if remaining is not None:
                slice_s = min(slice_s, remaining)
            try:
                # the ack-wait slice applies to the status byte only;
                # once it arrives, the 8-byte seq follows at wire speed
                # under the patient timeout (a timeout mid-ack would
                # desync — that one IS treated as a raw failure)
                try:
                    self._sock.settimeout(slice_s)
                    try:
                        st = self._status()
                    except socket.timeout:
                        continue  # overdue = backpressured, keep waiting
                finally:
                    try:
                        self._sock.settimeout(self._timeout_s)
                    except OSError:
                        pass
                if st != _ST_OK:
                    raise RuntimeError(
                        f"protocol error in windowed-put ack: {st!r}"
                    )
                (seq,) = struct.unpack("<Q", _recv_exact(self._sock, 8))
            except (ConnectionError, socket.timeout, OSError) as e:
                self._reconnect(e)  # full envelope; resends the tail itself
                continue
            while self._put_unacked and self._put_unacked[0][0] <= seq:
                self._put_unacked.popleft()
            STREAM.put_depth(len(self._put_unacked))
        return True

    def put_pipelined(self, item: Any, deadline: Optional[float] = None) -> bool:
        """Windowed pipelined put: send without waiting for the status,
        keeping up to ``put_window`` sequence-numbered puts in flight
        (backpressure arrives as delayed acks from the server's blocking
        enqueue — no refusal/retry round trips). Returns False when the
        window is still full at ``deadline`` (the item was NOT sent —
        retry it); raises TransportClosed when the transport is dead
        (``deadline`` bounds the wait for window space, NOT the
        reconnect envelope — a supervisor restart mid-window rides the
        full backoff like every other op). On reconnect the unacked
        tail is resent: duplicates possible, holes never. Call
        :meth:`flush_puts` before relying on durability (EOS,
        shutdown)."""
        if self._stream is not None:
            return self._side_channel().put_pipelined(item, deadline)
        with self._lock:
            if not self._drain_put_acks(self._put_window - 1, deadline):
                return False
            # encode under the lock: the negotiated codec is per-
            # connection state a racing reconnect may flip
            parts, clease = self._encode_for_wire(item)
            try:
                n = _parts_nbytes(parts)
                if n > _MAX_PAYLOAD:  # fail fast: peer would drop the conn
                    raise ValueError(
                        f"payload of {n} bytes exceeds wire maximum "
                        f"{_MAX_PAYLOAD}"
                    )
                self._put_seq += 1
                seq = self._put_seq
                self._put_unacked.append((seq, item))
                STREAM.put_depth(len(self._put_unacked))
                head = _OP_PUT_SEQ + struct.pack("<QI", seq, n)
                try:
                    _sendmsg_all(self._sock, [head, *parts])
                except (ConnectionError, socket.timeout, OSError) as e:
                    # full-envelope reconnect (no caller deadline: see
                    # the docstring) resends the whole tail — including
                    # this item, already appended above
                    self._reconnect(e)
            finally:
                if clease is not None:
                    clease.release()
            return True

    def flush_puts(self, deadline: Optional[float] = None) -> bool:
        """Block until every windowed put is acknowledged (False when
        ``deadline`` expires first; the tail stays in flight)."""
        if self._stream is not None:
            side = self._side
            return True if side is None else side.flush_puts(deadline)
        with self._lock:
            return self._drain_put_acks(0, deadline)

    # -- streaming consumption (opcodes 'M'/'K') --------------------------
    def stream_open(self, window: int = DEFAULT_STREAM_WINDOW) -> "TcpStreamReader":
        """Subscribe this connection to server-push delivery with an
        initial credit count of ``window`` frames (idempotent — the
        first subscription wins). From here on the connection carries
        only pushes and acks: reads (get/get_wait/get_batch) drain the
        stream, while puts/probes route over a lazily opened side
        channel (see :meth:`_side_channel`)."""
        with self._lock:
            if self._stream is not None:
                return self._stream
            if self._replay_args is not None:
                # the server rejects 'M' on a replay connection (replay
                # is pull-mode by design) and kills the connection; the
                # protocol-dialogue checker pins this guard client-side
                raise RuntimeError(
                    "stream_open on a replay connection — replay is "
                    "pull-mode; use a dedicated (non-replay) client"
                )
            window = max(1, int(window))

            def _do():
                self._sock.sendall(_OP_STREAM + struct.pack("<I", window))

            self._retrying(_do)
            self._stream = TcpStreamReader(self, window)
            STREAM.ensure_registered()
            return self._stream

    def get_batch_stream(
        self, max_items: int, timeout: Optional[float] = None
    ) -> List[Any]:
        """Streamed drain (subscribing with the default credit window on
        first use): returns whatever the server has already pushed, up
        to ``max_items``, blocking at most ``timeout`` for the first
        frame. The batcher prefers this entry point over ``get_batch``
        — zero request round trips, zero empty-queue polls."""
        return self.stream_open().get_batch_stream(max_items, timeout)

    def _side_channel(self) -> "TcpQueueClient":
        """A second plain connection for the rare request/response ops a
        streamed client still needs (EOS duplicate put-backs, probes):
        any such opcode on the streamed socket itself would desync the
        push framing. Replays the named binding, shares the pool."""
        side = self._side
        if side is None:
            ns, nm, ms = self._binding or (None, None, 0)
            # "auto" inherits THIS connection's probe decision instead
            # of re-probing: the side channel shares the link
            codec_arg = self._codec_arg
            with self._lock:
                names = self._codec_names
                put_window = self._put_window
            if self._codec_auto:
                codec_arg = ",".join(names) if names else None
            side = TcpQueueClient(
                self.host,
                self.port,
                timeout_s=self._timeout_s,
                namespace=ns,
                queue_name=nm,
                maxsize=ms,
                reconnect_tries=self._reconnect_tries,
                reconnect_base_s=self._reconnect_base_s,
                pool=self._pool,
                put_window=put_window,
                codec=codec_arg,
            )
            self._side = side
        return side

    # -- contract ---------------------------------------------------------
    def put(self, item: Any, deadline: Optional[float] = None) -> bool:
        if self._stream is not None:  # streamed conn: puts use the side channel
            return self._side_channel().put(item, deadline)

        # scatter-gather: the frame payload goes to the kernel straight
        # from the record's panel memory (wire_parts memoryview) — no
        # to_bytes() serialization copy, no request-assembly concat copy.
        # A negotiated codec stages the compressed form in a pool lease,
        # released once the exchange is over. Encoding happens INSIDE
        # the retried exchange: a reconnect may renegotiate (or an
        # old-peer refusal may downgrade) the codec, and the retry must
        # send what THIS connection speaks, never stale compressed parts.
        def _do():
            parts, clease = self._encode_for_wire(item)
            try:
                n = _parts_nbytes(parts)
                if n > _MAX_PAYLOAD:  # fail fast: peer would drop the conn
                    raise ValueError(
                        f"payload of {n} bytes exceeds wire maximum "
                        f"{_MAX_PAYLOAD}"
                    )
                head = _OP_PUT + struct.pack("<I", n)
                _sendmsg_all(self._sock, [head, *parts])
                return self._status() == _ST_OK
            finally:
                if clease is not None:
                    clease.release()

        with self._lock:
            return self._retrying(_do, deadline)

    def get(self, deadline: Optional[float] = None) -> Any:
        if self._stream is not None:  # drain already-pushed frames only
            return self._stream.get_wait_stream(0.0)

        def _do():
            self._sock.sendall(_OP_GET)
            st = self._status()
            if st == _ST_NO:
                return EMPTY
            (n,) = struct.unpack("<I", _recv_exact(self._sock, 4))
            return _recv_payload(self._sock, n, self._pool)

        with self._lock:
            return self._retrying(_do, deadline)

    # size()/stats() are observability probes (scrape threads, heartbeats,
    # the stall detector): they must fail FAST on a dead server — the full
    # reconnect backoff cycle (minutes, serialized under self._lock) would
    # stall /metrics exactly during the incident the probe exists to show.
    # Data opcodes (put/get) keep the patient default.
    PROBE_DEADLINE_S = 5.0

    def size(self, deadline: Optional[float] = None) -> int:
        import time

        if self._stream is not None:  # probes would desync the push framing
            return self._side_channel().size(deadline)

        def _do():
            self._sock.sendall(_OP_SIZE)
            self._status()
            (n,) = struct.unpack("<I", _recv_exact(self._sock, 4))
            return n

        if deadline is None:
            deadline = time.monotonic() + self.PROBE_DEADLINE_S
        with self._lock:
            return self._retrying(_do, deadline)

    def anchor(self, deadline: Optional[float] = None) -> dict:
        """Clock ping/anchor exchange (opcode 'A', the stats RPC's tracing
        sibling): returns the server's (wall, mono) pair bracketed by this
        process's own samples, plus the measured RTT — exactly what
        :func:`psana_ray_tpu.obs.tracing.exchange_anchors` spools so the
        trace merge tool can align this host's clock to the server's."""
        if self._stream is not None:
            return self._side_channel().anchor(deadline)

        def _do():
            t0_wall, t0_mono = time.time(), time.monotonic()
            self._sock.sendall(_OP_ANCHOR + struct.pack("<dd", t0_wall, t0_mono))
            self._status()
            peer_wall, peer_mono = struct.unpack("<dd", _recv_exact(self._sock, 16))
            t1_wall, t1_mono = time.time(), time.monotonic()
            return {
                "send_wall": t0_wall,
                "send_mono": t0_mono,
                "recv_wall": t1_wall,
                "recv_mono": t1_mono,
                "peer_wall": peer_wall,
                "peer_mono": peer_mono,
                "rtt_s": t1_mono - t0_mono,
                "peer": f"{self.host}:{self.port}",
            }

        if deadline is None:
            deadline = time.monotonic() + self.PROBE_DEADLINE_S
        with self._lock:
            return self._retrying(_do, deadline)

    def stats(self, deadline: Optional[float] = None) -> dict:
        """Queue-health RPC (opcode 'T'): depth, high-water mark, put/get
        counters, liveness ages of the queue this connection is bound to —
        the cross-host half of the observability story (the stall detector
        and the Prometheus endpoint read the same dict server-side)."""
        import time

        if self._stream is not None:
            return self._side_channel().stats(deadline)

        def _do():
            self._sock.sendall(_OP_STATS)
            self._status()
            (n,) = struct.unpack("<I", _recv_exact(self._sock, 4))
            return json.loads(_recv_exact(self._sock, n).decode())

        if deadline is None:
            deadline = time.monotonic() + self.PROBE_DEADLINE_S
        with self._lock:
            return self._retrying(_do, deadline)

    def cluster_rpc(self, payload: dict, deadline: Optional[float] = None) -> dict:
        """Consumer-group coordination RPC (opcode 'N'): send one JSON
        request to the server's :class:`psana_ray_tpu.cluster.
        coordinator.GroupRegistry` and return its JSON answer. Control
        plane, so it fails fast like the other probes (PROBE_DEADLINE_S)
        — a dead coordinator must surface as TransportClosed promptly,
        not hang a rebalance behind the full reconnect envelope."""
        import time

        if self._stream is not None:  # would desync the push framing
            return self._side_channel().cluster_rpc(payload, deadline)
        body = json.dumps(payload).encode()

        def _do():
            self._sock.sendall(_OP_CLUSTER + struct.pack("<I", len(body)) + body)
            self._status()
            (n,) = struct.unpack("<I", _recv_exact(self._sock, 4))
            return json.loads(_recv_exact(self._sock, n).decode())

        if deadline is None:
            deadline = time.monotonic() + self.PROBE_DEADLINE_S
        with self._lock:
            return self._retrying(_do, deadline)

    # -- durable log surface (opcodes 'R'/'J', ISSUE 8) -------------------
    def replay_open(self, from_offset=None, group: str = "replay") -> dict:
        """Switch this connection's reads to a NON-DESTRUCTIVE replay
        cursor over the bound queue's retained segment-log range for
        ``group`` (durable queues only — raises RuntimeError otherwise).
        ``from_offset``: ``None``/``"resume"`` resumes at the group's
        committed offset, ``"begin"`` starts at the earliest retained
        record, an int is an explicit offset. Live consumers are
        undisturbed. Delivered records are committed for the group at
        this connection's implicit-ACK points, so a crashed replay
        consumer re-opens with ``resume`` and loses nothing (duplicates
        possible since the last commit). Returns ``{"start", "end"}``.
        On reconnect the subscription replays itself at ``resume``."""
        from psana_ray_tpu.storage.log import REPLAY_BEGIN, REPLAY_RESUME

        if self._stream is not None:
            # a streamed connection carries only pushes and acks; 'R'
            # on it is a protocol error server-side, and a side-channel
            # replay would NOT redirect THIS connection's reads — there
            # is no sane silent fallback, so refuse loudly
            raise RuntimeError(
                "replay_open on a streamed connection — replay is "
                "pull-mode; use a dedicated (non-streamed) client"
            )
        if from_offset is None or from_offset == "resume":
            pos = REPLAY_RESUME
        elif from_offset == "begin":
            pos = REPLAY_BEGIN
        else:
            pos = int(from_offset)
        g = group.encode()

        def _do():
            self._sock.sendall(
                _OP_REPLAY + struct.pack("<QH", pos, len(g)) + g
            )
            st = self._status()
            if st != _ST_OK:
                raise RuntimeError(
                    f"replay refused: queue {self._binding or 'default'} "
                    f"on {self.host}:{self.port} has no segment log "
                    f"(start the server with --durable_dir)"
                )
            start, end = struct.unpack("<QQ", _recv_exact(self._sock, 16))
            return {"start": start, "end": end}

        with self._lock:
            out = self._retrying(_do)
            # reconnects re-subscribe at the group's committed offset —
            # the server-side commit state carries the position
            self._replay_args = (REPLAY_RESUME, group)
        # client-side breadcrumb: the consumer process's own flight ring
        # (and its --status_interval `durable[...]` bracket) must show
        # the replay even when the server runs elsewhere
        FLIGHT.record(
            "replay_open", host=self.host, port=self.port, group=group,
            start=out["start"], end=out["end"],
        )
        return out

    def commit_offset(
        self, offset=None, group: str = "", deadline: Optional[float] = None
    ) -> bool:
        """Persist a committed offset for ``group`` on the bound durable
        queue ('J'). ``offset=None`` commits everything DELIVERED to
        this connection's replay cursor so far (the explicit form of the
        implicit ack). False when the queue has no log."""
        from psana_ray_tpu.storage.log import COMMIT_DELIVERED

        if self._stream is not None:
            return self._side_channel().commit_offset(offset, group, deadline)
        pos = COMMIT_DELIVERED if offset is None else int(offset)
        g = group.encode()

        def _do():
            self._sock.sendall(
                _OP_COMMIT + struct.pack("<QH", pos, len(g)) + g
            )
            return self._status() == _ST_OK

        with self._lock:
            return self._retrying(_do, deadline)

    def promote(
        self, namespace: str, queue_name: str, deadline: Optional[float] = None
    ) -> Optional[dict]:
        """Replication failover ('Y', ISSUE 11): ask this server to
        promote its replica log for ``(namespace, queue_name)`` into the
        live durable queue — sent by the cluster client against a
        partition's new owner BEFORE opening it, so the promoted backlog
        (and retained replay range) is what OPEN mounts. Returns
        ``{"start", "end"}`` (the retained range) or None when the
        server holds no replica (the partition starts empty there).
        Control plane: fails fast like the probes."""
        if self._stream is not None:  # would desync the push framing
            return self._side_channel().promote(namespace, queue_name, deadline)
        ns, nm = namespace.encode(), queue_name.encode()

        def _do():
            self._sock.sendall(
                _OP_PROMOTE
                + struct.pack("<H", len(ns)) + ns
                + struct.pack("<H", len(nm)) + nm
            )
            st = self._status()
            if st != _ST_OK:
                return None
            start, end = struct.unpack("<QQ", _recv_exact(self._sock, 16))
            return {"start": start, "end": end}

        if deadline is None:
            deadline = time.monotonic() + self.PROBE_DEADLINE_S
        with self._lock:
            return self._retrying(_do, deadline)

    def unacked_puts(self) -> List[Any]:
        """Snapshot of the windowed-put items not yet acknowledged by
        THIS server, oldest first. The cluster client reads it when a
        server dies for good (reconnects exhausted): the tail must be
        resent to the partition's NEW owner — the PR 5 resend invariant
        carried across servers (duplicates possible, holes never)."""
        with self._lock:
            return [item for (_seq, item) in self._put_unacked]

    def close_remote(self):
        """Close the remote queue (fault-injection / teardown)."""
        if self._stream is not None:
            return self._side_channel().close_remote()

        def _do():
            self._sock.sendall(_OP_CLOSE)
            self._status()

        with self._lock:
            return self._retrying(_do)

    # -- blocking helpers (same surface as RingBuffer) --------------------
    # The surviving client-side sleeps below are deadline-checked every
    # iteration and only run BETWEEN server-side bounded waits (the
    # server already blocked _SERVER_WAIT_CAP_S for the condition), so
    # total blocking is caller-bounded — the latency contract the
    # blocking-hot-path lint checker's TcpQueueClient exclusion documents.
    def get_wait(self, timeout: Optional[float] = None, poll_s: float = 0.001) -> Any:
        import time

        deadline = None if timeout is None else time.monotonic() + timeout
        if self._stream is not None:  # streamed: the push IS the wait
            while True:
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    return EMPTY
                item = self._stream.get_wait_stream(remaining)
                if item is not EMPTY:
                    return item
                if deadline is not None and time.monotonic() >= deadline:
                    return EMPTY
        while True:
            # server-side bounded wait ('D', max_items=1): an empty queue
            # costs one round trip per cap interval, not one per poll
            out = self._get_batch_once(1, deadline, self._server_wait(deadline))
            if out:
                return out[0]
            if deadline is not None and time.monotonic() >= deadline:
                return EMPTY
            time.sleep(poll_s)

    @staticmethod
    def _server_wait(deadline: Optional[float]) -> float:
        """How long the SERVER should block for this round trip: the full
        cap, clipped to the caller's remaining deadline."""
        if deadline is None:
            return _SERVER_WAIT_CAP_S
        return min(_SERVER_WAIT_CAP_S, max(0.0, deadline - time.monotonic()))

    def put_wait(
        self, item: Any, timeout: Optional[float] = None, poll_s: float = 0.001
    ) -> bool:
        import time

        if self._stream is not None:
            return self._side_channel().put_wait(item, timeout, poll_s)
        deadline = None if timeout is None else time.monotonic() + timeout
        # bill this thread's CPU to "enqueue" for the continuous
        # profiler until the put resolves (restored in the finally)
        prev_tag = swap_stage(TAG_ENQUEUE)
        # the compressed bytes depend only on (item, codec), so the
        # encode is CACHED across full-queue retries — paying the codec
        # once per frame, not once per bounded-wait round trip — and
        # invalidated when a reconnect mid-attempt renegotiates the
        # codec (get_codec returns per-name singletons, so identity is
        # the negotiation generation; the retry then re-encodes to what
        # this connection now speaks). The staging lease lives until
        # the put resolves.
        cached = None  # (codec, parts, staging_lease)
        try:
            while True:
                # server-side bounded wait for SPACE ('U'): a full queue
                # costs one round trip per cap interval, not one
                # rejected put per poll tick
                wait_ms = int(self._server_wait(deadline) * 1000)

                def _do():
                    nonlocal cached
                    codec = self._codec
                    if cached is None or cached[0] is not codec:
                        if cached is not None and cached[2] is not None:
                            cached[2].release()
                        cached = None
                        parts, clease = self._encode_for_wire(item)
                        cached = (codec, parts, clease)
                    parts = cached[1]
                    n = _parts_nbytes(parts)
                    if n > _MAX_PAYLOAD:  # fail fast
                        raise ValueError(
                            f"payload of {n} bytes exceeds wire maximum "
                            f"{_MAX_PAYLOAD}"
                        )
                    head = _OP_PUT_WAIT + struct.pack("<II", wait_ms, n)
                    _sendmsg_all(self._sock, [head, *parts])
                    return self._status() == _ST_OK

                with self._lock:
                    if self._retrying(_do, deadline):
                        return True
                if deadline is not None and time.monotonic() >= deadline:
                    return False
                time.sleep(poll_s)
        finally:
            set_stage(prev_tag)
            if cached is not None and cached[2] is not None:
                cached[2].release()

    def get_batch(
        self,
        max_items: int,
        timeout: Optional[float] = None,
        poll_s: float = 0.001,
    ) -> List[Any]:
        """Drain up to ``max_items`` in ONE round trip; when the remote
        queue is momentarily empty the SERVER blocks for the first item
        (opcode 'D', bounded by ``timeout`` and the server cap), with
        ``poll_s`` pacing retries between bounded waits."""
        import time

        deadline = None if timeout is None else time.monotonic() + timeout
        if self._stream is not None:
            while True:
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    return []
                out = self._stream.get_batch_stream(max_items, remaining)
                if out:
                    return out
                if deadline is not None and time.monotonic() >= deadline:
                    return []
        while True:
            out = self._get_batch_once(
                max_items, deadline, self._server_wait(deadline)
            )
            if out:
                return out
            if deadline is not None and time.monotonic() >= deadline:
                return []
            time.sleep(poll_s)

    def _get_batch_once(
        self,
        max_items: int,
        deadline: Optional[float] = None,
        server_wait_s: float = 0.0,
    ) -> List[Any]:
        def _do():
            if server_wait_s > 0:
                self._sock.sendall(
                    _OP_GET_BATCH_WAIT
                    + struct.pack("<II", max_items, int(server_wait_s * 1000))
                )
            else:
                self._sock.sendall(_OP_GET_BATCH + struct.pack("<I", max_items))
            self._status()
            (count,) = struct.unpack("<I", _recv_exact(self._sock, 4))
            out = []
            for _ in range(count):
                (n,) = struct.unpack("<I", _recv_exact(self._sock, 4))
                out.append(_recv_payload(self._sock, n, self._pool))
            return out

        with self._lock:
            return self._retrying(_do, deadline)

    def put_batch(self, items: List[Any]) -> int:
        """Send N items in ONE round trip (opcode 'Q'); returns how many
        the server accepted (a full queue truncates — retry the rest).
        Scatter-gather like :meth:`put`: N frames leave straight from
        their panel memory, never assembled into one request buffer."""
        if self._stream is not None:
            # a request/response opcode on the streamed socket would
            # desync the push framing (the server kills anything but
            # ack/BYE there) — route over the side channel like every
            # other non-stream op; the protocol-dialogue checker pins
            # this guard
            return self._side_channel().put_batch(items)

        # the whole request assembles INSIDE the retried exchange so a
        # post-reconnect retry re-encodes under the renegotiated codec
        def _do():
            parts = [_OP_PUT_BATCH + struct.pack("<I", len(items))]
            leases = []
            try:
                for item in items:
                    item_parts, clease = self._encode_for_wire(item)
                    if clease is not None:
                        leases.append(clease)
                    n = _parts_nbytes(item_parts)
                    if n > _MAX_PAYLOAD:  # fail fast
                        raise ValueError(
                            f"payload of {n} bytes exceeds wire maximum "
                            f"{_MAX_PAYLOAD}"
                        )
                    parts.append(struct.pack("<I", n))
                    parts.extend(item_parts)
                _sendmsg_all(self._sock, parts)
                self._status()
                (accepted,) = struct.unpack("<I", _recv_exact(self._sock, 4))
                return accepted
            finally:
                for clease in leases:
                    clease.release()

        with self._lock:
            return self._retrying(_do)

    def disconnect(self):
        side, self._side = self._side, None
        if side is not None:
            side.disconnect()
        sock = getattr(self, "_sock", None)  # absent if the first dial failed
        if sock is None:
            return
        # BYE acks the last response: without it the server would treat
        # the close as a mid-delivery death and re-enqueue (duplicate) the
        # last frame this client already consumed. A windowed-put tail is
        # drained first (bounded — this is teardown, not delivery), and a
        # streamed connection sends its final cumulative ack so consumed
        # frames are not redelivered to a sibling.
        try:
            with self._lock:
                if self._put_unacked:
                    self._drain_put_acks(
                        0, time.monotonic() + self.PROBE_DEADLINE_S
                    )
                if self._stream is not None:
                    self._stream.ack_consumed()
                sock.sendall(_OP_BYE)
        except (OSError, TransportClosed):
            pass
        try:
            sock.close()
        except OSError:
            pass

    def _status(self) -> bytes:
        # guarded-by-caller: _lock
        st = _recv_exact(self._sock, 1)
        if st == _ST_CLOSED:
            raise TransportClosed(f"remote queue at {self.host}:{self.port} is closed")
        if st == _ST_ERR:
            raise RuntimeError("protocol error")
        return st


class TcpStreamReader:
    """Client half of stream mode: reads server-pushed frames off a
    subscribed :class:`TcpQueueClient` connection and replenishes
    credits with cumulative acks AS IT CONSUMES — a frame is acked when
    the caller comes back for the next one, the exact point the
    request/response mode took its implicit ACK, so crash-redelivery
    granularity is unchanged (frames returned-but-unacked redeliver to
    another consumer; duplicates possible, loss never).

    Deliberately a separate class from TcpQueueClient: the blocking-
    hot-path lint checker audits everything reachable from the batcher
    drain loop, and this is that path (the client class itself is
    excluded as deadline-audited). Every READ here is bounded by the
    caller's timeout or the client's socket timeout, and there are no
    sleeps. The one wait that deliberately exceeds a read timeout is a
    mid-stream RECONNECT: it runs the client's full backoff envelope
    (bounded by reconnect_tries x (backoff + dial timeout), NOT by the
    read's pacing timeout) because a streamed subscription is a
    long-lived attachment — bounding recovery by a 10 ms poll-pacing
    timeout would turn every server restart into a consumer exit. All
    methods run under the owning client's lock; probes that must not
    wait behind it use their own connections (DataReader.open_monitor)."""

    def __init__(self, client: TcpQueueClient, window: int):
        self._c = client
        self.window = window
        self.delivered_seq = 0  # last seq returned to the caller
        self.acked_seq = 0  # last seq cumulatively acked to the server
        self._dead: Optional[str] = None  # 'X' seen: the stream is over

    def reset_after_reconnect(self):
        """The server assigns sequence numbers per connection: a fresh
        subscription restarts at 1, and anything the dead connection had
        unacked was re-enqueued server-side (it redelivers here)."""
        self.delivered_seq = 0
        self.acked_seq = 0

    # -- protocol primitives (caller holds the client lock) ---------------
    def ack_consumed(self):
        """Cumulative credit replenish for everything already returned."""
        if self.delivered_seq > self.acked_seq:
            self._c._sock.sendall(
                _OP_STREAM_ACK + struct.pack("<Q", self.delivered_seq)
            )
            self.acked_seq = self.delivered_seq
            STREAM.acked_msg()

    def _read_push(self, first_timeout: Optional[float]):
        """One pushed frame, or EMPTY when no push arrives within
        ``first_timeout`` (0 = only take what is already buffered). The
        timeout applies to the leading status byte alone; once a push
        has started, the remainder is read under the client's patient
        timeout (a timeout mid-message would desync the framing)."""
        if self._dead is not None:
            raise TransportClosed(self._dead)
        sock = self._c._sock
        try:
            sock.settimeout(first_timeout)  # 0 -> non-blocking probe
            try:
                st = _recv_exact(sock, 1)
            except (BlockingIOError, socket.timeout):
                return EMPTY
        finally:
            try:
                sock.settimeout(self._c._timeout_s)
            except OSError:
                pass
        if st == _ST_CLOSED:
            self._dead = (
                f"remote queue at {self._c.host}:{self._c.port} is closed"
            )
            raise TransportClosed(self._dead)
        if st != _ST_OK:
            raise RuntimeError(
                f"protocol error on streamed connection: {st!r}"
            )
        seq, n = struct.unpack("<QI", _recv_exact(sock, 12))
        item = _recv_payload(sock, n, self._c._pool)
        self.delivered_seq = seq
        return item

    # -- drain surface -----------------------------------------------------
    def get_batch_stream(
        self, max_items: int, timeout: Optional[float] = None
    ) -> List[Any]:
        """Up to ``max_items`` pushed frames: ack everything previously
        returned (credit replenish), block up to ``timeout`` for the
        first frame, then take whatever is already buffered without
        blocking. Returns [] on timeout — and after a mid-stream
        reconnect (the fresh subscription's redeliveries arrive on the
        next call)."""
        c = self._c
        with c._lock:
            try:
                self.ack_consumed()
                first = self._read_push(timeout)
            except TransportClosed:
                raise
            except (ConnectionError, socket.timeout, OSError) as e:
                c._reconnect(e)  # re-subscribes; unacked frames redeliver
                return []
            if first is EMPTY:
                return []
            out = [first]
            while len(out) < int(max_items):
                try:
                    nxt = self._read_push(0.0)
                except TransportClosed:
                    break  # deliver what we hold; the next call raises
                except (ConnectionError, socket.timeout, OSError):
                    break  # the next call reconnects
                if nxt is EMPTY:
                    break
                out.append(nxt)
            return out

    def get_wait_stream(self, timeout: Optional[float] = None) -> Any:
        batch = self.get_batch_stream(1, timeout)
        return batch[0] if batch else EMPTY
