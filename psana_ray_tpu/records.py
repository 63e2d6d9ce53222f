"""Versioned frame record schema and typed end-of-stream marker.

The reference ships a bare 4-list ``[rank, idx, data, photon_energy]``
(reference ``producer.py:101``) and overloads ``None`` for both "queue empty"
and "end of stream" (``shared_queue.py:21``, ``producer.py:124-125``), which
its own example mis-unpacks (``psana_consumer.py:35`` — 3-way unpack of a
4-list). This module fixes those quirks (SURVEY.md §3 quirks 1-2) with:

- :class:`FrameRecord` — an explicit, versioned record with named fields;
- :class:`EndOfStream` — a typed EOS marker distinct from "try again";
- a compact binary wire format for cross-process / cross-host transports.

Everything here is plain Python + numpy so it is importable without JAX.
"""

from __future__ import annotations

import dataclasses
import struct
import time
from typing import Optional

import numpy as np

from psana_ray_tpu.obs.tracing import TraceContext
from psana_ray_tpu.utils.bufpool import WIRE

SCHEMA_VERSION = 3
# Frames WITHOUT a trace context encode as v2 — byte-identical to the
# pre-tracing wire format, so unsampled streams pay zero extra wire
# bytes and zero extra allocations. A trace context (ISSUE 4 sampled
# distributed tracing) bumps that frame to v3 with the compact context
# appended after the shape.
_UNTRACED_WIRE_VERSION = 2

# Wire format magics (little-endian u32).
_FRAME_MAGIC = 0x50525446  # "PRTF" — psana-ray-tpu frame
_EOS_MAGIC = 0x50525445  # "PRTE" — psana-ray-tpu EOS

# header: magic, version, shard_rank, event_idx, ndim, dtype_code, photon_energy(f64), timestamp(f64)
_FRAME_HEADER = struct.Struct("<IIqqII d d")
_EOS_HEADER_V1 = struct.Struct("<IIqq")
# v2 appends shards_done + total_shards (multi-producer EOS aggregation)
_EOS_HEADER = struct.Struct("<IIqqqq")

_DTYPE_CODES = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.uint16): 2,
    np.dtype(np.int32): 3,
    np.dtype(np.uint8): 4,
    np.dtype(np.int16): 5,
}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}


@dataclasses.dataclass(frozen=True, eq=False)
class FrameRecord:
    """One detector event.

    Parity with the reference payload ``[rank, idx, data, photon_energy]``
    (``producer.py:101``), plus schema version and timestamp. ``panels`` is
    always 3-D ``[P, H, W]`` — 2-D frames are promoted with a leading panel
    axis exactly like the reference does (``producer.py:96-97``).

    ``eq=False``: dataclass-generated ``__eq__`` would tuple-compare the
    ndarray field and raise; use :meth:`equals` for value comparison.
    """

    shard_rank: int
    event_idx: int
    panels: np.ndarray  # [P, H, W]
    photon_energy: float
    timestamp: float = 0.0
    schema_version: int = SCHEMA_VERSION
    # Process-local monotonic hop timestamps (observability, never on the
    # wire): ``{hop_name: time.monotonic()}`` written by :func:`mark_hop`
    # at each pipeline boundary (psana_ray_tpu.obs.stages names the hops).
    # None (the default, and always after decode) keeps the hot path at
    # zero cost for streams nobody is timing. Cross-process, the wall-clock
    # ``timestamp`` field is the enqueue-side stamp consumers fall back to.
    hops: Optional[dict] = dataclasses.field(default=None, repr=False)
    # The instant the transport accepted this record, on the HOST's
    # monotonic clock (never on the wire; 0.0 = unknown). A transport
    # whose queue lives in memory shared by the host's processes stamps
    # each slot and hands the stamp back with the pop (shm ring), so
    # ``queue_dwell`` and an enqueue -> result ``e2e`` exist on the far
    # side of a process hop, where ``hops`` cannot travel.
    t_enq: float = dataclasses.field(default=0.0, repr=False)
    # Host-local buffer ownership (never on the wire): when ``panels`` is
    # a zero-copy view into pooled/transport memory, ``lease`` keeps that
    # memory checked out (utils.bufpool.Lease or a transport slot lease).
    # The view is valid for the record's lifetime; :meth:`release` hands
    # the buffer back once the payload has been copied onward
    # (FrameBatcher.push_view), and GC of the record releases as a
    # backstop. None (the default) means the record owns its data.
    lease: Optional[object] = dataclasses.field(default=None, repr=False)
    # Sampled distributed-tracing context (obs.tracing) — ON the wire
    # (unlike hops): the trace id must link this frame's spans across the
    # producer / queue-server / consumer processes. None (the default and
    # the unsampled case) keeps the wire format at v2, byte-identical to
    # pre-tracing encoders.
    trace: Optional[TraceContext] = dataclasses.field(default=None, repr=False)
    # Relay pass-through cache (ISSUE 9, never on the wire as a field):
    # when this record was decoded from a COMPRESSED wire payload
    # (transport/codec.py TAG_COMPRESSED), ``wire_cache`` is
    # ``(codec_id, lease, payload_memoryview)`` — the exact compressed
    # bytes, kept checked out alongside the decompressed panels. A
    # relay pushing this record to a peer that negotiated the SAME
    # codec re-sends those bytes verbatim (zero codec CPU per brokered
    # frame); any other destination re-encodes from ``panels`` as
    # usual. Released with :meth:`release` / dropped by
    # :meth:`materialize`; GC of the lease is the backstop.
    wire_cache: Optional[tuple] = dataclasses.field(default=None, repr=False)

    def __post_init__(self):
        panels = np.asarray(self.panels)
        if panels.ndim == 2:
            panels = panels[None]  # promote, reference producer.py:96-97
        if panels.ndim != 3:
            raise ValueError(f"panels must be 2-D or 3-D, got ndim={panels.ndim}")
        object.__setattr__(self, "panels", panels)

    @property
    def nbytes(self) -> int:
        return int(self.panels.nbytes)

    def equals(self, other: "FrameRecord") -> bool:
        return (
            isinstance(other, FrameRecord)
            and self.shard_rank == other.shard_rank
            and self.event_idx == other.event_idx
            and self.photon_energy == other.photon_energy
            and np.array_equal(self.panels, other.panels)
        )

    # -- host buffer ownership -------------------------------------------
    def release(self):
        """Return the leased transport buffer (if any) to its pool.

        Call ONLY after the panel payload has been copied onward — the
        view in ``panels`` dies with the lease. Idempotent; no-op for
        records that own their data. Also drops the compressed
        ``wire_cache`` lease (its reuse window ends with the record)."""
        cache = self.wire_cache
        if cache is not None:
            object.__setattr__(self, "wire_cache", None)
            cache[1].release()
        lease = self.lease
        if lease is not None:
            object.__setattr__(self, "lease", None)
            lease.release()

    def materialize(self) -> "FrameRecord":
        """Self if this record owns its data; otherwise a copy that does,
        with the lease released. Use before re-enqueueing or retaining a
        view-backed record past its transport buffer (e.g. frames handed
        back to a queue whose slots those very leases occupy)."""
        if self.lease is None and self.wire_cache is None:
            return self
        panels = self.panels.copy() if self.lease is not None else self.panels
        if self.lease is not None:
            WIRE.add(panels.nbytes)
        self.release()
        # replace() carries every other field — including the hops dict,
        # so stage timing survives materialization
        return dataclasses.replace(
            self, panels=panels, lease=None, wire_cache=None
        )

    # -- wire format ------------------------------------------------------
    def wire_parts(self) -> tuple:
        """``(header_bytes, payload_memoryview)`` — the scatter-gather
        form of :meth:`to_bytes`. The header covers magic through shape;
        the payload is a ZERO-COPY flat byte view of the panels (one
        ``ascontiguousarray`` copy only if the panels are strided), so a
        ``socket.sendmsg`` sender never materializes the frame as a
        fresh bytes object. ``b"".join(wire_parts())`` == ``to_bytes()``."""
        panels = self.panels
        if not panels.flags.c_contiguous:
            panels = np.ascontiguousarray(panels)
            WIRE.add(panels.nbytes)
        header = _FRAME_HEADER.pack(
            _FRAME_MAGIC,
            self._wire_version(),
            self.shard_rank,
            self.event_idx,
            panels.ndim,
            _DTYPE_CODES[panels.dtype],
            float(self.photon_energy),
            float(self.timestamp),
        ) + struct.pack(f"<{panels.ndim}q", *panels.shape)
        if self.trace is not None:  # v3: compact trace context after shape
            header += self.trace.pack()
        return header, panels.data.cast("B")

    def _wire_version(self) -> int:
        """v2 for untraced frames (byte-identical to pre-tracing
        encoders), v3 when a trace context must ride along."""
        return SCHEMA_VERSION if self.trace is not None else _UNTRACED_WIRE_VERSION

    def to_bytes(self) -> bytes:
        header, payload = self.wire_parts()
        return header + payload.tobytes()

    @staticmethod
    def from_bytes(buf, copy: bool = True) -> "FrameRecord":
        """Decode one frame. ``copy=True`` (default): the record owns its
        panels. ``copy=False``: ``panels`` is a zero-copy ``frombuffer``
        view into ``buf`` — the caller must keep ``buf`` alive/unchanged
        for the record's lifetime (the pooled transports do this by
        attaching the buffer's lease to the record)."""
        rank, idx, shape, dtype, energy, ts, version, trace, off = (
            parse_frame_header(buf)
        )
        panels = np.frombuffer(buf, dtype=dtype, count=int(np.prod(shape)), offset=off).reshape(shape)
        if copy:
            panels = panels.copy()
            WIRE.add(panels.nbytes)
        return FrameRecord(
            shard_rank=rank,
            event_idx=idx,
            panels=panels,
            photon_energy=energy,
            timestamp=ts,
            schema_version=version,
            trace=trace,
        )


def parse_frame_header(buf) -> tuple:
    """Parse a frame wire HEADER without touching payload bytes:
    ``(shard_rank, event_idx, shape, dtype, photon_energy, timestamp,
    version, trace, header_len)``. Raises ValueError on non-frame
    bytes. THE wire-header grammar: :meth:`FrameRecord.from_bytes` is
    this plus the payload ``frombuffer``, and the wire-compression
    layer reads it off the raw head of a compressed payload
    (transport/codec.py) to build a :class:`LazyFrameRecord` without
    decompressing anything — a schema bump changes exactly one
    parser."""
    magic, version, rank, idx, ndim, dtype_code, energy, ts = _FRAME_HEADER.unpack_from(buf, 0)
    if magic != _FRAME_MAGIC:
        raise ValueError(f"bad frame magic {magic:#x}")
    if version > SCHEMA_VERSION:
        raise ValueError(f"unsupported schema version {version}")
    off = _FRAME_HEADER.size
    shape = struct.unpack_from(f"<{ndim}q", buf, off)
    off += 8 * ndim
    trace = None
    if version >= 3:
        trace = TraceContext.unpack_from(buf, off)
        off += TraceContext.WIRE_SIZE
    if dtype_code not in _CODE_DTYPES:
        raise ValueError(f"unknown dtype code {dtype_code}")
    return (rank, idx, shape, _CODE_DTYPES[dtype_code], energy, ts, version, trace, off)


class LazyFrameRecord(FrameRecord):
    """A FrameRecord decoded from a COMPRESSED wire payload without
    decompressing the panels (ISSUE 9, server relay path): the header
    fields are real — they ride the compressed payload raw — and
    ``panels`` inflates on first touch through a codec-layer closure.
    A relay that re-sends the record's cached compressed bytes
    verbatim (``wire_cache`` pass-through) never touches panels, so a
    same-codec broker pays ZERO codec CPU per brokered frame; every
    other consumer of the record (mixed-codec push, durable log
    encode, shm re-encode, in-process reads) sees an ordinary
    FrameRecord that just decompresses at the first panel access.

    Only codecs whose streams are cheaply VALIDATED up front may
    produce these (codec ``validate()``): a corrupt payload must fail
    AT RECEIVE — where the connection dies and the in-flight requeue
    contract runs — never inside a later push to an innocent consumer
    (a poison frame redelivering forever).

    Built by the codec layer via :func:`make_lazy_frame` —
    ``__init__``/``__post_init__`` are bypassed, and the ``panels``
    property (a data descriptor, so it wins over any instance
    attribute) carries the laziness."""

    @property
    def panels(self):  # type: ignore[override]
        p = self.__dict__.get("_panels")
        if p is None:
            # inflate returns (panels, lease) and deliberately knows
            # nothing about this record: a closure capturing the record
            # would be a reference CYCLE (record -> closure -> record),
            # and the pool leases would then wait on a gc pass instead
            # of refcount death — a measured leak, not a theory
            p, lease = self.__dict__["_inflate"]()
            object.__setattr__(self, "_panels", p)
            if lease is not None:
                object.__setattr__(self, "lease", lease)
        return p

    @property
    def nbytes(self) -> int:
        return int(self.__dict__["_panel_nbytes"])  # no inflate for stats

    def materialize(self) -> "FrameRecord":
        panels = self.panels.copy()
        WIRE.add(panels.nbytes)
        rec = FrameRecord(
            shard_rank=self.shard_rank,
            event_idx=self.event_idx,
            panels=panels,
            photon_energy=self.photon_energy,
            timestamp=self.timestamp,
            schema_version=self.schema_version,
            hops=self.hops,
            trace=self.trace,
        )
        self.release()
        return rec


def make_lazy_frame(
    rank, idx, energy, ts, version, trace, panel_nbytes, inflate, wire_cache,
) -> LazyFrameRecord:
    """Codec-layer factory for :class:`LazyFrameRecord`: all header
    fields are set directly (no __init__ — there are no panels yet);
    ``inflate`` is a zero-arg closure returning ``(panels, lease)`` —
    the decompressed typed view plus the pool lease backing it (None
    off the pooled path). It must NOT reference the record (see the
    panels property on cycles)."""
    rec = object.__new__(LazyFrameRecord)
    object.__setattr__(rec, "shard_rank", rank)
    object.__setattr__(rec, "event_idx", idx)
    object.__setattr__(rec, "photon_energy", energy)
    object.__setattr__(rec, "timestamp", ts)
    object.__setattr__(rec, "schema_version", version)
    object.__setattr__(rec, "hops", None)
    object.__setattr__(rec, "lease", None)
    object.__setattr__(rec, "trace", trace)
    object.__setattr__(rec, "wire_cache", wire_cache)
    object.__setattr__(rec, "_panel_nbytes", int(panel_nbytes))
    object.__setattr__(rec, "_inflate", inflate)
    return rec


def mark_hop(rec, hop: str, t: Optional[float] = None) -> None:
    """Stamp ``time.monotonic()`` (or ``t``) on ``rec`` under ``hop``.

    The observability layer's envelope hook: producers stamp source-read
    and enqueue, the batcher stamps dequeue/assembly, the prefetcher
    stamps device placement, and :func:`psana_ray_tpu.obs.stages.
    observe_frame_stages` turns consecutive stamps into per-stage latency
    histograms. No-op on non-frame items (EOS markers are not timed);
    safe on the frozen dataclass (the dict is attached once via
    ``object.__setattr__``, then mutated in place)."""
    if not isinstance(rec, FrameRecord):
        return
    hops = rec.hops
    if hops is None:
        hops = {}
        object.__setattr__(rec, "hops", hops)
    hops[hop] = time.monotonic() if t is None else t


def validate_wire_dtype(dtype_str: str) -> np.dtype:
    """The one place the "is this dtype wire-codable" rule lives: CLI
    validation (addressing.apply_wire_args) and the narrowing path
    below both resolve through here."""
    dtype = np.dtype(dtype_str)
    if dtype not in _DTYPE_CODES:
        raise ValueError(
            f"wire dtype {dtype_str!r} is not wire-codable "
            f"(supported: {sorted(str(d) for d in _DTYPE_CODES)})"
        )
    return dtype


def narrow_panels(panels: np.ndarray, dtype_str: str) -> np.ndarray:
    """Opt-in wire dtype narrowing (ISSUE 9, ``--wire_dtype``): convert
    panels to a narrower wire dtype BEFORE encode, clipping integer
    targets to their representable range (a f32 calibrated frame that
    fits u16 halves its wire bytes before compression even starts;
    calibration already emits narrow output dtypes, this applies the
    same idea at the transport boundary). LOSSY by construction — the
    operator opts in per stream. The target must be a wire-codable
    dtype (``_DTYPE_CODES``); no-op when panels already match."""
    dtype = validate_wire_dtype(dtype_str)
    if panels.dtype == dtype:
        return panels
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        if np.issubdtype(panels.dtype, np.floating):
            src = np.rint(panels)
            # calibrated frames mark bad pixels NaN; NaN→int casts are
            # undefined in numpy (platform-dependent garbage), so map
            # them to 0 — the usual masked-pixel convention. clip()
            # already sends ±inf to the dtype bounds.
            np.copyto(src, 0.0, where=np.isnan(src))
        else:
            src = panels
        out = np.clip(src, info.min, info.max).astype(dtype)
    else:
        out = panels.astype(dtype)
    WIRE.add(out.nbytes)
    return out


@dataclasses.dataclass(frozen=True)
class EndOfStream:
    """Typed end-of-stream marker.

    Replaces the reference's ``None`` sentinel (``producer.py:124-125``),
    which was indistinguishable from "queue momentarily empty"
    (``shared_queue.py:21``). ``producer_rank`` records who signalled;
    ``total_events`` (when known) lets consumers verify completeness.

    The reference coordinates N producer processes with a global MPI
    barrier before a single rank-0 sentinel emission (``producer.py:
    119-126``). Without MPI, each producer runtime emits its own EOS
    carrying how many shards it covered (``shards_done``) out of how many
    exist globally (``total_shards``); consumers tally markers with
    :class:`EosTally` and stop only once every shard is accounted for.
    """

    producer_rank: int = 0
    total_events: int = -1  # -1 = unknown
    shards_done: int = 1  # shards covered by the emitting runtime
    total_shards: int = 1  # global shard count across all runtimes
    schema_version: int = SCHEMA_VERSION

    def to_bytes(self) -> bytes:
        return _EOS_HEADER.pack(
            _EOS_MAGIC,
            self.schema_version,
            self.producer_rank,
            self.total_events,
            self.shards_done,
            self.total_shards,
        )

    @staticmethod
    def from_bytes(buf: bytes) -> "EndOfStream":
        magic, version, rank, total = _EOS_HEADER_V1.unpack_from(buf, 0)
        if magic != _EOS_MAGIC:
            raise ValueError(f"bad EOS magic {magic:#x}")
        shards_done = total_shards = 1
        if version >= 2:
            off = _EOS_HEADER_V1.size
            shards_done, total_shards = struct.unpack_from("<qq", buf, off)
        return EndOfStream(
            producer_rank=rank,
            total_events=total,
            shards_done=shards_done,
            total_shards=total_shards,
            schema_version=version,
        )


class EosTally:
    """Tracks EOS markers from multiple producer runtimes.

    ``observe(eos)`` returns True once every global shard is covered —
    i.e. the sum of ``shards_done`` over distinct producer ranks reaches
    ``total_shards``. ``is_duplicate(eos)`` tells a consumer that it
    already holds this runtime's marker — the copy belongs to a sibling
    consumer (each runtime emits one marker per expected consumer, parity
    with reference ``producer.py:124-125``).

    :meth:`process` + :meth:`flush_duplicates` are the shared consumer-side
    protocol: duplicates are *held* (never dropped) and returned to the
    queue when space is available — re-enqueueing inline could fail against
    a full queue and silently starve the sibling.

    Coverage is IDEMPOTENT per producer rank (``observe`` keys shards_done
    by ``producer_rank``): an EOS marker duplicated by an at-least-once
    transport retry (TCP reconnect, ``transport/tcp.py`` delivery
    contract) cannot double-count coverage or complete a tally early —
    the surplus copy is just held-and-returned like a sibling's.
    """

    def __init__(self):
        self._shards_by_rank = {}
        self._total = 1
        self._pending_dups: list = []

    def is_duplicate(self, eos: "EndOfStream") -> bool:
        return eos.producer_rank in self._shards_by_rank

    def observe(self, eos: "EndOfStream") -> bool:
        self._shards_by_rank[eos.producer_rank] = eos.shards_done
        self._total = max(self._total, eos.total_shards)
        return self.complete

    def process(self, eos: "EndOfStream") -> bool:
        """Observe a marker read off the queue; duplicate copies are held
        for :meth:`flush_duplicates`. Returns True when the stream is
        complete (every global shard covered)."""
        if self.is_duplicate(eos):
            self._pending_dups.append(eos)
            return self.complete
        return self.observe(eos)

    def flush_duplicates(self, queue, final: bool = False) -> int:
        """Return held sibling markers to ``queue``; returns how many were
        placed. Cheap no-op when none pend. Call after reads (a get just
        freed a slot) and once more on exit with ``final=True``
        (persistent, so the markers survive this consumer). A closed
        transport discards them — the sibling sees the dead queue itself.

        CALLERS THAT FLUSH WHILE STARVED MUST YIELD THE SCHEDULER when
        this returns nonzero before reading again: the very next read
        would otherwise pop the marker straight back (put and pop happen
        inside one GIL slice), and two competing consumers each cycling
        their own sibling-bound marker this way never hand them over —
        a livelock measured at 60+ s on 1-2 cores
        (test_two_consumers_two_runtimes).

        The final flush routes through the shared recovery path
        (:func:`psana_ray_tpu.transport.recovery.return_to_queue`): head
        placement when supported, timed retries + logged drop otherwise."""
        if not self._pending_dups:
            return 0
        if final:
            from psana_ray_tpu.transport.recovery import return_to_queue

            n = len(self._pending_dups)
            return_to_queue(queue, self._pending_dups, what="sibling EOS marker")
            self._pending_dups = []
            return n
        from psana_ray_tpu.transport.registry import TransportClosed, TransportWedged

        kept = []
        placed = 0
        for eos in self._pending_dups:
            try:
                if not queue.put(eos):
                    kept.append(eos)
                else:
                    placed += 1
            except TransportWedged:
                raise  # crashed-peer wedge is an error, not a drained queue
            except TransportClosed:
                self._pending_dups = []
                return placed
        self._pending_dups = kept
        return placed

    def markers(self) -> list:
        """Reconstruct one EOS marker per observed producer rank — what a
        consumer must RETURN to a queue it is handing off mid-tally (a
        cluster rebalance revoking a partly-drained partition): the new
        owner's tally re-observes the same coverage. Reconstruction, not
        retention, so held duplicates stay with flush_duplicates."""
        return [
            EndOfStream(
                producer_rank=rank, shards_done=done, total_shards=self._total
            )
            for rank, done in sorted(self._shards_by_rank.items())
        ]

    @property
    def complete(self) -> bool:
        return sum(self._shards_by_rank.values()) >= self._total


def decode(buf, lease=None):
    """Decode a wire message into FrameRecord or EndOfStream. Accepts any
    buffer protocol object (bytes, memoryview into shared memory, ...).

    Without ``lease`` (default) the returned record owns its data
    (panels are copied out). With ``lease`` — a checked-out buffer that
    ``buf`` views (utils.bufpool.Lease or a transport slot lease) — a
    FrameRecord is returned ZERO-COPY: its panels view ``buf`` and the
    lease rides on the record (released after the batch copy by
    ``FrameBatcher.push_view``, or on GC). Non-frame messages never need
    the buffer past decode, so their lease is released here."""
    (magic,) = struct.unpack_from("<I", buf, 0)
    if magic == _FRAME_MAGIC:
        if lease is None:
            return FrameRecord.from_bytes(buf)
        rec = FrameRecord.from_bytes(buf, copy=False)
        object.__setattr__(rec, "lease", lease)
        return rec
    try:
        if magic == _EOS_MAGIC:
            return EndOfStream.from_bytes(buf)
        raise ValueError(f"unknown wire magic {magic:#x}")
    finally:
        # released only AFTER the payload is fully parsed: the pool may
        # hand a released buffer to another thread immediately
        if lease is not None:
            lease.release()


def encoded_size(item) -> int:
    """Exact wire size of ``to_bytes()`` without building it — lets a
    zero-copy transport reserve the right slot span up front."""
    if isinstance(item, FrameRecord):
        trace_bytes = TraceContext.WIRE_SIZE if item.trace is not None else 0
        return (
            _FRAME_HEADER.size + 8 * item.panels.ndim + trace_bytes
            + int(item.panels.nbytes)
        )
    if isinstance(item, EndOfStream):
        return _EOS_HEADER.size
    raise TypeError(f"not a wire record: {type(item)!r}")


def encode_into(item, buf) -> int:
    """Serialize ``item`` directly into a writable buffer (e.g. a shm ring
    slot), avoiding the intermediate bytes of ``to_bytes()``. The frame
    payload lands via ONE ``np.copyto`` memcpy. Returns bytes written."""
    mv = memoryview(buf)
    if isinstance(item, EndOfStream):
        data = item.to_bytes()  # header-only, tiny
        mv[: len(data)] = data
        return len(data)
    if not isinstance(item, FrameRecord):
        raise TypeError(f"not a wire record: {type(item)!r}")
    panels = np.ascontiguousarray(item.panels)
    _FRAME_HEADER.pack_into(
        mv,
        0,
        _FRAME_MAGIC,
        item._wire_version(),
        item.shard_rank,
        item.event_idx,
        panels.ndim,
        _DTYPE_CODES[panels.dtype],
        float(item.photon_energy),
        float(item.timestamp),
    )
    off = _FRAME_HEADER.size
    struct.pack_into(f"<{panels.ndim}q", mv, off, *panels.shape)
    off += 8 * panels.ndim
    if item.trace is not None:  # v3: trace context between shape and payload
        ctx = item.trace.pack()
        mv[off : off + len(ctx)] = ctx
        off += len(ctx)
    dst = np.frombuffer(mv, dtype=panels.dtype, count=panels.size, offset=off)
    np.copyto(dst, panels.reshape(-1))
    WIRE.add(panels.nbytes)
    return off + int(panels.nbytes)


def is_eos(item) -> bool:
    return isinstance(item, EndOfStream)
