"""DurableRingBuffer: the log-backed RingBuffer variant.

Drop-in for :class:`~psana_ray_tpu.transport.ring.RingBuffer` anywhere
the transport mounts a queue (the event-loop TCP server's default and
OPENed named queues under ``--durable_dir``). Semantics added on top of
the base contract:

- **Every put is logged first.** ``_box`` appends the record to the
  :class:`~psana_ray_tpu.storage.log.SegmentLog` (one ``encode_into``
  memcpy into the mmap'd segment — the same encode-into-slot plumbing
  the shm ring uses, no intermediate bytes) and the assigned offset
  rides the queue entry.
- **Bounded spill.** While the RAM-resident count fits ``ram_items``
  the item itself stays queued (delivery is the usual zero-copy path);
  beyond that the RAM copy is RELEASED (its pooled lease returns to the
  BufferPool immediately — a deep queue must not pin the pool) and the
  entry spills: delivery re-reads the record from the log.
- **Committed offsets.** Delivery tracks each popped item as
  OUTSTANDING until :meth:`ack_delivered` (the event-loop server calls
  it at exactly its implicit-ACK points); the committed floor — the
  highest offset below every queued/outstanding record — is persisted
  through the log. A restart re-exposes exactly ``(floor, tail]``:
  crash-redelivery across process death is "rewind to the last
  committed offset", not "whatever RAM remembered" (which is nothing).
  ``commit_on_get=True`` restores memory-only semantics (commit at
  delivery) for direct in-process consumers that never ack.
- **Replay.** :meth:`open_replay` hands out a non-destructive
  :class:`~psana_ray_tpu.storage.log.ReplayCursor` over the retained
  range for a named consumer group — a second group re-reads
  yesterday's stream without disturbing live consumers.

``put_front`` (the transport's requeue-at-head recovery path)
reinstates a still-outstanding item under its ORIGINAL offset — no
duplicate log append, and the floor stays pinned below it.
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional

from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.storage.log import ReplayCursor, SegmentLog
from psana_ray_tpu.storage.telemetry import DURABLE
from psana_ray_tpu.transport.ring import RingBuffer


class _Entry:
    """Stored form of one queued record: its log offset plus the RAM
    copy (None when spilled — delivery re-reads the log)."""

    __slots__ = ("offset", "item")

    def __init__(self, offset: int, item: Any):
        self.offset = offset
        self.item = item


class SpilledRecord:
    """A delivered spilled record that has NOT been read into the
    interpreter (``lazy_spill`` queues only).

    The evloop server never interprets queue items — it frames and
    relays them — so delivery can hand it this handle instead of the
    decoded record: the kernel pass-through path asks
    :meth:`payload_span` for a (file, pos, nbytes) sendfile span and
    the payload bytes go mmap->socket without a Python copy;
    :meth:`materialize` is the fallback (compressed connection, no
    sendfile) and behaves exactly like the eager ``log.read``.

    Identity-stable on purpose: every delivery contract in the server
    is keyed by ``id(item)`` (``_outstanding``, ``_box_front`` requeue,
    stream unacked tails, in-flight ack), and while this object is
    outstanding the commit floor stays pinned at or below ``offset`` —
    which is precisely what keeps the span's segment from being
    recycled mid-send (see ``SegmentLog.payload_span``).
    """

    __slots__ = ("log", "offset", "_item")

    def __init__(self, log: SegmentLog, offset: int):
        self.log = log
        self.offset = offset
        self._item = None

    def payload_span(self):
        """``(file, file_pos, nbytes)`` of the raw tagged payload, or
        None (offset no longer retained — caller materializes)."""
        return self.log.payload_span(self.offset)

    def materialize(self) -> Any:
        """Decode the record (cached): the copying path, for consumers
        that need the bytes in Python after all."""
        if self._item is None:
            DURABLE.spill_read()
            self._item = self.log.read(self.offset)
        return self._item


class DurableRingBuffer(RingBuffer):
    def __init__(
        self,
        log: SegmentLog,
        maxsize: int = 100,
        name: str = "durable_queue",
        ram_items: Optional[int] = None,
        commit_on_get: bool = False,
        lazy_spill: bool = False,
    ):
        super().__init__(maxsize=maxsize, name=name)
        self.log = log
        # None / 0 = the queue's maxsize; a negative count is held at 1
        self.ram_items = max(1, int(ram_items)) if ram_items else int(maxsize)
        self.commit_on_get = commit_on_get
        # lazy_spill: deliver spilled entries as SpilledRecord handles
        # instead of eagerly decoding (the evloop server's kernel
        # pass-through). Only meaningful with ack-based commits: a
        # commit-on-get consumer lets the floor pass the offset before
        # the handle is read, so that mode stays eager.
        self.lazy_spill = bool(lazy_spill) and not commit_on_get
        self._resident = 0  # RAM-held entries in _q  # guarded-by: _lock
        self._spilled = 0  # log-only entries in _q  # guarded-by: _lock
        # delivered-but-unacked: id(item) -> entry. Strong item refs on
        # purpose — they pin the id()s against reuse AND keep the floor
        # honest until the ack (or put_front) resolves each delivery.
        self._outstanding: dict = {}  # guarded-by: _lock
        self._floor = log.committed("")  # guarded-by: _lock
        DURABLE.ensure_registered()
        self._reexpose()

    # -- recovery ----------------------------------------------------------
    def _reexpose(self) -> None:
        """Boot: everything the log retains above the committed floor is
        unconsumed — queue it (spilled; reads hydrate from the log).
        Depth may exceed maxsize here, exactly like put_front: the
        records were admitted in a previous life."""
        with self._lock:
            offsets = self.log.offsets_after(self._floor)
            if not offsets:
                return
            for off in offsets:
                self._q.append(_Entry(off, None))
            self._spilled += len(offsets)
            if len(self._q) > self._high_water:
                self._high_water = len(self._q)
            self._not_empty.notify_all()
            self._notify_listeners()
        DURABLE.spill_delta(len(offsets))
        FLIGHT.record(
            "durable_reexpose", queue=self.name, records=len(offsets),
            from_offset=offsets[0], to_offset=offsets[-1],
        )

    # -- storage hooks (see RingBuffer._box/_unbox) ------------------------
    def _box(self, item: Any) -> Any:
        # guarded-by-caller: _lock
        offset = self.log.append(item)
        if self._resident < self.ram_items:
            self._resident += 1
            return _Entry(offset, item)
        # spill: the log holds the bytes; release the RAM copy's pooled
        # lease NOW (a deep durable queue must not pin the BufferPool)
        if self._spilled == 0:
            FLIGHT.record("spill_enter", queue=self.name, depth=len(self._q))
        self._spilled += 1
        DURABLE.spill_delta(1)
        release = getattr(item, "release", None)
        if release is not None:
            release()
        return _Entry(offset, None)

    def _box_front(self, item: Any) -> Any:
        """Head re-insertion: an OUTSTANDING item comes back under its
        original offset (no new log append — the floor never advanced
        past it); anything else (e.g. a sibling EOS marker flushed back,
        or a materialized copy) is a fresh logged record."""
        # guarded-by-caller: _lock
        entry = self._outstanding.pop(id(item), None)
        if entry is not None:
            entry.item = item
            self._resident += 1
            return entry
        offset = self.log.append(item)
        self._resident += 1
        return _Entry(offset, item)

    def _unbox(self, stored: Any) -> Any:
        # guarded-by-caller: _lock
        entry: _Entry = stored
        if entry.item is None:
            if self.lazy_spill:
                # no read, no copy: the handle carries the offset and
                # the evloop moves the payload kernel-side (or
                # materializes — which is when spill_read is counted)
                entry.item = SpilledRecord(self.log, entry.offset)
            else:
                DURABLE.spill_read()
                entry.item = self.log.read(entry.offset)
            self._spilled -= 1
            if self._spilled == 0:
                FLIGHT.record("spill_exit", queue=self.name)
        else:
            self._resident -= 1
        item = entry.item
        if self.commit_on_get:
            # immediate commit (memory-only delivery semantics): floor is
            # still min-pending-based — a head-requeued FRESH item carries
            # a high offset at the queue head, so committing this entry's
            # own offset could leap past unconsumed records. The entry
            # being delivered is excluded: unboxing runs BEFORE the pop
            # (transactional get), so it still sits in _q here.
            self._commit_floor(exclude=entry)
        else:
            self._outstanding[id(item)] = entry
        return item

    # -- replicated ack floor support (ISSUE 11) ---------------------------
    def put(self, item: Any) -> bool:
        """One admission implementation: :meth:`put_offset` is the
        primitive (the event-loop's replicated-ack-floor gate needs the
        offset); ``put`` is its offset-discarding face."""
        return self.put_offset(item)[0]

    def put_offset(self, item: Any):
        """``put`` that also reports the appended record's log offset —
        the event-loop server's replicated-ack-floor gate needs it to
        hold the producer's ack until the follower has logged exactly
        this record. Returns ``(ok, offset)``; ``(False, None)`` when
        full."""
        with self._lock:
            self._check_open()
            self._check_accepting()
            if len(self._q) >= self.maxsize:
                self._n_put_rejected += 1
                return False, None
            entry = self._box(item)
            self._q.append(entry)
            self._note_put()
            self._not_empty.notify()
            return True, entry.offset

    @property
    def committed_floor(self) -> int:
        """The live committed floor — piggybacked on replica appends so
        a promoted follower re-exposes only ``(floor, tail]``."""
        with self._lock:
            return self._floor

    # -- committed offsets -------------------------------------------------
    def ack_delivered(self, items) -> int:
        """The delivery of ``items`` is confirmed (the event-loop server
        calls this at its implicit-ACK points: next-opcode, stream
        cumulative ack, clean BYE). Advances and persists the committed
        floor. Unknown items (already acked, or not from this queue) are
        ignored. Returns the new floor."""
        with self._lock:
            changed = False
            for item in items:
                if self._outstanding.pop(id(item), None) is not None:
                    changed = True
            if changed:
                self._commit_floor()
            return self._floor

    def _commit_floor(self, exclude=None) -> None:
        """floor = (lowest offset still queued or outstanding) - 1; when
        nothing is pending, everything assigned is consumed. O(depth) —
        called per ack batch, bounded by maxsize."""
        # guarded-by-caller: _lock
        pending = [e.offset for e in self._q if e is not exclude]
        pending.extend(e.offset for e in self._outstanding.values())
        floor = (min(pending) - 1) if pending else (self.log.next_offset - 1)
        self._advance_floor_to(floor)

    def _advance_floor_to(self, floor: int) -> None:
        # guarded-by-caller: _lock
        if floor > self._floor:
            self._floor = floor
            self.log.commit(floor, "")

    def commit_offset(self, offset: int, group: str) -> bool:
        """Explicit offset commit for a NAMED group (the 'J' opcode's
        backing; the live floor is group ``""`` and owned by acks)."""
        if not group:
            return False
        return self.log.commit(offset, group)

    # -- replay ------------------------------------------------------------
    def open_replay(self, group: str, requested: int) -> ReplayCursor:
        """A non-destructive cursor over the retained range for
        ``group`` (position sentinels: storage.log.REPLAY_BEGIN /
        REPLAY_RESUME). Live consumption is untouched."""
        start = self.log.resolve_start(requested, group)
        return ReplayCursor(self.log, group, start)

    # -- lifecycle / observability ----------------------------------------
    def close(self):
        super().close()
        try:
            self.log.sync()
        except (RuntimeError, OSError):
            pass  # log already closed / disk fault already breadcrumbed

    def stats(self) -> dict:
        out = super().stats()
        with self._lock:
            out.update(
                durable=True,
                spilled=self._spilled,
                resident=self._resident,
                outstanding=len(self._outstanding),
                committed_offset=self._floor,
                log=self.log.stats(),
            )
        return out
