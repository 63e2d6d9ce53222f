"""SegmentLog: an offset-addressed, append-only record log over a ring
of recycled mmap'd segments, with committed offsets and crash recovery.

One SegmentLog backs one queue (``DurableRingBuffer``). Records are
assigned monotonically increasing offsets at append; consumers'
positions are COMMITTED OFFSETS persisted in a small sidecar store, so
a restart re-exposes exactly the ``(committed, tail]`` range —
at-least-once across process death: duplicates possible (anything
delivered after the last commit redelivers), holes never, loss never.

Layout of the log directory::

    seg-<base_offset>.seg     pre-allocated mmap'd segments (storage.segment)
    offsets.jsonl             committed offsets per consumer group (appended
                              JSON lines, compacted in place when large; a
                              torn final line from a crash is ignored)

``fsync`` policy (the classic durability/throughput dial):

- ``none``   — never fsync. Survives PROCESS death (kill -9): the
  mmap'd writes live in page cache, which outlives the process. A
  MACHINE crash may lose the un-flushed tail — the producer-side
  windowed-put retention (PR 5/7) is the backstop there.
- ``batch``  — fsync the active segment every ``fsync_batch_n``
  appends, on segment roll, and on every commit. Bounds machine-crash
  loss to one batch.
- ``always`` — fsync after every append: one disk flush a frame, so
  nobody should pick this by accident.

Retention: segments whose every record sits below the LIVE committed
floor (group ``""`` — the queue's own consumption cursor) are kept
until more than ``retain_segments`` sealed segments of consumed
history exist, then recycled (reset + renamed to the new tail,
DALI-style, never deleted/reallocated). Unconsumed records are NEVER
recycled regardless of count — loss never — so disk usage is bounded
by (queued backlog + retain_segments of replayable history).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.storage.segment import (
    Segment,
    parse_base_offset,
    record_nbytes,
    segment_filename,
)
from psana_ray_tpu.storage.telemetry import DURABLE
from psana_ray_tpu.transport.codec import decode_payload

FSYNC_NONE = "none"
FSYNC_BATCH = "batch"
FSYNC_ALWAYS = "always"
FSYNC_POLICIES = (FSYNC_NONE, FSYNC_BATCH, FSYNC_ALWAYS)

DEFAULT_SEGMENT_BYTES = 64 * 1024 * 1024
DEFAULT_RETAIN_SEGMENTS = 8
DEFAULT_FSYNC_BATCH_N = 64

# replay_open() position sentinels (also u64-encoded on the wire, 'R'):
REPLAY_BEGIN = (1 << 64) - 1  # earliest retained offset
REPLAY_RESUME = (1 << 64) - 2  # this group's committed offset + 1

# commit_offset() sentinel ('J'): commit everything the server has
# DELIVERED to this connection's replay cursor so far (the client never
# learns raw offsets; delivery order is the shared truth)
COMMIT_DELIVERED = (1 << 64) - 1

_OFFSETS_FILE = "offsets.jsonl"
_OFFSETS_COMPACT_BYTES = 64 * 1024
# recycled-but-unneeded segments kept mapped for reuse before they are
# truly unlinked — the free list that makes a roll an O(1) rename
_FREE_SEGMENTS_MAX = 2

# Patchable disk-fault hook (tests/faultproxy.DiskFaultInjector): called
# with the op name ("append"/"sync") before the segment write or flush;
# raising OSError simulates a failing/full disk. The log degrades LOUDLY
# on it — DURABLE counter + flight breadcrumb + the OSError surfacing to
# the caller (the event-loop server answers the producer 'E') — instead
# of wedging or killing the serving loop.
_DISK_FAULT_HOOK = None


def set_disk_fault_hook(hook) -> None:
    """Install (or clear, with None) the process-wide disk-fault hook."""
    global _DISK_FAULT_HOOK
    _DISK_FAULT_HOOK = hook


def _disk_fault_check(op: str) -> None:
    hook = _DISK_FAULT_HOOK
    if hook is not None:
        hook(op)


class SegmentLog:
    """See module docstring. Thread-safe behind one lock."""

    def __init__(
        self,
        dirpath: str,
        segment_bytes: int = DEFAULT_SEGMENT_BYTES,
        retain_segments: int = DEFAULT_RETAIN_SEGMENTS,
        fsync: str = FSYNC_BATCH,
        fsync_batch_n: int = DEFAULT_FSYNC_BATCH_N,
        name: str = "queue",
    ):
        if fsync not in FSYNC_POLICIES:
            raise ValueError(
                f"fsync must be one of {FSYNC_POLICIES}, got {fsync!r}"
            )
        self.dir = dirpath
        self.name = name
        self.segment_bytes = int(segment_bytes)
        self.retain_segments = max(1, int(retain_segments))
        self._fsync = fsync
        self._fsync_batch_n = max(1, int(fsync_batch_n))
        self._lock = threading.RLock()
        self._segments: List[Segment] = []  # oldest..active  # guarded-by: _lock
        self._free: List[Segment] = []  # recycled, awaiting reuse  # guarded-by: _lock
        self._committed: Dict[str, int] = {}  # group -> offset  # guarded-by: _lock
        self._next_offset = 0  # guarded-by: _lock
        self._appends_since_sync = 0  # guarded-by: _lock
        self._closed = False  # guarded-by: _lock
        self.torn_tail_repaired = False
        self._free_id = 0  # guarded-by: _lock
        os.makedirs(dirpath, exist_ok=True)
        with self._lock:  # no peer can hold the object yet; keeps the
            self._recover()  # guarded-by annotations honest
        DURABLE.ensure_registered()

    # -- recovery ----------------------------------------------------------
    def _recover(self) -> None:
        """Boot scan: load committed offsets, walk every segment file in
        base-offset order validating records, repair a torn tail by
        truncation, and resume appends after the last valid record."""
        # guarded-by-caller: _lock
        t0 = time.monotonic()
        self._committed = _load_offsets(os.path.join(self.dir, _OFFSETS_FILE))
        for n in os.listdir(self.dir):
            # a crash can leave retired (scrubbed, renamed) segments on
            # the free list's namespace; they hold nothing — drop them
            if n.startswith("free-") and n.endswith(".seg"):
                try:
                    os.unlink(os.path.join(self.dir, n))
                except OSError:
                    pass
        names = sorted(
            n for n in os.listdir(self.dir) if parse_base_offset(n) is not None
        )
        torn = False
        records = 0
        next_offset = 0
        for fname in names:
            base = parse_base_offset(fname)
            if not self._segments:
                next_offset = base
            seg = Segment.open_existing(os.path.join(self.dir, fname), base)
            try:
                seg_next, seg_torn = seg.scan(next_offset)
            except BaseException:
                # a scan failure mid-recovery must not strand the
                # mapping: close before propagating (the caller decides
                # whether recovery as a whole survives)
                seg.close()
                raise
            torn = torn or seg_torn
            records += len(seg.index)
            next_offset = seg_next
            if not seg.index and len(names) > 1 and fname != names[-1]:
                # an empty non-tail segment (e.g. created then never
                # written before the crash): recycle it rather than
                # carrying a hole in the ring
                seg.close()
                os.unlink(seg.path)
                continue
            self._segments.append(seg)
        self._next_offset = next_offset
        if not self._segments:
            self._segments.append(self._new_segment(self._next_offset))
        ms = (time.monotonic() - t0) * 1000.0
        self.torn_tail_repaired = torn
        DURABLE.recovered(ms, records, torn)
        if records or torn:
            FLIGHT.record(
                "recovery_scan", log=self.name, records=records,
                next_offset=self._next_offset, torn_tail=torn,
                ms=round(ms, 3),
            )
        if torn:
            FLIGHT.record(
                "torn_tail_repair", log=self.name,
                truncated_at_offset=self._next_offset,
            )

    # -- segment ring ------------------------------------------------------
    def _new_segment(self, base_offset: int) -> Segment:
        # guarded-by-caller: _lock
        path = os.path.join(self.dir, segment_filename(base_offset))
        if self._free:
            seg = self._free.pop()
            seg.reset(base_offset, path)
            DURABLE.rolled(recycled=True)
            return seg
        DURABLE.rolled(recycled=False)
        return Segment.allocate(path, self.segment_bytes, base_offset)

    def _roll(self) -> Segment:
        # guarded-by-caller: _lock
        active = self._segments[-1]
        if self.fsync != FSYNC_NONE:
            active.sync()
            DURABLE.fsynced()
        seg = self._new_segment(self._next_offset)
        self._segments.append(seg)
        FLIGHT.record(
            "segment_rollover", log=self.name, base_offset=self._next_offset,
            segments=len(self._segments),
        )
        self._maybe_recycle()
        return seg

    def _maybe_recycle(self) -> None:
        """Recycle fully consumed history beyond the retention window.
        Only the LIVE cursor's committed floor gates this: unconsumed
        records are never recycled (loss never); named replay groups
        read best-effort within the retained window."""
        # guarded-by-caller: _lock
        floor = self._committed.get("", -1)
        while len(self._segments) > self.retain_segments + 1:
            seg = self._segments[0]
            last = seg.last_offset
            if last is None or last > floor:
                break
            self._segments.pop(0)
            if len(self._free) < _FREE_SEGMENTS_MAX:
                self._free_id += 1
                seg.retire(
                    os.path.join(self.dir, f"free-{self._free_id}.seg")
                )
                self._free.append(seg)
            else:
                seg.close()
                os.unlink(seg.path)

    @property
    def fsync(self) -> str:
        """The flush policy: given at construction, fixed for the life
        of the log (what a machine crash can lose is what the
        configuration states, not a value that moves at run time)."""
        return self._fsync

    @property
    def fsync_batch_n(self) -> int:
        """Appends per fsync under the ``batch`` policy; fixed likewise."""
        return self._fsync_batch_n

    # -- append ------------------------------------------------------------
    def append(self, item) -> int:
        """Append one record; returns its assigned offset."""
        need = self._check_fits(item)
        with self._lock:
            self._check_open()
            offset = self._next_offset
            self._append_locked(offset, item, need)
            self._next_offset = offset + 1
            return offset

    def append_at(self, offset: int, item) -> int:
        """Append one record under an EXPLICIT offset — the replica path
        (ISSUE 11): a follower mirrors the owner's offset space so a
        promoted replica serves the same addresses. ``offset`` must equal
        the tail; the caller reconciles divergence first
        (:meth:`truncate_to` / :meth:`reset_to`)."""
        need = self._check_fits(item)
        with self._lock:
            self._check_open()
            if offset != self._next_offset:
                raise ValueError(
                    f"append_at out of order: offset {offset} vs tail "
                    f"{self._next_offset} (reconcile with truncate_to/"
                    f"reset_to first)"
                )
            self._append_locked(offset, item, need)
            self._next_offset = offset + 1
            return offset

    def _check_fits(self, item) -> int:
        need = record_nbytes(item)
        if need > self.segment_bytes:
            raise ValueError(
                f"record of {need} framed bytes exceeds segment_bytes="
                f"{self.segment_bytes}"
            )
        return need

    def _append_locked(self, offset: int, item, need: int) -> None:
        # guarded-by-caller: _lock
        try:
            _disk_fault_check("append")
            seg = self._segments[-1]
            if seg.append(offset, item) is None:
                seg = self._roll()
                if seg.append(offset, item) is None:
                    raise RuntimeError(
                        f"record did not fit a fresh segment ({need} bytes)"
                    )
            DURABLE.appended(need)
            if self.fsync == FSYNC_ALWAYS:
                seg.sync()
                DURABLE.fsynced()
            elif self.fsync == FSYNC_BATCH:
                self._appends_since_sync += 1
                if self._appends_since_sync >= self.fsync_batch_n:
                    self._appends_since_sync = 0
                    seg.sync()
                    DURABLE.fsynced()
        except OSError as e:
            # a failing/full disk degrades LOUDLY: counter + breadcrumb
            # + the exception surfacing as THIS append's failure (the
            # event-loop server answers the producer 'E' and lives on)
            DURABLE.disk_faulted()
            FLIGHT.record(
                "disk_fault", log=self.name, op="append", error=repr(e)
            )
            raise

    # -- replica reconciliation (ISSUE 11) ---------------------------------
    def truncate_to(self, offset: int) -> None:
        """Discard every record with offset >= ``offset`` so the next
        append lands there. The follower's torn-tail sibling: after an
        owner reconnect, the owner's view of the unacknowledged suffix
        WINS — the replica rewinds and the overwriting appends (and any
        later recovery scan) see a clean end. Committed floors are
        untouched (monotonic, and always at or below the acked range)."""
        with self._lock:
            self._check_open()
            if offset >= self._next_offset:
                return
            if offset <= self.first_retained_offset():
                self._reset_locked(offset)
            else:
                while self._segments:
                    seg = self._segments[-1]
                    first = seg.first_offset
                    if first is not None and first < offset:
                        pos = seg.find(offset)
                        if pos is not None:
                            seg.truncate_from(pos)
                        break
                    # the whole tail segment goes (including empty ones)
                    self._segments.pop()
                    seg.close()
                    os.unlink(seg.path)
                if not self._segments:
                    self._segments.append(self._new_segment(offset))
                self._next_offset = offset
            DURABLE.truncated()
        FLIGHT.record("replica_truncate", log=self.name, to_offset=offset)

    def reset_to(self, offset: int) -> None:
        """Forget everything and restart the offset space at ``offset``
        (the owner's earliest shippable record lies beyond our tail — a
        contiguous local copy is impossible, so the replica restarts
        there; loudly breadcrumbed, consumed-history-only by the owner's
        retention contract)."""
        with self._lock:
            self._check_open()
            self._reset_locked(offset)
        FLIGHT.record("replica_reset", log=self.name, to_offset=offset)

    def _reset_locked(self, offset: int) -> None:
        # guarded-by-caller: _lock
        for seg in self._segments:
            seg.close()
            os.unlink(seg.path)
        self._segments = []
        self._segments.append(self._new_segment(offset))
        self._next_offset = offset

    # -- read --------------------------------------------------------------
    def read(self, offset: int):
        """Decode the record at ``offset``. The returned item OWNS its
        data (panels copied out of the mmap — a spilled record's segment
        may be recycled once consumption passes it, so views must not
        escape the lock)."""
        with self._lock:
            self._check_open()
            seg = self._find_segment(offset)
            if seg is None:
                raise KeyError(
                    f"offset {offset} is not retained (earliest "
                    f"{self.first_retained_offset()}, next {self._next_offset})"
                )
            pos = seg.find(offset)
            if pos is None:
                raise KeyError(f"offset {offset} missing from {seg!r}")
            mv = seg.payload_at(pos)
            try:
                return decode_payload(mv)
            finally:
                mv.release()

    def payload_span(self, offset: int):
        """The record's on-disk payload as a sendfile span: a
        :class:`~psana_ray_tpu.transport.splice.FileSpan`-shaped tuple
        ``(file, file_pos, nbytes)``, or None when the offset is not
        retained. Unlike :meth:`read`, NOTHING is copied — the caller
        (the evloop's kernel pass-through) moves the bytes file->socket
        without the interpreter touching them. Safe only for a record
        whose delivery pins the commit floor at or below ``offset``
        (the durable queue's ``_outstanding`` contract): that pin is
        what keeps ``_maybe_recycle`` from retiring the segment while
        the span is queued. Replay cursors have no such pin and must
        stay on the copying :meth:`read` path."""
        with self._lock:
            self._check_open()
            seg = self._find_segment(offset)
            if seg is None:
                return None
            pos = seg.find(offset)
            if pos is None:
                return None
            return seg.payload_extent(pos)

    def _find_segment(self, offset: int) -> Optional[Segment]:
        # guarded-by-caller: _lock
        for seg in reversed(self._segments):
            first = seg.first_offset
            if first is not None and first <= offset:
                last = seg.last_offset
                return seg if last is not None and offset <= last else None
        return None

    def offsets_after(self, floor: int) -> List[int]:
        """Every retained offset strictly above ``floor`` — the
        unconsumed range a recovering queue re-exposes."""
        with self._lock:
            out: List[int] = []
            for seg in self._segments:
                out.extend(off for (off, _pos) in seg.index if off > floor)
            return out

    # -- offsets -----------------------------------------------------------
    def committed(self, group: str = "") -> int:
        with self._lock:
            return self._committed.get(group, -1)

    def commit(self, offset: int, group: str = "") -> bool:
        """Persist ``group``'s committed offset (monotonic: a stale
        commit is a no-op). Returns True when the floor advanced."""
        with self._lock:
            self._check_open()
            cur = self._committed.get(group, -1)
            if offset <= cur:
                return False
            self._committed[group] = offset
            _append_offset(
                os.path.join(self.dir, _OFFSETS_FILE), group, offset,
                self._committed, durable=self.fsync != FSYNC_NONE,
            )
            DURABLE.committed()
            if not group:
                self._maybe_recycle()
            return True

    def first_retained_offset(self) -> int:
        """Earliest offset still readable (``replay from=begin``);
        equals next_offset when the log holds nothing."""
        with self._lock:
            for seg in self._segments:
                first = seg.first_offset
                if first is not None:
                    return first
            return self._next_offset

    @property
    def next_offset(self) -> int:
        with self._lock:
            return self._next_offset

    def resolve_start(self, requested: int, group: str = "") -> int:
        """Map a replay-open position (offset or sentinel) onto the
        retained range: ``REPLAY_BEGIN`` -> earliest retained,
        ``REPLAY_RESUME`` -> the group's committed offset + 1, an
        explicit offset is clamped into the retained range."""
        with self._lock:
            earliest = self.first_retained_offset()
            if requested == REPLAY_BEGIN:
                return earliest
            if requested == REPLAY_RESUME:
                return max(self._committed.get(group, -1) + 1, earliest)
            return min(max(int(requested), earliest), self._next_offset)

    # -- lifecycle ---------------------------------------------------------
    def sync(self) -> None:
        with self._lock:
            if self._closed:
                return
            try:
                _disk_fault_check("sync")
                self._segments[-1].sync()
            except OSError as e:
                DURABLE.disk_faulted()
                FLIGHT.record(
                    "disk_fault", log=self.name, op="sync", error=repr(e)
                )
                raise
            DURABLE.fsynced()
            self._appends_since_sync = 0

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            for seg in self._segments + self._free:
                try:
                    seg.sync()
                except (ValueError, OSError):
                    pass
                seg.close()
            self._segments = []
            self._free = []

    def _check_open(self):
        # guarded-by-caller: _lock
        if self._closed:
            raise RuntimeError(f"segment log {self.name!r} is closed")

    # -- observability -----------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            return {
                "next_offset": self._next_offset,
                "first_retained_offset": self.first_retained_offset()
                if self._segments
                else self._next_offset,
                "committed": dict(self._committed),
                "segments": len(self._segments),
                "free_segments": len(self._free),
                "segment_bytes": self.segment_bytes,
                "fsync": self.fsync,
                "torn_tail_repaired": self.torn_tail_repaired,
            }


class ReplayCursor:
    """A non-destructive reader over a log's retained range for one
    consumer group: live consumers are undisturbed (nothing is popped),
    and the cursor follows the tail — a replay of a finished stream
    terminates naturally on the logged EndOfStream markers. Commit via
    :meth:`commit` persists the group's position; crash-redelivery is
    re-open at ``REPLAY_RESUME``."""

    def __init__(self, log: SegmentLog, group: str, start: int):
        self.log = log
        self.group = group
        self.position = start  # next offset to read
        self.delivered = start - 1  # last offset handed out
        DURABLE.replay_opened()
        FLIGHT.record(
            "replay_open", log=log.name, group=group, start=start,
            end=log.next_offset,
        )

    def next_batch(self, max_items: int) -> list:
        out = []
        while len(out) < int(max_items):
            with self.log._lock:
                if self.log._closed:
                    break
                tail = self.log._next_offset
                if self.position >= tail:
                    break
                earliest = self.log.first_retained_offset()
                if self.position < earliest:
                    # retention passed us while we lagged: skip forward
                    # (consumed history only — never unconsumed records)
                    FLIGHT.record(
                        "replay_gap", log=self.log.name, group=self.group,
                        skipped_from=self.position, resumed_at=earliest,
                    )
                    self.position = earliest
                    continue
                try:
                    item = self.log.read(self.position)
                except KeyError:
                    self.position += 1
                    continue
            out.append(item)
            self.delivered = self.position
            self.position += 1
        return out

    def caught_up(self) -> bool:
        return self.position >= self.log.next_offset

    def commit(self, through: Optional[int] = None) -> bool:
        """Persist the group's position (default: everything delivered)."""
        through = self.delivered if through is None else through
        if through < 0:
            return False
        return self.log.commit(through, self.group)


# -- committed-offset sidecar store -----------------------------------------
def _load_offsets(path: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    try:
        with open(path, "r") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                    group, off = rec["g"], int(rec["o"])
                except (ValueError, KeyError, TypeError):
                    continue  # torn final line from a crash: ignore
                if off > out.get(group, -1):
                    out[group] = off
    except FileNotFoundError:
        pass
    return out


def _append_offset(
    path: str, group: str, offset: int, current: Dict[str, int], durable: bool
) -> None:
    """Append one commit line; compact (atomic rewrite of the latest
    per-group map) when the file grows past the threshold."""
    line = json.dumps({"g": group, "o": offset}) + "\n"
    try:
        size = os.path.getsize(path)
    except OSError:
        size = 0
    if size > _OFFSETS_COMPACT_BYTES:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            for g, o in sorted(current.items()):
                f.write(json.dumps({"g": g, "o": o}) + "\n")
            if durable:
                f.flush()
                os.fsync(f.fileno())
        os.replace(tmp, path)
        return
    with open(path, "a") as f:
        f.write(line)
        if durable:
            f.flush()
            os.fsync(f.fileno())
