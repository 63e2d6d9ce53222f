"""Kernel pass-through for payload bytes: sendfile spans + capability probe.

The brokered hot path's remaining Python-byte source is the durable
spill read: ``SegmentLog.read`` copies the payload
out of the mmap into interpreter-owned bytes just so the evloop can
hand them back to ``socket.sendmsg``. But the bytes at rest in a
segment ARE the wire payload (tag byte + record body, written verbatim
at append time) — the copy exists only because the write engine speaks
buffers. This module teaches it to speak FILE REGIONS instead:

- :class:`FileSpan` — a (fd, offset, nbytes) triple the evloop's write
  queue holds alongside ordinary buffers. The flush pump moves it with
  ``os.sendfile`` — payload bytes go mmap-page -> socket inside the
  kernel and never enter the interpreter; only the ~9-byte frame header
  stays Python. ``py_bytes_per_frame ~= 0`` on the spliced path, by
  construction; the PR 16 cost model measures it and
  ``tests/test_workers.py`` pins it.
- **capability probe** — ``os.sendfile`` is Linux/macOS/FreeBSD; exotic
  sockets (AF_UNIX on some kernels, TLS wrappers) refuse it at call
  time with ENOTSOCK/EINVAL. :func:`sendfile_capable` answers the
  startup question; a per-call refusal downgrades THAT span to the
  existing sendmsg scatter-gather path with a loud flight breadcrumb
  (``splice_fallback``) — degrade, never die.
- **MSG_ZEROCOPY** — probed (:func:`zerocopy_capable`) and reported in
  telemetry, but NOT wired into the pump: its completion notifications
  arrive on the socket error queue, and releasing a staging lease
  before the kernel is done with the pages would corrupt in-flight
  sends — the exact contract ``_out_releases`` exists to protect. The
  probe keeps the capability visible so a future PR can add errqueue
  reaping; sendfile needs no such dance (it copies into the socket
  buffer kernel-side, or pins the page cache itself).

Telemetry rides the obs registry as the ``splice`` source, mirroring
``wire_codec``: spliced frames/bytes, per-reason fallbacks, capability
flags. The flush pump joins the ``event-loop-blocking`` audited graph
(the checker roots at it): ``os.sendfile`` on a non-blocking socket
returns short or raises ``BlockingIOError`` — it never blocks the loop.
"""

from __future__ import annotations

import errno
import os
import socket
import threading
from typing import Dict, Optional

from psana_ray_tpu.obs.flight import FLIGHT

__all__ = [
    "FileSpan",
    "sendfile_capable",
    "zerocopy_capable",
    "probe_report",
    "SPLICE",
]

#: errnos that mean "this socket/fd pair can't splice" — downgrade the
#: span, keep the connection (anything else is a real send error and
#: propagates like a failed sendmsg)
_FALLBACK_ERRNOS = frozenset(
    getattr(errno, n) for n in ("EINVAL", "ENOSYS", "ENOTSOCK", "ENOTSUP", "EOPNOTSUPP", "EBADF")
    if hasattr(errno, n)
)


class FileSpan:
    """A payload region of an on-disk segment, queued for kernel-side
    transmission.

    Holds the segment's OPEN file object (not a dup'd fd): the span is
    only ever queued while its record sits in the durable queue's
    ``_outstanding`` table, which pins the commit floor below the
    record's offset, which blocks ``_maybe_recycle`` from retiring the
    segment — the file object outlives every queued span by contract
    (see ``storage/log.py``). ``advance`` mutates in place so the flush
    pump resumes a partial sendfile without re-queueing.
    """

    __slots__ = ("_file", "pos", "nbytes")

    def __init__(self, file, pos: int, nbytes: int):
        self._file = file
        self.pos = int(pos)
        self.nbytes = int(nbytes)

    def fileno(self) -> int:
        return self._file.fileno()

    def advance(self, sent: int) -> None:
        """Consume ``sent`` bytes off the front (partial sendfile)."""
        self.pos += sent
        self.nbytes -= sent

    def materialize(self) -> bytes:
        """The remaining span as interpreter bytes — the sendmsg
        fallback (one pread; no seek, so the segment's own file
        position is untouched). Counted against the wire copy counters:
        these are exactly the payload bytes the spliced path keeps out
        of the interpreter, and the cost model's ``py_bytes_per_frame``
        must see the downgrade."""
        buf = os.pread(self._file.fileno(), self.nbytes, self.pos)
        try:
            from psana_ray_tpu.utils.bufpool import WIRE

            WIRE.add(len(buf))
        except Exception:
            pass
        return buf

    def __repr__(self) -> str:  # debugging/flight only
        return f"FileSpan(fd={self._file.fileno()}, pos={self.pos}, nbytes={self.nbytes})"


class SpliceTelemetry:
    """Counters for the kernel pass-through path (obs source
    ``splice``). Single-writer per counter in practice (the evloop
    thread owns the pump) but lock-guarded anyway: fallbacks can be
    noted from open/encode paths too."""

    def __init__(self):
        self._lock = threading.Lock()
        self._registered = False
        self.spliced_frames = 0  # guarded-by: _lock
        self.spliced_bytes = 0  # guarded-by: _lock
        self.sendfile_calls = 0  # guarded-by: _lock
        self.fallbacks: Dict[str, int] = {}  # reason -> count  # guarded-by: _lock

    def ensure_registered(self):
        with self._lock:
            if self._registered:
                return
            self._registered = True
        try:
            from psana_ray_tpu.obs.registry import MetricsRegistry

            MetricsRegistry.default().register("splice", self)
        except Exception:  # obs optional: splice must work without it
            pass

    def note_sendfile(self, nbytes: int) -> None:
        with self._lock:
            self.spliced_bytes += nbytes
            self.sendfile_calls += 1

    def note_frame(self) -> None:
        with self._lock:
            self.spliced_frames += 1

    def note_fallback(self, reason: str) -> None:
        """Count a downgrade to the sendmsg path; the FIRST sight of
        each reason leaves a flight breadcrumb (loud once, a counter
        forever — the runbook's 'reading the fallback breadcrumb')."""
        with self._lock:
            first = reason not in self.fallbacks
            self.fallbacks[reason] = self.fallbacks.get(reason, 0) + 1
        if first:
            FLIGHT.record("splice_fallback", reason=reason)

    def snapshot(self) -> dict:
        with self._lock:
            out = {
                "capable": 1 if sendfile_capable() else 0,
                "zerocopy_capable": 1 if zerocopy_capable() else 0,
                "spliced_frames_total": self.spliced_frames,
                "spliced_bytes_total": self.spliced_bytes,
                "sendfile_calls_total": self.sendfile_calls,
                "fallback_total": sum(self.fallbacks.values()),
            }
            for reason, n in self.fallbacks.items():
                out[f"fallback_{reason}_total"] = n
            return out


SPLICE = SpliceTelemetry()

_sendfile_capable: Optional[bool] = None
_zerocopy_capable: Optional[bool] = None


def sendfile_capable() -> bool:
    """Does this platform splice file->socket in the kernel? Answered
    once per process: ``os.sendfile`` exists AND works fd->fd here
    (probed with a real pipe-free socketpair + tempfile round trip —
    some platforms export the symbol but refuse sockets)."""
    global _sendfile_capable
    if _sendfile_capable is not None:
        return _sendfile_capable
    if not hasattr(os, "sendfile"):
        _sendfile_capable = False
        SPLICE.note_fallback("no_os_sendfile")
        return False
    try:
        import tempfile

        a, b = socket.socketpair()
        try:
            with tempfile.TemporaryFile() as f:
                f.write(b"probe")
                f.flush()
                # the kernel accepting all 5 bytes proves the fd pair
                # splices; no read-back needed (and none wanted — this
                # probe is reachable from telemetry snapshots, which
                # must never wait on a socket)
                _sendfile_capable = os.sendfile(a.fileno(), f.fileno(), 0, 5) == 5
        finally:
            a.close()
            b.close()
    except OSError:
        _sendfile_capable = False
    if not _sendfile_capable:
        SPLICE.note_fallback("probe_refused")
    return _sendfile_capable


def zerocopy_capable() -> bool:
    """MSG_ZEROCOPY support (Linux >= 4.14): probed for telemetry and
    the runbook, NOT used by the pump — see the module docstring for
    why (errqueue completions vs. the lease-release contract)."""
    global _zerocopy_capable
    if _zerocopy_capable is not None:
        return _zerocopy_capable
    if not (hasattr(socket, "SO_ZEROCOPY") and hasattr(socket, "MSG_ZEROCOPY")):
        _zerocopy_capable = False
        return False
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_ZEROCOPY, 1)
            _zerocopy_capable = True
        finally:
            s.close()
    except OSError:
        _zerocopy_capable = False
    return _zerocopy_capable


def fallback_errno(exc: OSError) -> bool:
    """Is this OSError a "can't splice HERE" refusal (downgrade the
    span) rather than a real send failure (kill the connection)?"""
    return exc.errno in _FALLBACK_ERRNOS


def probe_report() -> dict:
    """Startup-log summary (queue_server prints it once)."""
    return {
        "sendfile": sendfile_capable(),
        "msg_zerocopy": zerocopy_capable(),
    }
