"""Size-classed receive-buffer pool with lease/release semantics.

The host fan-in ceiling is raw memory traffic: >= 3 frame-sized copies
per frame plus a fresh multi-MB allocation per hop. ``enable_large_alloc_reuse`` (utils/hostmem.py)
attacked the allocation half indirectly, by asking glibc to keep
MB-scale blocks on the heap; this module attacks it EXPLICITLY — the
transport hot path leases recycled buffers from a process-wide pool, so
steady-state receive costs zero allocations regardless of libc:

- :class:`BufferPool` — power-of-two size classes, bounded free lists,
  hit/miss/lease gauges for the obs registry (``bufpool.*``);
- :class:`Lease` — one checked-out buffer; ``release()`` is idempotent
  and also runs on GC, so a leaked record can delay reuse but never
  corrupts it (a buffer is NEVER handed out while its lease is alive);
- :class:`WireCounters` — process-wide copy accounting
  (``wire.bytes_copied`` / ``wire.copies_total``) so a test can
  pin copies/frame instead of inferring it.

Contract for view-backed records (records.decode with a lease): the
numpy view into the leased buffer is valid for the LIFETIME OF THE
RECORD. Release the lease only once the payload has been copied onward
(``FrameBatcher.push_view`` does this after the batch-arena copy);
holding the bare ``panels`` array past the record is undefined.

Debug mode (``PSANA_RAY_BUFPOOL_DEBUG=1`` or ``BufferPool(debug=True)``)
records the acquisition stack of every outstanding lease;
:meth:`BufferPool.leaks` returns them for leak hunts in tests.
"""

from __future__ import annotations

import os
import threading
import traceback
from typing import Dict, List, Optional

_MIN_CLASS = 1 << 12  # 4 KB — below this, pooling costs more than malloc


def _size_class(nbytes: int) -> int:
    c = _MIN_CLASS
    while c < nbytes:
        c <<= 1
    return c


class Lease:
    """One buffer checked out of a :class:`BufferPool`.

    ``mv`` is a writable memoryview of exactly the requested size (the
    backing buffer is the full size class). ``release()`` returns the
    buffer to the pool; it is idempotent and also fires from ``__del__``,
    so dropping the last reference to a lease (e.g. GC of a view-backed
    record that was never pushed) recycles the buffer instead of leaking
    it. Never release before the last read of any view into the buffer.
    """

    __slots__ = ("_pool", "_buf", "mv", "_released", "_origin", "__weakref__")

    def __init__(self, pool: "BufferPool", buf: bytearray, nbytes: int, origin=None):
        self._pool = pool
        self._buf = buf
        self.mv = memoryview(buf)[:nbytes]
        self._released = False
        self._origin = origin

    @property
    def nbytes(self) -> int:
        return len(self.mv)

    @property
    def pool(self) -> "BufferPool":
        """The owning pool — lets a decoder that was handed only a lease
        (e.g. the wire-compression decompressor, transport/codec.py)
        stage its output in a sibling lease from the SAME pool instead
        of threading the pool through every call site."""
        return self._pool

    def release(self):
        if self._released:
            return
        self._released = True
        self.mv = None  # drop the exported view before the buffer moves on
        self._pool._give_back(self._buf, self)
        self._buf = None

    def __del__(self):
        try:
            self.release()
        except Exception:
            pass

    def __enter__(self) -> "Lease":
        return self

    def __exit__(self, *exc):
        self.release()


class BufferPool:
    """Recycles byte buffers by power-of-two size class.

    ``lease(n)`` pops a free buffer of the smallest class >= n (hit) or
    allocates one (miss); ``Lease.release`` pushes it back. Retention
    per class is ADAPTIVE: the free list keeps up to
    ``max(min_per_class, peak concurrently-leased)`` buffers — a relay
    whose queue holds 64 frames in flight settles at ~64 retained
    buffers (they all existed simultaneously anyway, so this pins no
    new memory), while a ping-pong consumer settles at 1-2. Steady
    state is therefore zero allocations regardless of queue depth.
    Thread-safe; the whole exchange is a few dict/list ops under one
    lock.
    """

    _default: Optional["BufferPool"] = None  # guarded-by: _default_lock
    _default_lock = threading.Lock()

    def __init__(self, min_per_class: int = 4, debug: Optional[bool] = None):
        self.min_per_class = max(0, int(min_per_class))
        if debug is None:
            debug = os.environ.get("PSANA_RAY_BUFPOOL_DEBUG", "") not in ("", "0")
        self.debug = debug
        self._lock = threading.Lock()
        self._free: Dict[int, List[bytearray]] = {}  # guarded-by: _lock
        self._out_by_class: Dict[int, int] = {}  # currently leased  # guarded-by: _lock
        self._peak_by_class: Dict[int, int] = {}  # high-water leased  # guarded-by: _lock
        self._rel_by_class: Dict[int, int] = {}  # releases since last decay  # guarded-by: _lock
        self._hits = 0  # guarded-by: _lock
        self._misses = 0  # guarded-by: _lock
        # misses while the class was ALREADY at this concurrency before
        # (the pool could have retained a buffer but didn't) — the
        # steady-state allocation churn, as opposed to working-set growth
        self._churn_misses = 0  # guarded-by: _lock
        self._leases = 0  # currently outstanding  # guarded-by: _lock
        self._bytes_pooled = 0  # resident in free lists  # guarded-by: _lock
        self._outstanding: Dict[int, str] = {}  # id(lease) -> stack (debug)  # guarded-by: _lock

    @classmethod
    def default(cls) -> "BufferPool":
        """The process-wide pool every transport shares; registered as
        the ``bufpool`` source in the default obs MetricsRegistry on
        first use (CLI ``--metrics_port`` endpoints expose it with no
        extra wiring)."""
        with cls._default_lock:
            if cls._default is None:
                cls._default = BufferPool()
                try:
                    from psana_ray_tpu.obs import MetricsRegistry

                    MetricsRegistry.default().register("bufpool", cls._default)
                    MetricsRegistry.default().register("wire", WIRE)
                except Exception:  # obs optional: pool must work without it
                    pass
            return cls._default

    @classmethod
    def reset_default(cls):
        with cls._default_lock:
            cls._default = None

    def lease(self, nbytes: int) -> Lease:
        cls_bytes = _size_class(nbytes)
        with self._lock:
            free = self._free.get(cls_bytes)
            if free:
                buf = free.pop()
                self._bytes_pooled -= cls_bytes
                self._hits += 1
            else:
                buf = None
                self._misses += 1
            self._leases += 1
            out = self._out_by_class.get(cls_bytes, 0) + 1
            self._out_by_class[cls_bytes] = out
            if out > self._peak_by_class.get(cls_bytes, 0):
                self._peak_by_class[cls_bytes] = out
            elif buf is None:
                self._churn_misses += 1
        if buf is None:
            buf = bytearray(cls_bytes)
        origin = "".join(traceback.format_stack(limit=8)) if self.debug else None
        lease = Lease(self, buf, nbytes, origin)
        if self.debug:
            with self._lock:
                self._outstanding[id(lease)] = lease._origin
        return lease

    # every this many releases of a class, its retention peak decays 25%
    # toward the LIVE outstanding count — a one-time burst (a transient
    # consumer stall queueing hundreds of frames) stops pinning its
    # high-water of memory forever once steady state shrinks back
    DECAY_EVERY = 256

    def _give_back(self, buf: bytearray, lease: Lease):
        cls_bytes = len(buf)
        with self._lock:
            self._leases -= 1
            out = self._out_by_class.get(cls_bytes, 1) - 1
            self._out_by_class[cls_bytes] = out
            if self.debug:
                self._outstanding.pop(id(lease), None)
            rel = self._rel_by_class.get(cls_bytes, 0) + 1
            peak = self._peak_by_class.get(cls_bytes, 0)
            if rel >= self.DECAY_EVERY:
                rel = 0
                peak = max(out, peak - max(1, peak >> 2))
                self._peak_by_class[cls_bytes] = peak
            self._rel_by_class[cls_bytes] = rel
            free = self._free.setdefault(cls_bytes, [])
            keep = max(self.min_per_class, peak)
            while len(free) >= keep and free:  # trim after a decay
                free.pop()
                self._bytes_pooled -= cls_bytes
            if len(free) < keep:
                free.append(buf)
                self._bytes_pooled += cls_bytes

    def leaks(self) -> List[str]:
        """Acquisition stacks of outstanding leases (debug mode only)."""
        with self._lock:
            return list(self._outstanding.values())

    def stats(self) -> dict:
        with self._lock:
            return {
                "leases": self._leases,
                "hits": self._hits,
                "misses": self._misses,
                "churn_misses": self._churn_misses,
                "bytes_pooled": self._bytes_pooled,
                "classes": len(self._free),
            }

    # obs registry source protocol
    def snapshot(self) -> dict:
        return self.stats()


class WireCounters:
    """Process-wide payload-copy accounting for the wire datapath.

    Every frame-sized memcpy on the host datapath (decode-with-copy,
    encode-into-slot, batch-arena assembly) reports here, so copies/frame is a
    count and not an inference: ``tests/test_wire_zero_copy.py`` pins the
    consumer side to exactly one copy. Registered as
    the ``wire`` obs source alongside the default pool.
    """

    __slots__ = ("_lock", "bytes_copied", "copies")

    def __init__(self):
        self._lock = threading.Lock()
        self.bytes_copied = 0  # guarded-by: _lock
        self.copies = 0  # guarded-by: _lock

    def add(self, nbytes: int):
        with self._lock:
            self.bytes_copied += int(nbytes)
            self.copies += 1

    def stats(self) -> dict:
        with self._lock:
            return {"bytes_copied_total": self.bytes_copied, "copies_total": self.copies}

    def snapshot(self) -> dict:
        return self.stats()


WIRE = WireCounters()
