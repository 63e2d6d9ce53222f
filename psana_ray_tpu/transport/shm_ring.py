"""Cross-process shared-memory ring: ctypes bindings over the C++ MPMC ring.

Same contract as :class:`psana_ray_tpu.transport.ring.RingBuffer` — put ->
bool / get -> item|EMPTY / size / close-with-TransportClosed — but the
queue lives in POSIX shared memory, so independent producer and consumer
*processes* on one host exchange frames with a single memcpy each way (the
reference needed two cross-node object-store hops through a Ray actor,
SURVEY.md §3.3).

Payloads are the wire format of :mod:`psana_ray_tpu.records` (FrameRecord /
EndOfStream); arbitrary Python objects are supported via pickle with a
1-byte tag.

The C library builds on demand with ``make`` (g++); see
``psana_ray_tpu/native/``.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import pickle
import subprocess
import threading
import time
from typing import Any, List, Optional

from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.records import EndOfStream, FrameRecord, encode_into, encoded_size
from psana_ray_tpu.transport.codec import TAG_PICKLE as _TAG_PICKLE
from psana_ray_tpu.transport.codec import TAG_RECORD as _TAG_RECORD
from psana_ray_tpu.transport.codec import TAG_VOID as _TAG_VOID
from psana_ray_tpu.transport.codec import decode_payload
from psana_ray_tpu.transport.registry import TransportClosed, TransportWedged
from psana_ray_tpu.transport.ring import EMPTY

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libshmring.so")
_STAMP_PATH = _LIB_PATH + ".stamp"  # source digest the .so was built from

_lib = None
_lib_lock = threading.Lock()


def _source_digest() -> str:
    """Content hash of everything ``make`` builds the library from."""
    h = hashlib.sha256()
    for fname in sorted(os.listdir(_NATIVE_DIR)):
        if fname.endswith((".cpp", ".h", ".hpp")) or fname == "Makefile":
            h.update(fname.encode())
            with open(os.path.join(_NATIVE_DIR, fname), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _lib_is_stale() -> bool:
    """True unless the .so was built from the native sources as they are
    now — a stale binary must never shadow an edited shmring.cpp. Judged
    by the source digest the build stamps beside it, not by mtimes: a
    copied tree (rsync, tar, a container layer) need not keep them."""
    if not os.path.exists(_LIB_PATH):
        return True
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip() != _source_digest()
    except FileNotFoundError:
        return True


def _load_lib() -> ctypes.CDLL:
    """Load (building/rebuilding if needed) the native library. Raises
    RuntimeError with guidance when no toolchain is available or the
    binary does not load on this platform.

    Build + load run under an inter-process file lock: the runbook starts
    producer and consumers near-simultaneously, and without the lock each
    process would race its own ``make`` while another dlopens the
    half-written .so."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        import fcntl

        lock_path = os.path.join(_NATIVE_DIR, ".build.lock")
        with open(lock_path, "w") as lock_f:
            fcntl.flock(lock_f, fcntl.LOCK_EX)
            try:
                if _lib_is_stale():  # re-check under the lock: a sibling
                    try:             # process may have just built it
                        digest = _source_digest()  # of what make reads
                        subprocess.run(
                            ["make", "-C", _NATIVE_DIR, "-s", "-B"],
                            check=True,
                            capture_output=True,
                            timeout=120,
                        )
                        with open(_STAMP_PATH, "w") as stamp_f:
                            stamp_f.write(digest)
                    except (
                        subprocess.CalledProcessError,
                        FileNotFoundError,
                        subprocess.TimeoutExpired,
                    ) as e:
                        detail = getattr(e, "stderr", b"")
                        if not os.path.exists(_LIB_PATH):
                            raise RuntimeError(
                                "could not build native shm ring (needs g++/make); "
                                "use the in-process RingBuffer or TCP transport "
                                f"instead: {detail!r}"
                            ) from e
                        # stale-but-present binary + no toolchain: load as-is
                try:
                    lib = ctypes.CDLL(_LIB_PATH)
                except OSError as e:  # wrong arch/glibc for a prebuilt binary
                    raise RuntimeError(
                        f"native shm ring library failed to load on this platform "
                        f"({e}); use the in-process RingBuffer or TCP transport instead"
                    ) from e
            finally:
                fcntl.flock(lock_f, fcntl.LOCK_UN)
        lib.shmring_create.restype = ctypes.c_void_p
        lib.shmring_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64]
        lib.shmring_attach.restype = ctypes.c_void_p
        lib.shmring_attach.argtypes = [ctypes.c_char_p]
        lib.shmring_put.restype = ctypes.c_int
        lib.shmring_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.shmring_get.restype = ctypes.c_int64
        lib.shmring_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        for fn in ("shmring_size", "shmring_capacity", "shmring_slot_bytes"):
            getattr(lib, fn).restype = ctypes.c_uint64
            getattr(lib, fn).argtypes = [ctypes.c_void_p]
        lib.shmring_reserve.restype = ctypes.c_int
        lib.shmring_reserve.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.shmring_commit.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]
        lib.shmring_acquire.restype = ctypes.c_int64
        lib.shmring_acquire.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.shmring_release.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.shmring_is_closed.restype = ctypes.c_int
        lib.shmring_is_closed.argtypes = [ctypes.c_void_p]
        lib.shmring_set_stall_timeout.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.shmring_begin_drain.argtypes = [ctypes.c_void_p]
        lib.shmring_close.argtypes = [ctypes.c_void_p]
        lib.shmring_stats.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64 * 7)]
        lib.shmring_free.argtypes = [ctypes.c_void_p, ctypes.c_int]
        _lib = lib
        return _lib


def native_available() -> bool:
    try:
        _load_lib()
        return True
    except RuntimeError:
        return False


def _stamped(item: Any, enq_ns: int) -> Any:
    """Hand the slot's enqueue stamp (CLOCK_MONOTONIC ns: the clock of
    ``time.monotonic()``, one for every process of the host) to a frame
    record as ``t_enq`` seconds — where the consumer's side of the hop
    starts the frame's timeline (``batches_from_queue``)."""
    if isinstance(item, FrameRecord):
        object.__setattr__(item, "t_enq", enq_ns * 1e-9)
    return item


class _SlotLease:
    """A consumed-but-unreleased ring slot backing a zero-copy record.

    ``get_batch_view`` hands out records whose panels view slot memory
    directly; this lease keeps the slot out of producers' hands until
    the payload has been copied onward (``FrameBatcher.push_view``
    releases right after the batch-arena copy). Idempotent; also fires
    on GC, so a dropped record frees its slot instead of wedging the
    ring. Holds the ring object itself — the mapping cannot be detached
    by GC while any slot lease is alive, and release after an explicit
    disconnect/destroy degrades to a no-op instead of touching a freed
    C handle."""

    __slots__ = ("_ring", "_ticket", "_released")

    def __init__(self, ring: "ShmRingBuffer", ticket: int):
        self._ring = ring
        self._ticket = ticket
        self._released = False

    def release(self):
        if self._released:
            return
        self._released = True
        ring = self._ring
        self._ring = None
        with ring._handle_lock:
            ring._slot_leases -= 1
            if ring._h:
                ring._lib.shmring_release(ring._h, self._ticket)

    def __del__(self):
        try:
            self.release()
        except Exception:
            pass


class ShmRingBuffer:
    """MPMC shared-memory queue; create on one process, attach on others."""

    # epix10k2M f32 frame = 8.6 MB; default slot fits it + header slack
    DEFAULT_SLOT_BYTES = 9 * 1024 * 1024

    def __init__(self, handle, name: str, owner: bool):
        self._h = handle  # guarded-by: _handle_lock
        self.name = name
        self._owner = owner
        self._lib = _load_lib()
        # immutable after creation; cached so put()/put_wait spins skip
        # the FFI round trip
        self._slot_bytes = int(self._lib.shmring_slot_bytes(handle))
        self._voids_skipped = 0  # guarded-by: _handle_lock
        # outstanding zero-copy gets (see _SlotLease)
        self._slot_leases = 0  # guarded-by: _handle_lock
        # serializes EVERY use of the C handle — the read surface
        # (stats/size — scraped from metrics HTTP threads), the data ops
        # (put/get: held across the FFI call, so disconnect() can never
        # free the handle mid-memcpy), and teardown itself — against
        # disconnect()/destroy() freeing it: a check-then-use on _h alone
        # can still pass a freed pointer to C when any of them races
        # teardown (the PR 1 segfault class). REENTRANT because a
        # _SlotLease can release from __del__ — cyclic GC may run it on
        # the very thread that already holds this lock
        self._handle_lock = threading.RLock()

    def set_stall_timeout(self, seconds: float):
        """Wedge-detection window for THIS handle (0 disables): a slot
        claimed by a peer but left uncommitted/unreleased longer than this
        raises :class:`TransportWedged` instead of stalling forever."""
        with self._handle_lock:
            self._lib.shmring_set_stall_timeout(self._live_handle(), int(seconds * 1000))

    def _wedged_msg(self, peer: str, verb: str) -> str:
        # breadcrumb for the flight recorder: a wedged ring is the exact
        # postmortem case the black box exists for
        FLIGHT.record("shm_wedged", ring=self.name, peer=peer)
        return (
            f"shm ring {self.name!r} is wedged: a {peer} process claimed a "
            f"slot and never {verb} it (likely crashed mid-operation). "
            f"Destroy and recreate the ring to recover; in-flight items in "
            f"the wedged region are lost."
        )

    # -- construction -----------------------------------------------------
    @classmethod
    def create(
        cls, name: str, maxsize: int = 64, slot_bytes: int = DEFAULT_SLOT_BYTES
    ) -> "ShmRingBuffer":
        lib = _load_lib()
        h = lib.shmring_create(cls._shm_name(name), maxsize, slot_bytes)
        if not h:
            raise RuntimeError(f"shmring_create({name!r}) failed")
        return cls(h, name, owner=True)

    @classmethod
    def attach(cls, name: str, retries: int = 10, interval_s: float = 1.0) -> "ShmRingBuffer":
        """Attach with the rendezvous retry semantics (producer.py:56-67)."""
        lib = _load_lib()
        deadline = time.monotonic() + retries * interval_s
        while True:
            h = lib.shmring_attach(cls._shm_name(name))
            if h:
                return cls(h, name, owner=False)
            if time.monotonic() >= deadline:
                from psana_ray_tpu.transport.registry import RendezvousTimeout

                raise RendezvousTimeout(
                    f"shm ring {name!r} not found after {retries} x {interval_s}s"
                )
            time.sleep(interval_s)

    @staticmethod
    def _shm_name(name: str) -> bytes:
        clean = "".join(c if c.isalnum() or c in "-_." else "_" for c in name)
        return f"/psana_ray_tpu_{clean}".encode()

    # -- transport contract ----------------------------------------------
    # put/get serialize straight into / out of the claimed slot memory
    # (shmring_reserve/commit + acquire/release): a FrameRecord costs ONE
    # numpy memcpy each way instead of the bytes-assembly + ctypes-buffer
    # + decode-copy chain (measured 38 -> ~300 fps on 8.6 MB epix frames).
    def put(self, item: Any) -> bool:
        wire = isinstance(item, (FrameRecord, EndOfStream))
        slot_bytes = self._slot_bytes
        if wire:
            n = 1 + encoded_size(item)
            payload = None
        else:
            payload = _TAG_PICKLE + pickle.dumps(item, protocol=pickle.HIGHEST_PROTOCOL)
            n = len(payload)
        if n > slot_bytes:
            raise ValueError(f"message of {n} bytes exceeds slot size {slot_bytes}")
        ptr = ctypes.c_void_p()
        ticket = ctypes.c_uint64()
        # the lock is held across reserve -> encode -> commit: disconnect/
        # destroy must not munmap the slot while the memcpy into it runs
        # (reserve and commit are non-blocking C calls, and in-process
        # producers sharing one handle were already serialized by the GIL
        # around the FFI boundary, so this costs no real concurrency)
        with self._handle_lock:
            h = self._live_handle()
            rc = self._lib.shmring_reserve(h, ctypes.byref(ptr), ctypes.byref(ticket))
            if rc == 0:
                return False
            if rc == -2:
                raise TransportClosed(f"shm ring {self.name!r} is closed")
            if rc == -4:
                raise TransportWedged(self._wedged_msg("consumer", "released"))
            mv = memoryview((ctypes.c_ubyte * slot_bytes).from_address(ptr.value)).cast("B")
            ok = False
            try:
                if wire:
                    mv[0:1] = _TAG_RECORD
                    encode_into(item, mv[1:n])
                else:
                    mv[:n] = payload
                ok = True
            finally:
                # always publish the claimed slot — an unreleased claim
                # would wedge every consumer at this position forever. A
                # failed encode publishes a 1-byte void marker consumers
                # skip.
                if not ok:
                    mv[0:1] = _TAG_VOID
                self._lib.shmring_commit(h, ticket, n if ok else 1)
        return True

    def get(self) -> Any:
        return self._get(view=False)

    def get_view(self) -> Any:
        """Zero-copy get: a FrameRecord's panels VIEW the ring slot, the
        slot stays claimed, and the record carries a :class:`_SlotLease`
        — release it (``rec.release()`` / ``FrameBatcher.push_view``)
        right after copying the payload onward. Each outstanding lease
        keeps one slot from producers, so never hold many across
        blocking waits. Non-frame payloads decode as owned objects with
        the slot released immediately (same as :meth:`get`)."""
        return self._get(view=True)

    def _get(self, view: bool) -> Any:
        # loops past void slots (producer-side encode failures): a void is
        # consumed-and-skipped, NOT "empty" — real items may sit right
        # behind it, and reporting EMPTY here could convince a get_wait
        # caller at its deadline that the queue starved
        while True:
            ptr = ctypes.c_void_p()
            ticket = ctypes.c_uint64()
            enq_ns = ctypes.c_uint64()
            # held across acquire -> decode -> release: teardown must not
            # munmap the slot while the decode copy (or the zero-copy view
            # hand-off) reads it — the same UAF class as the PR 1 scrape
            # segfault, on the data path. RLock: _SlotLease.release (e.g.
            # via GC inside decode's allocations) re-enters safely.
            with self._handle_lock:
                h = self._live_handle()
                n = self._lib.shmring_acquire(
                    h, ctypes.byref(ptr), ctypes.byref(ticket), ctypes.byref(enq_ns)
                )
                if n == -1:
                    return EMPTY
                if n == -2:
                    raise TransportClosed(f"shm ring {self.name!r} is closed")
                if n == -4:
                    raise TransportWedged(self._wedged_msg("producer", "committed"))
                mv = memoryview((ctypes.c_ubyte * int(n)).from_address(ptr.value)).cast("B")
                if bytes(mv[:1]) == _TAG_VOID:
                    self._voids_skipped += 1
                    self._lib.shmring_release(h, ticket)
                    continue
                if not view:
                    try:
                        # copies panels out of the slot
                        return _stamped(self._decode(mv), enq_ns.value)
                    finally:
                        self._lib.shmring_release(h, ticket)
                self._slot_leases += 1
                lease = _SlotLease(self, int(ticket.value))
                try:
                    return _stamped(decode_payload(mv, lease=lease), enq_ns.value)
                except BaseException:
                    lease.release()
                    raise

    def get_wait(self, timeout: Optional[float] = None, poll_s: float = 0.0002) -> Any:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            item = self.get()
            if item is not EMPTY:
                return item
            if deadline is not None and time.monotonic() >= deadline:
                return EMPTY
            time.sleep(poll_s)

    def put_wait(self, item: Any, timeout: Optional[float] = None, poll_s: float = 0.0002) -> bool:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            if self.put(item):
                return True
            if deadline is not None and time.monotonic() >= deadline:
                return False
            time.sleep(poll_s)

    def get_batch(self, max_items: int, timeout: Optional[float] = None) -> List[Any]:
        return self._get_batch(max_items, timeout, view=False)

    def get_batch_view(self, max_items: int, timeout: Optional[float] = None) -> List[Any]:
        """Batch drain with ZERO-COPY records (see :meth:`get_view`):
        the one-memcpy consumer path ``batches_from_queue`` prefers when
        the transport offers it. Blocks only for the first item; every
        returned frame holds its slot until released, so consume the
        batch promptly (the batcher copies + releases per record)."""
        return self._get_batch(max_items, timeout, view=True)

    def _get_batch(self, max_items: int, timeout: Optional[float], view: bool) -> List[Any]:
        out = []
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:  # blocking first-get, matching get_wait's poll loop
            first = self._get(view)
            if first is not EMPTY:
                break
            if deadline is not None and time.monotonic() >= deadline:
                return out
            time.sleep(0.0002)
        out.append(first)
        while len(out) < max_items:
            item = self._get(view)
            if item is EMPTY:
                break
            out.append(item)
        return out

    def _live_handle(self):
        """The C handle, or TransportClosed after disconnect()/destroy().
        Every surface that hands the handle to C (data ops, stats/size
        scrapes — possibly after teardown) must fail as a catchable
        dead-transport error, never hand NULL to C (a segfault)."""
        # guarded-by-caller: _handle_lock
        h = self._h
        if not h:
            raise TransportClosed(f"shm ring {self.name!r} is detached")
        return h

    def size(self) -> int:
        with self._handle_lock:
            return int(self._lib.shmring_size(self._live_handle()))

    @property
    def maxsize(self) -> int:
        with self._handle_lock:
            return int(self._lib.shmring_capacity(self._live_handle()))

    @property
    def closed(self) -> bool:
        with self._handle_lock:
            return bool(self._lib.shmring_is_closed(self._live_handle()))

    def close(self):
        # no-op after disconnect()/destroy(): there is nothing left to
        # close, and the C side dereferences the handle without a NULL
        # check (same segfault class _live_handle guards the read surface
        # against; teardown paths may close and detach in either order —
        # the lock makes the check-then-use atomic vs a concurrent free)
        with self._handle_lock:
            if self._h:
                self._lib.shmring_close(self._h)

    def begin_drain(self):
        """Half-close for graceful teardown: producer puts/reserves are
        refused (they see the closed signal, a clean exit) while gets keep
        serving. Cross-process: every attached producer observes it."""
        with self._handle_lock:
            if self._h:
                self._lib.shmring_begin_drain(self._h)

    def stats(self) -> dict:
        """Depth and counters, plus the queue residency (enqueue stamp ->
        pop) of every item popped so far, by any handle of the ring:
        ``dwell_ms_mean``/``dwell_ms_max`` over ``dwell_count`` items —
        ``queue_dwell`` for every frame, with no tracing on."""
        buf = (ctypes.c_uint64 * 7)()
        with self._handle_lock:
            h = self._live_handle()
            self._lib.shmring_stats(h, ctypes.byref(buf))
            maxsize = int(self._lib.shmring_capacity(h))
            voids = self._voids_skipped
        return {
            "depth": int(buf[0]),
            "maxsize": maxsize,
            "puts": int(buf[1]),
            "gets": int(buf[2]),
            "puts_rejected": int(buf[3]),
            "voids_skipped": voids,
            "dwell_count": int(buf[6]),
            "dwell_ms_mean": buf[4] / buf[6] / 1e6 if buf[6] else 0.0,
            "dwell_ms_max": buf[5] / 1e6,
        }

    def disconnect(self):
        """Detach this handle (the ring survives for other processes)."""
        with self._handle_lock:
            self._warn_live_leases("disconnect")
            if self._h:
                self._lib.shmring_free(self._h, 0)
                self._h = None

    def destroy(self):
        """Detach AND unlink the shared memory object."""
        with self._handle_lock:
            self._warn_live_leases("destroy")
            if self._h:
                self._lib.shmring_free(self._h, 1)
                self._h = None

    def _warn_live_leases(self, what: str):
        # guarded-by-caller: _handle_lock. Unmapping under a zero-copy record's
        # panels view is use-after-munmap; surface it loudly — the fix is
        # to release (push_view/materialize) before teardown.
        if self._h and self._slot_leases > 0:
            logger.warning(
                "%s(%s) with %d zero-copy slot lease(s) outstanding — "
                "views into this ring become invalid",
                what, self.name, self._slot_leases,
            )

    def __del__(self):
        try:
            self.disconnect()
        except Exception:
            pass

    # -- payload codec ----------------------------------------------------
    @staticmethod
    def _decode(buf) -> Any:
        return decode_payload(buf)  # copies panels out of the slot view
