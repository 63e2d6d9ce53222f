"""Crash flight recorder: a bounded in-memory ring of structured events,
dumped to disk with a metrics snapshot and all thread stacks when the
pipeline wedges.

The black box for postmortems: counters tell you THAT a run degenerated;
the flight recorder tells you the last N things that happened before it
did (queue ops, reconnects, EOS markers, stall events, errors) plus what
every thread was doing at the moment of the dump. Recording is always on
and cheap (one deque append under a lock, and only at RARE control-plane
events — never per frame); dumping requires :meth:`FlightRecorder.
install` with a directory.

Dump triggers (ISSUE 4):

- a :class:`~psana_ray_tpu.obs.stall.StallDetector` event (wire
  ``on_event=FLIGHT.on_stall`` — the queue server CLI does);
- an unhandled exception (``install`` chains ``sys.excepthook``);
- ``SIGUSR2`` (``kill -USR2 <pid>`` on any wedged process).

Pure stdlib, importable without JAX or numpy.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import signal
import socket
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Dict, Optional

logger = logging.getLogger(__name__)

__all__ = ["FlightRecorder", "FLIGHT"]

# Rate limit between automatic dumps (stall storms fire once per episode
# already, but several queues can degenerate at once): one dump per
# window keeps the postmortem readable and the disk bounded.
DUMP_MIN_INTERVAL_S = 5.0

# Samples per key of time-series history appended to a dump (ISSUE 13):
# at the default 1 s sampling this is the last ~minute of every series
# BEFORE the trigger — the "how did we get here", where the metrics
# snapshot is only the "where we ended up".
TAIL_SAMPLES = 64


def _thread_stacks() -> Dict[str, list]:
    """Every live thread's current stack, keyed ``name-ident`` — the
    "what was everyone doing" half of the dump."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        key = f"{names.get(ident, 'unknown')}-{ident}"
        out[key] = [ln.rstrip("\n") for ln in traceback.format_stack(frame)]
    return out


class FlightRecorder:
    """Bounded ring of structured events + the dump machinery."""

    def __init__(self, maxlen: int = 1024):
        # REENTRANT: the SIGUSR2 handler runs in the MAIN thread between
        # bytecodes and calls record()/dump() — if the signal lands while
        # that same thread already holds this lock (mid-record/snapshot),
        # a plain Lock would deadlock the process the operator was trying
        # to diagnose. Handler re-entry under an RLock only ever appends
        # to the ring mid-operation, which is harmless.
        self._lock = threading.RLock()
        self._events: deque = deque(maxlen=maxlen)
        self._counts: Dict[str, int] = {}
        self._total = 0
        self._dumps = 0
        self._last_dump = 0.0
        self._dir: Optional[str] = None
        self._process = ""
        self._host = socket.gethostname()
        self._prev_sighandler = None
        self._prev_excepthook = None
        self._prev_threading_excepthook = None
        self._installed_signum: Optional[int] = None

    # -- recording (always on, rare events only) --------------------------
    def record(self, kind: str, /, **detail) -> None:
        """Append one structured event; bounded ring, never blocks, never
        raises into the caller (the black box must not take down the
        plane). The reserved keys (kind/wall/mono) win over same-named
        detail fields."""
        try:
            evt = dict(detail)
            evt["kind"] = kind
            evt["wall"] = time.time()
            evt["mono"] = time.monotonic()
            with self._lock:
                self._events.append(evt)
                self._total += 1
                self._counts[kind] = self._counts.get(kind, 0) + 1
        except Exception:  # noqa: BLE001
            logger.debug("flight record failed", exc_info=True)

    @property
    def event_count(self) -> int:
        with self._lock:
            return self._total

    def events(self) -> list:
        with self._lock:
            return list(self._events)

    def count_of(self, *kinds: str) -> int:
        """Lifetime count across the named event kinds (counts survive
        ring eviction — heartbeat summaries must not undercount)."""
        with self._lock:
            return sum(self._counts.get(k, 0) for k in kinds)

    # -- dump machinery ---------------------------------------------------
    def install(
        self,
        dump_dir: str,
        process: str = "",
        signum: Optional[int] = None,
        excepthook: bool = True,
    ) -> "FlightRecorder":
        """Arm dumping into ``dump_dir``: SIGUSR2 (or ``signum``) dumps on
        demand, and unhandled exceptions dump before the interpreter dies
        (the previous hook still runs). Signal installation is skipped off
        the main thread (Python restriction) — the excepthook and
        programmatic triggers still work there."""
        os.makedirs(dump_dir, exist_ok=True)
        with self._lock:
            # armed-state stores under the lock: dump()/snapshot() read
            # them there, and arming must never race a dump into a
            # half-set (dir, process) pair
            self._dir = dump_dir
            self._process = process or self._process
            proc = self._process
        if signum is None:
            signum = getattr(signal, "SIGUSR2", None)
        if signum is not None and threading.current_thread() is threading.main_thread():
            try:
                self._prev_sighandler = signal.signal(signum, self._on_signal)
                self._installed_signum = signum
            except (ValueError, OSError):  # non-main thread / unsupported
                self._installed_signum = None
        if excepthook:
            self._prev_excepthook = sys.excepthook
            sys.excepthook = self._on_exception
            # sys.excepthook never fires for non-main threads (Python
            # 3.8+ routes those to threading.excepthook) — a crashing
            # worker (serve thread, prefetcher, pump) is exactly the
            # multithreaded wedge the black box exists for
            self._prev_threading_excepthook = threading.excepthook
            threading.excepthook = self._on_thread_exception
        self.record("flight_installed", dir=dump_dir, process=proc)
        return self

    def uninstall(self) -> None:
        """Restore the previous signal handler / excepthook (tests)."""
        if self._installed_signum is not None and self._prev_sighandler is not None:
            try:
                signal.signal(self._installed_signum, self._prev_sighandler)
            except (ValueError, OSError):
                pass
        self._installed_signum = None
        self._prev_sighandler = None
        if self._prev_excepthook is not None:
            sys.excepthook = self._prev_excepthook
            self._prev_excepthook = None
        if self._prev_threading_excepthook is not None:
            threading.excepthook = self._prev_threading_excepthook
            self._prev_threading_excepthook = None
        with self._lock:
            self._dir = None

    def _on_signal(self, signum, frame):
        self.record("sigusr2", signum=int(signum))
        # dump from a SEPARATE thread, never the signal frame: the dump
        # takes a metrics-registry snapshot, which acquires other
        # sources' plain (non-reentrant) locks — the interrupted main
        # thread may be HOLDING one of them mid-observation (Tracer.span,
        # Meter.add, ...), and acquiring it from the handler would
        # deadlock the very process the operator is diagnosing. A helper
        # thread just blocks until the main thread resumes and releases.
        threading.Thread(
            target=self.dump, args=("signal",), kwargs={"force": True},
            daemon=True, name="flight-dump",
        ).start()
        prev = self._prev_sighandler
        if callable(prev):
            prev(signum, frame)

    def _on_thread_exception(self, hook_args):
        """threading.excepthook chain: a worker thread died uncaught."""
        self.record(
            "unhandled_thread_exception",
            thread=getattr(hook_args.thread, "name", "?"),
            exc_type=getattr(hook_args.exc_type, "__name__", str(hook_args.exc_type)),
            message=str(hook_args.exc_value),
        )
        self.dump(
            "thread_exception",
            trigger={
                "thread": getattr(hook_args.thread, "name", "?"),
                "exc_type": getattr(
                    hook_args.exc_type, "__name__", str(hook_args.exc_type)
                ),
                "message": str(hook_args.exc_value),
                "traceback": traceback.format_exception(
                    hook_args.exc_type, hook_args.exc_value, hook_args.exc_traceback
                ),
            },
            force=True,
        )
        prev = self._prev_threading_excepthook or threading.__excepthook__
        prev(hook_args)

    def _on_exception(self, exc_type, exc, tb):
        self.record(
            "unhandled_exception",
            exc_type=getattr(exc_type, "__name__", str(exc_type)),
            message=str(exc),
        )
        self.dump(
            "exception",
            trigger={
                "exc_type": getattr(exc_type, "__name__", str(exc_type)),
                "message": str(exc),
                "traceback": traceback.format_exception(exc_type, exc, tb),
            },
            force=True,
        )
        prev = self._prev_excepthook or sys.__excepthook__
        prev(exc_type, exc, tb)

    def on_stall(self, event) -> None:
        """`StallDetector(on_event=...)` hook: record the stall AND dump —
        a wedged pipeline is exactly what the black box exists for."""
        detail = (
            dataclasses.asdict(event) if dataclasses.is_dataclass(event) else {"event": repr(event)}
        )
        self.record("stall", stall_kind=detail.get("kind"), **{
            k: v for k, v in detail.items() if k != "kind"
        })
        self.dump("stall", trigger=detail)

    def dump(
        self,
        reason: str,
        trigger: Optional[dict] = None,
        path: Optional[str] = None,
        force: bool = False,
    ) -> Optional[str]:
        """Write the black box to disk: the event ring, a metrics-registry
        snapshot, and every thread's stack. Returns the path, or None when
        no directory is armed / the rate limit suppressed it. Never raises
        (logged instead): the dump rides failure paths."""
        try:
            with self._lock:
                if self._dir is None and path is None:
                    return None
                now = time.monotonic()
                if not force and now - self._last_dump < DUMP_MIN_INTERVAL_S:
                    return None
                self._last_dump = now
                self._dumps += 1
                seq = self._dumps
                events = list(self._events)
                counts = dict(self._counts)
                # armed-state snapshot: the file write below runs OUTSIDE
                # the lock (record() callers must not block on disk), so
                # take a coherent (dir, process) pair here
                dump_dir, proc = self._dir, self._process
            try:
                from psana_ray_tpu.obs.registry import MetricsRegistry

                metrics = MetricsRegistry.default().snapshot()
            except Exception as e:  # noqa: BLE001 — snapshot is best-effort
                metrics = {"error": repr(e)}
            # the local time-series tail (ISSUE 13): the minutes BEFORE
            # the trigger, when a history sampler is running — absent
            # history costs nothing and fails nothing
            tail = None
            try:
                from psana_ray_tpu.obs.timeseries import default_history

                hist = default_history()
                if hist is not None:
                    tail = hist.tail(TAIL_SAMPLES)
            except Exception as e:  # noqa: BLE001 — best-effort like metrics
                tail = {"error": repr(e)}
            # WHAT the process was burning CPU on when it stalled
            # (ISSUE 16): top hot frames + per-stage cpu_ms from the
            # live flame sampler — null when profiling is off
            prof_top = None
            try:
                from psana_ray_tpu.obs.profiling import profile_top

                prof_top = profile_top(16)
            except Exception as e:  # noqa: BLE001 — best-effort like metrics
                prof_top = {"error": repr(e)}
            doc = {
                "reason": reason,
                "trigger": trigger,
                "host": self._host,
                "pid": os.getpid(),
                "process": proc,
                "wall": time.time(),
                "mono": time.monotonic(),
                "event_counts": counts,
                "events": events,
                "metrics": metrics,
                "timeseries_tail": tail,
                "profile_top": prof_top,
                "threads": _thread_stacks(),
            }
            if path is None:
                stamp = time.strftime("%Y%m%d-%H%M%S")
                path = os.path.join(
                    dump_dir,
                    f"flight-{proc or 'proc'}-{os.getpid()}-{stamp}-{seq}.json",
                )
            # whole or not at all: a reader polling the directory (an
            # operator's tail, the tests) never sees half a document
            with open(path + ".tmp", "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=1)
            os.replace(path + ".tmp", path)
            # the tracer holds its spans in memory until a flush: a dump
            # rides failure paths, so what led here goes to the spool now
            from psana_ray_tpu.obs.tracing import TRACER

            TRACER.flush()
            logger.warning("flight recorder dump (%s) -> %s", reason, path)
            return path
        except Exception:  # noqa: BLE001 — the black box must not crash the plane
            logger.exception("flight recorder dump failed")
            return None

    # -- registry source ---------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            out: Dict[str, Any] = {
                "events_total": self._total,
                "dumps_total": self._dumps,
                "armed": self._dir is not None,
            }
            for kind, n in self._counts.items():
                out[f"events_{kind}_total"] = n
        return out


#: The process-global recorder; call sites record into it unconditionally
#: (rare control-plane events only), CLIs arm dumping via ``install``.
FLIGHT = FlightRecorder()
