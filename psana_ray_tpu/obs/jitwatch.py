"""The program's account of its own start: what JAX traced, lowered,
loaded from the persistent cache and compiled, as spans and counters.

``setup_s`` and an operator's restart are mostly this: before a consumer's
first batch JAX traces every jitted function (Python), lowers it to MLIR,
asks the persistent compilation cache, and on a miss compiles. JAX times
all four itself and hands the numbers to whoever listens
(``jax.monitoring``); until this module nobody in the program did, and a
kernel whose unrolled body cost every warm start 1.5 s of TRACING was
found by hand, from debug logs, after the fact (PERF.md section 6, PRs 39,
41, 52).

:class:`JitWatch` registers listeners with ``jax.monitoring`` — they are
called on compile-path events only, so where nothing compiles (a serving
loop in its steady state) this costs nothing, and there is no "off" to be
faster than "on" — and keeps one row ``(kind, fun_name, t0, t1, thread)``
an event, ``t1 = time.monotonic()`` at the callback and ``t0 = t1 -
duration``: the clock of :class:`~psana_ray_tpu.obs.tracing.Tracer` spans
(and of the benchmark's marks and stops), so a span can be clipped to a
phase of set-up. ``kind`` is one of

- ``jit.trace`` — ``/jax/core/compile/jaxpr_trace_duration``;
- ``jit.lower`` — ``.../jaxpr_to_mlir_module_duration``;
- ``jit.cache_load`` — a ``.../backend_compile_duration`` whose request
  HIT the persistent cache (the event then spans the lookup, the read and
  the deserialization; ``cache_retrieval_time_sec`` lies inside it);
- ``jit.compile`` — a ``backend_compile_duration`` that missed the cache
  or did not ask it: a program this process had to compile.

``fun_name`` is normalised (``jit(keye_step)`` -> ``keye_step``), and a
function JAX knows only by a wrapper's name — ``wrapped``, which is
``pallas_call``'s own jit around a KERNEL'S BODY, or ``<lambda>`` — is
named with the function it was traced in: ``select_keys/wrapped`` is the
trace of that kernel's body, which every warm start pays.

Events NEST: an inner ``jax.jit`` called while an outer one is traced
reports its own duration inside the outer's (a decoder step fires
thousands of tiny ones, for ``add``, ``matmul``, ...), and an eager op
inside a trace lowers and compiles there. A kind's seconds are therefore
the UNION of its intervals on a thread, never the sum of durations, and a
function's OWN seconds are its interval less the intervals directly nested
in it. JAX announces the start of each timed region too (a scalar event),
which gives the listener a true stack per thread: unions and own seconds
are exact and cost O(1) an event. Rows are bounded (``MAX_ROWS``; beyond
it dropped and counted, never blocking); the aggregates by ``(kind,
fun_name)`` and the counters are not.

Where it goes (nothing here has no reader — PERF.md section 3):

- :meth:`Tracer.configure <psana_ray_tpu.obs.tracing.Tracer.configure>`
  takes the rows so far into its spool as phase rows named by kind, and
  later ones as they come, so ``obs/trace_merge.py`` shows a restart's
  trace / lower / load / compile on the process's track before its first
  batch;
- the metrics registry's source ``jit`` (``/metrics``, ``obs/top.py``, the
  time-series ring): :meth:`JitWatch.snapshot`;
- the serving loops log ONE line when their first result is out
  (:meth:`JitWatch.first_result`), and any load or compile after that is
  a flight-recorder event ``recompile``: the answer to "which step
  recompiled in mid-stream";
- the benchmark's six ``startup_*`` metrics read :meth:`JitWatch.record`.

Pure stdlib at import (``jax`` only inside :meth:`JitWatch.install`).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

__all__ = [
    "KINDS",
    "KIND_TRACE",
    "KIND_LOWER",
    "KIND_CACHE_LOAD",
    "KIND_COMPILE",
    "MAX_ROWS",
    "JitWatch",
    "WATCH",
    "install",
]

KIND_TRACE = "jit.trace"
KIND_LOWER = "jit.lower"
KIND_CACHE_LOAD = "jit.cache_load"
KIND_COMPILE = "jit.compile"
KINDS = (KIND_TRACE, KIND_LOWER, KIND_CACHE_LOAD, KIND_COMPILE)

MAX_ROWS = 10_000

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_TIMED = {_TRACE_EVENT: 0, _LOWER_EVENT: 1, _BACKEND_EVENT: 3}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_LOAD, _COMPILE = 2, 3
_ANONYMOUS = ("wrapped", "<lambda>")  # names that say nothing without their caller's

Row = Tuple[str, str, float, float, int]


def _short(fun_name: str) -> str:
    """``jit(keye_step)`` -> ``keye_step``; anything else as it is."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _process_start() -> float:
    """The instant this process started, on ``time.monotonic()``'s clock
    (``/proc``'s start time against the boot clock; where that cannot be
    read, the instant this module was imported)."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat", "rb") as f:
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < 7 * 86400.0:
            return now - age
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return now


class _Open:
    """One timed region of JAX's that a thread is inside."""

    __slots__ = ("event", "name", "nested", "covered")

    def __init__(self, event: str, name: str):
        self.event = event
        self.name = name
        self.nested = 0.0  # seconds of the regions directly inside this one
        self.covered = None  # seconds by kind that its descendants span (a list once it has one)


class JitWatch:
    """The listener, its bounded rows and its unbounded sums."""

    def __init__(self, max_rows: int = MAX_ROWS):
        self.max_rows = int(max_rows)
        self.installed = False
        #: a serving loop's first result is out: a load or compile from
        #: here on is a ``recompile`` (read by the loops, one flag)
        self.serving = False
        self.t_process = _process_start()
        self._lock = threading.Lock()
        self._local = threading.local()
        self._forget()

    def _forget(self) -> None:
        # guarded-by-caller: _lock (but in __init__)
        self._rows: List[Row] = []
        self._dropped = 0
        self._dropped_from: Optional[float] = None  # the earliest start among the dropped rows
        self._seconds = [0.0, 0.0, 0.0, 0.0]  # a kind's union, summed over threads
        self._counts = [0, 0, 0, 0]  # events by kind: loads are the cache's hits, compiles its misses
        # (kind, fun_name) -> [events, seconds (durations summed), own seconds]
        self._by_fun: Dict[Tuple[str, str], List[float]] = {}
        self._sink: Optional[Callable] = None  # a Tracer's phase_span while it spools

    # -- registration -----------------------------------------------------
    def install(self) -> "JitWatch":
        """Register with ``jax.monitoring`` (once: a second call changes
        nothing). The module's :func:`install` does it for the process's
        :data:`WATCH` and makes it the metrics registry's source ``jit``."""
        with self._lock:
            if self.installed:
                return self
            self.installed = True
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_scalar_listener(self._on_start)
        return self

    def uninstall(self) -> None:
        """Take the listeners back and forget what was heard (tests: a CLI's
        ``main`` run in-process must not listen into the next test)."""
        with self._lock:
            was, self.installed, self.serving = self.installed, False, False
            self._forget()
        if not was:
            return
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_scalar_listener(self._on_start)

    # -- jax.monitoring callbacks (compile-path events only) ----------------
    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _on_start(self, event: str, value, fun_name: str = "", **kw) -> None:
        """JAX entered a timed region (it records the start as a scalar)."""
        if event not in _TIMED:
            return
        stack, name = self._stack(), _short(str(fun_name))
        if name in _ANONYMOUS and stack:  # a kernel's body, a lambda: whose?
            name = f"{stack[-1].name.split('/')[0]}/{name}"
        stack.append(_Open(event, name))

    def _on_event(self, event: str, **kw) -> None:
        """The persistent cache was asked / answered: what the NEXT
        ``backend_compile_duration`` of this thread was."""
        if event == _CACHE_HIT:
            self._local.hit = True
        elif event == _CACHE_ASKED:
            self._local.hit = False

    def _on_duration(self, event: str, duration: float, fun_name: str = "", **kw) -> None:
        k = _TIMED.get(event)
        if k is None:
            return
        t1 = time.monotonic()
        duration = float(duration)
        if k == _COMPILE and getattr(self._local, "hit", False):
            k = _LOAD
        if event == _BACKEND_EVENT:
            self._local.hit = False
        stack = self._stack()
        me = stack.pop() if stack else None
        if me is not None and me.event != event:  # a start without its end: begin again
            stack.clear()
            me = None
        if me is None:
            covered, own, fun = None, duration, _short(str(fun_name))
        else:
            covered, own, fun = me.covered, max(0.0, duration - me.nested), me.name
        if covered is not None:
            covered[k] = duration  # this region spans whatever of its kind lay inside
        parent = stack[-1] if stack else None
        if parent is not None:
            parent.nested += duration
            if parent.covered is None:
                parent.covered = [0.0, 0.0, 0.0, 0.0]
            self._add_spans(parent.covered, covered, k, duration)
        kind = KINDS[k]
        row = (kind, fun, t1 - duration, t1, threading.get_ident())
        with self._lock:
            if parent is None:  # outermost on its thread: its spans are a union's parts
                self._add_spans(self._seconds, covered, k, duration)
            self._counts[k] += 1
            agg = self._by_fun.get((kind, fun))
            if agg is None:
                agg = self._by_fun[(kind, fun)] = [0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += duration
            agg[2] += own
            if len(self._rows) < self.max_rows:
                self._rows.append(row)
            else:
                self._dropped += 1
                if self._dropped_from is None or row[2] < self._dropped_from:
                    self._dropped_from = row[2]
            sink, serving = self._sink, self.serving
        if sink is not None:
            sink(0, kind, row[2], t1, label=fun)
        if serving and k in (_LOAD, _COMPILE):
            from psana_ray_tpu.obs.flight import FLIGHT

            FLIGHT.record("recompile", fun_name=fun, seconds=duration, hit=k == _LOAD)

    @staticmethod
    def _add_spans(into: list, covered, k: int, duration: float) -> None:
        """A closed region's seconds by kind (its own kind's are its
        duration; a leaf has no others) into its parent's, or the sums."""
        if covered is None:
            into[k] += duration
        else:
            for j in range(4):
                into[j] += covered[j]

    # -- readers -----------------------------------------------------------
    def record(self) -> Tuple[List[Row], int]:
        """``(rows so far, rows dropped past the bound)``: what the
        benchmark's ``startup_*`` metrics read. A record with drops is
        short from :meth:`dropped_from` on, and a reader of a span that
        reaches past that instant should refuse it."""
        with self._lock:
            return list(self._rows), self._dropped

    def dropped_from(self) -> Optional[float]:
        """The earliest start among the rows dropped (``None``: none was):
        rows are kept in the order they end, so before this instant the
        record is whole — a start's account stands although a later
        phase of the process (a benchmark's reference check) overran the
        bound."""
        with self._lock:
            return self._dropped_from

    def attach(self, sink: Callable) -> List[Row]:
        """Send every later row to ``sink(0, kind, t0, t1, label=fun_name)``
        (a tracer's ``phase_span``) and return the rows so far: none is
        lost or doubled between the two."""
        with self._lock:
            self._sink = sink
            return list(self._rows)

    def detach(self, sink: Callable) -> None:
        with self._lock:
            if self._sink == sink:
                self._sink = None

    def by_function(self) -> Dict[Tuple[str, str], Tuple[int, float, float]]:
        """``{(kind, fun_name): (events, seconds, own seconds)}``, unbounded:
        ``seconds`` sums the durations (a nested function's are in its
        caller's too), ``own`` leaves out what was nested directly inside."""
        with self._lock:
            return {key: (int(v[0]), v[1], v[2]) for key, v in self._by_fun.items()}

    def snapshot(self) -> dict:
        """Registry source ``jit``: each kind's seconds (unions), the
        persistent cache's hits (loads) and misses (compiles), rows
        dropped past the bound."""
        with self._lock:
            s, c = self._seconds, self._counts
            return {
                "jit_trace_seconds_total": round(s[0], 6),
                "jit_lower_seconds_total": round(s[1], 6),
                "jit_cache_load_seconds_total": round(s[2], 6),
                "jit_compile_seconds_total": round(s[3], 6),
                "jit_cache_hits_total": c[_LOAD],
                "jit_cache_misses_total": c[_COMPILE],
                "jit_spans_dropped_total": self._dropped,
            }

    # -- the operator's line -------------------------------------------------
    def first_result(self, who: str) -> None:
        """A serving loop's first result is out: log the start's account,
        once a process, and call every load or compile from here on a
        ``recompile``. Without the listener there is nothing to say."""
        with self._lock:
            if self.serving:
                return
            self.serving = True
            installed = self.installed
        if installed:
            logger.info("%s", self.start_line(who))

    def start_line(self, who: str) -> str:
        """Seconds since the process started, each kind's seconds, hits and
        misses, the three functions with the most own seconds by kind, and
        what missed the cache."""
        since = time.monotonic() - self.t_process
        snap, by_fun = self.snapshot(), self.by_function()
        missed = sorted(fun for kind, fun in by_fun if kind == KIND_COMPILE)
        parts = []
        for kind in KINDS:
            top = sorted(((v[2], fun) for (k, fun), v in by_fun.items() if k == kind), reverse=True)
            if top:
                parts.append(kind[4:] + " " + ", ".join(f"{fun} {own:.3f}" for own, fun in top[:3]))
        return (
            f"{who}: first result {since:.2f} s after the process started; jit seconds: "
            f"trace {snap['jit_trace_seconds_total']:.3f}, lower {snap['jit_lower_seconds_total']:.3f}, "
            f"cache load {snap['jit_cache_load_seconds_total']:.3f}, "
            f"compile {snap['jit_compile_seconds_total']:.3f}; persistent cache: "
            f"{snap['jit_cache_hits_total']} hits, {snap['jit_cache_misses_total']} misses"
            f"{', ' + str(snap['jit_spans_dropped_total']) + ' rows dropped' if snap['jit_spans_dropped_total'] else ''}"
            f"; most own seconds: {'; '.join(parts) or 'none'}; compiled here: "
            f"{', '.join(missed[:12]) + (' ...' if len(missed) > 12 else '') if missed else 'nothing'}"
        )


#: The process's listener: ``jaxenv.configure_compile_cache()`` installs it.
WATCH = JitWatch()


def install() -> JitWatch:
    """Install :data:`WATCH` (idempotent) and register it as the metrics
    registry's source ``jit``."""
    WATCH.install()
    from psana_ray_tpu.obs.registry import MetricsRegistry

    MetricsRegistry.default().register("jit", WATCH)
    return WATCH
