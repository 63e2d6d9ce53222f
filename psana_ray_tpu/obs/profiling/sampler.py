"""Continuous flame sampler: bounded stack trie + 97 Hz daemon thread.

The host datapath's ceiling is Python CPU, but until this module nothing
measured WHERE that CPU goes. This module is the always-on half of the
answer: a daemon thread wakes ~97 times a second (off-aligned from the
100 Hz USER_HZ tick and from 1 Hz telemetry scrapes, so it never beats
against either), snapshots every thread's Python stack via
``sys._current_frames()``, and folds each stack into a bounded trie —
preallocated ``array`` columns for parent/key/counts, one interned
code-object key per frame — so the steady state allocates NOTHING and
the whole profile lives in a few hundred KB regardless of runtime.

Two discriminators keep the flame honest:

- **on-CPU vs waiting** — per-thread CPU time read from
  ``/proc/self/task/<tid>/stat`` (utime+stime, one ``os.pread`` of a
  cached fd per thread per sample; the clock equivalent of
  ``CLOCK_THREAD_CPUTIME_ID`` without a per-call syscall wrapper
  allocation). A thread whose CPU ticks did not advance since the last
  sample was waiting (GIL, select, queue get) and bills to the ``off``
  column — so blocked threads don't pollute the on-CPU flame. Where
  procfs is unavailable the sampler degrades to counting every sample
  as on-CPU rather than failing.
- **stage tags** — each sample bills to the
  :mod:`~psana_ray_tpu.obs.profiling.stagetag` tag its thread last
  declared, so the profile decomposes into the same
  enqueue/dequeue/batch/device_put vocabulary the latency histograms
  speak.

Sampling-loop functions are marked ``# lint: sample-path`` and kept
allocation-free by construction (the telemetry-discipline checker
enforces it); first-sight growth (new code object, new trie path, new
thread) happens in unmarked helpers, mirroring ``SeriesRing`` /
``TimeSeriesStore.record``. ``tests/test_profiling.py`` pins the
steady state with ``sys.getallocatedblocks``.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from array import array
from typing import Dict, List, Optional

from psana_ray_tpu.obs.profiling.stagetag import (
    N_TAGS,
    TAG_NAMES,
    _TAGS,
    clear_thread,
)
from psana_ray_tpu.obs.profiling.costmodel import ProfTelemetry

__all__ = ["StackTrie", "FlameSampler", "DEFAULT_HZ", "DEFAULT_MAX_NODES", "DEFAULT_MAX_DEPTH"]

#: Default sample rate. 97 is prime and off-aligned from the kernel's
#: 100 Hz accounting tick and the 1 Hz history sampler, so the profiler
#: neither aliases against scheduler quanta nor synchronises with other
#: periodic work (the classic "everything looks idle at the tick" trap).
DEFAULT_HZ = 97.0
DEFAULT_MAX_NODES = 8192
DEFAULT_MAX_DEPTH = 64


class StackTrie:
    """Bounded call-stack trie with preallocated count columns.

    Nodes are rows in parallel ``array`` columns (parent link, interned
    key, on-CPU count, waiting count); children are per-node dicts
    keyed by ``id(code)`` — the interned key — which stay hit-only once
    every hot path has been seen, so :meth:`sample` is allocation-free
    at steady state. The trie is rooted at one synthetic node per stage
    tag (negative keys), so (stage, stack) is a single path and export
    needs no join. When ``max_nodes`` is exhausted new paths bill to
    their deepest existing prefix and ``overflow_total`` counts what
    was truncated — a full trie degrades the profile, never the
    process.

    Single-writer by design: only the sampler thread calls
    :meth:`sample`; readers (exports, snapshots) tolerate a count
    landing one sample late rather than taking a lock on the hot path.
    """

    __slots__ = (
        "_cap",
        "_max_depth",
        "_parent",
        "_key",
        "_on",
        "_off",
        "_kids",
        "_code",
        "_stack",
        "_stage_root",
        "_stage_on",
        "_stage_off",
        "_n",
        "samples_total",
        "on_cpu_total",
        "waiting_total",
        "overflow_total",
    )

    def __init__(self, max_nodes: int = DEFAULT_MAX_NODES, max_depth: int = DEFAULT_MAX_DEPTH):
        cap = max(int(max_nodes), N_TAGS + 1)
        self._cap = cap
        self._max_depth = max(int(max_depth), 4)
        self._parent = array("l", [-1]) * cap
        self._key = array("q", [0]) * cap
        self._on = array("q", [0]) * cap
        self._off = array("q", [0]) * cap
        self._kids: List[Dict[int, int]] = []
        self._code: Dict[int, object] = {}  # id(code) -> code (keeps keys unique)
        self._stack = array("q", [0]) * self._max_depth
        self._stage_on = array("q", [0]) * N_TAGS
        self._stage_off = array("q", [0]) * N_TAGS
        self._n = 0
        self.samples_total = 0
        self.on_cpu_total = 0
        self.waiting_total = 0
        self.overflow_total = 0
        # one root per stage tag, key = -(tag + 1) (negative sentinel:
        # can never collide with an id())
        self._stage_root = array("l", [0]) * N_TAGS
        for t in range(N_TAGS):
            self._stage_root[t] = self._grow(-1, -(t + 1))

    @property
    def n_nodes(self) -> int:
        return self._n

    def _grow(self, parent: int, key: int) -> int:
        """First-sight node allocation (unmarked: runs once per new
        (stage, stack-prefix), never at steady state)."""
        n = self._n
        if n >= self._cap:
            return -1
        self._parent[n] = parent
        self._key[n] = key
        self._kids.append({})
        if parent >= 0:
            self._kids[parent][key] = n
        self._n = n + 1
        return n

    def sample(self, frame, on_cpu, tag):  # lint: sample-path
        """Fold one thread's stack into the trie (sampler thread only)."""
        stack = self._stack
        code_of = self._code
        lim = self._max_depth
        depth = 0
        f = frame
        while f is not None and depth < lim:
            c = f.f_code
            k = id(c)
            if k not in code_of:
                code_of[k] = c  # first sight of this code object
            stack[depth] = k
            depth += 1
            f = f.f_back
        node = self._stage_root[tag]
        kids = self._kids
        i = depth - 1  # stack is leaf-first; fold root-first
        while i >= 0:
            k = stack[i]
            nxt = kids[node].get(k, -1)
            if nxt < 0:
                nxt = self._grow(node, k)
                if nxt < 0:
                    self.overflow_total += 1
                    break  # bill to the deepest prefix that fit
            node = nxt
            i -= 1
        if on_cpu:
            self._on[node] += 1
            self._stage_on[tag] += 1
            self.on_cpu_total += 1
        else:
            self._off[node] += 1
            self._stage_off[tag] += 1
            self.waiting_total += 1
        self.samples_total += 1

    # ---- read side (cold: exports, dumps, tests) ----

    def _label(self, key: int) -> str:
        c = self._code.get(key)
        if c is None:
            return "?"
        name = getattr(c, "co_qualname", None) or c.co_name
        return "%s:%s:%d" % (os.path.basename(c.co_filename), name, c.co_firstlineno)

    def rows(self) -> List[dict]:
        """Every counted (stage, stack) path as
        ``{"stage", "frames", "on", "off"}`` — frames root-first."""
        out: List[dict] = []
        for node in range(self._n):
            on = self._on[node]
            off = self._off[node]
            if on == 0 and off == 0:
                continue
            frames: List[str] = []
            stage = TAG_NAMES[0]
            i = node
            while i >= 0:
                k = self._key[i]
                if k < 0:
                    stage = TAG_NAMES[-k - 1]
                else:
                    frames.append(self._label(k))
                i = self._parent[i]
            frames.reverse()
            out.append({"stage": stage, "frames": frames, "on": int(on), "off": int(off)})
        return out

    def hot_frames(self, n: int = 16) -> List[dict]:
        """Top-``n`` frames by SELF on-CPU samples (counts bill to the
        sampled leaf, so a node's count is its self time)."""
        agg: Dict[str, int] = {}
        for node in range(self._n):
            on = self._on[node]
            k = self._key[node]
            if on and k >= 0:
                lbl = self._label(k)
                agg[lbl] = agg.get(lbl, 0) + int(on)
        top = sorted(agg.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
        return [{"frame": lbl, "self": cnt} for lbl, cnt in top]

    def stage_totals(self) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for t in range(N_TAGS):
            on = int(self._stage_on[t])
            off = int(self._stage_off[t])
            if on or off:
                out[TAG_NAMES[t]] = {"on": on, "off": off}
        return out


class FlameSampler:
    """The continuous-profiler daemon thread.

    ``start()`` spawns one daemon thread that paces itself with a
    drift-corrected ``Event.wait`` (never ``time.sleep`` — the
    blocking-hot-path checker guards this file), samples every live
    thread into a :class:`StackTrie`, and about once a second does the
    cold housekeeping: cost-model tick (:class:`ProfTelemetry`), dead
    thread GC, procfs fd hygiene. ``stop()`` joins the thread, closes
    fds, and (when ``spool_dir`` is set) writes the spool JSON that
    ``python -m psana_ray_tpu.obs.prof_merge`` consumes.

    ``register=True`` publishes the cost model as the ``prof`` source
    on the obs MetricsRegistry so cpu_frac / cpu_ns_per_frame ride the
    existing history rings, Prometheus endpoint, and federation.
    """

    DEFAULT_HZ = DEFAULT_HZ

    def __init__(
        self,
        hz: float = DEFAULT_HZ,
        max_nodes: int = DEFAULT_MAX_NODES,
        max_depth: int = DEFAULT_MAX_DEPTH,
        process: str = "",
        spool_dir: Optional[str] = None,
        registry=None,
        register: bool = True,
        frames_fn=None,
        bytes_fn=None,
    ):
        self.hz = float(hz)
        if self.hz <= 0:
            raise ValueError("FlameSampler hz must be > 0 (use 0 at the CLI to disable)")
        self.period_s = 1.0 / self.hz
        self.process = process or os.path.basename(sys.argv[0] or "py")
        self.spool_dir = spool_dir
        self.trie = StackTrie(max_nodes=max_nodes, max_depth=max_depth)
        self.telemetry = ProfTelemetry(sampler=self, frames_fn=frames_fn, bytes_fn=bytes_fn)
        self._registry = registry
        self._register = register
        self._registered = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._own_ident = -1
        # ident -> [fd, last_cpu_ticks]; a 2-slot list so per-sample
        # updates mutate in place (no tuple churn)
        self._threads: Dict[int, list] = {}
        self.start_wall = 0.0
        self.start_mono = 0.0
        self.anchors: List[dict] = []

    # ---- lifecycle ----

    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> "FlameSampler":
        if self._thread is not None:
            return self
        self.start_wall = time.time()
        self.start_mono = time.monotonic()
        self.anchors.append({"wall": self.start_wall, "mono": self.start_mono})
        self._stop.clear()
        if self._register and not self._registered:
            try:
                if self._registry is None:
                    from psana_ray_tpu.obs.registry import MetricsRegistry

                    self._registry = MetricsRegistry.default()
                self._registry.register("prof", self.telemetry)
                self._registered = True
            except Exception:  # obs optional: profiler must work without it
                pass
        t = threading.Thread(target=self._run, name="prof-sampler", daemon=True)
        self._thread = t
        t.start()
        return self

    def stop(self, write_spool: bool = True) -> None:
        t = self._thread
        if t is None:
            return
        self._stop.set()
        t.join(timeout=2.0)
        self._thread = None
        self.telemetry.tick_cost_model()
        if self._registered and self._registry is not None:
            try:
                self._registry.unregister("prof")
            except Exception:
                pass
            self._registered = False
        for info in self._threads.values():
            if info[0] >= 0:
                try:
                    os.close(info[0])
                except OSError:
                    pass
        self._threads.clear()
        if write_spool and self.spool_dir:
            try:
                from psana_ray_tpu.obs.profiling.export import write_spool

                write_spool(self, directory=self.spool_dir)
            except Exception:
                pass

    def rearm_after_fork(self, process: Optional[str] = None) -> "FlameSampler":
        """Make a sampler inherited across ``os.fork`` valid in the CHILD.

        Fork clones neither the sampler thread nor the procfs task
        directory: ``self._thread`` points at a thread that does not
        exist here, and every cached ``/proc/self/task/<tid>/stat`` fd
        in ``self._threads`` describes the PARENT's threads (procfs
        fds stay readable post-fork — they would silently misattribute
        CPU). Reset both and restart. ``queue_server --workers`` forks
        BEFORE any sampler starts, so its workers never need this; it
        exists for embedders that fork with a live profiler, and
        ``process`` lets the child rename its spool (e.g. a worker id)
        so prof_merge shows it as its own process row."""
        self._thread = None  # the parent's thread; not ours to join
        self._stop.clear()
        for info in self._threads.values():
            if info[0] >= 0:
                try:
                    os.close(info[0])
                except OSError:
                    pass
        self._threads.clear()
        self._registered = False  # the child's registry is a fresh copy
        if process:
            self.process = process
        return self.start()

    # ---- sampling loop (hot: lint-guarded) ----

    def _run(self):  # lint: sample-path
        self._own_ident = threading.get_ident()
        period = self.period_s
        nxt = time.monotonic() + period
        last_house = 0.0
        while True:
            now = time.monotonic()
            delay = nxt - now
            if delay < 0.0:
                nxt = now + period  # fell behind (suspend, GIL storm): re-anchor
                delay = 0.0
            if self._stop.wait(delay):
                break
            self._sample_once()
            nxt += period
            now = time.monotonic()
            if now - last_house >= 1.0:
                last_house = now
                self._housekeep(now)

    def _sample_once(self):  # lint: sample-path
        frames = sys._current_frames()
        trie = self.trie
        own = self._own_ident
        tags = _TAGS
        for ident in frames:
            if ident == own:
                continue
            tag = tags.get(ident, 0)
            if tag < 0 or tag >= N_TAGS:
                tag = 0
            trie.sample(frames[ident], self._thread_on_cpu(ident), tag)
        # break the dict <-> own-frame reference cycle: the snapshot
        # holds THIS frame, whose locals hold the snapshot — without
        # this decref every tick leaves one cycle for the generational
        # GC (pinned by the zero-alloc test, which runs no GC)
        frames = None

    def _thread_on_cpu(self, ident):  # lint: sample-path
        """Did this thread's CPU clock advance since its last sample?
        One pread of a cached ``/proc/self/task/<tid>/stat`` fd; procfs
        regenerates the whole file at offset 0 so no seek/reopen."""
        info = self._threads.get(ident)
        if info is None:
            info = self._register_thread(ident)
        fd = info[0]
        if fd < 0:
            return True  # no procfs: count as on-CPU rather than guess
        try:
            data = os.pread(fd, 512, 0)
        except OSError:
            info[0] = -1  # thread exited between snapshot and read
            return True
        j = data.rfind(b")") + 2  # comm field may contain spaces; skip past it
        parts = data[j:].split()
        ticks = int(parts[11]) + int(parts[12])  # utime + stime
        prev = info[1]
        info[1] = ticks
        return ticks > prev

    # ---- cold helpers (first-sight / ~1 Hz) ----

    def _register_thread(self, ident) -> list:
        nid = -1
        for t in threading.enumerate():
            if t.ident == ident:
                nid = getattr(t, "native_id", None) or -1
                break
        fd = -1
        if nid > 0:
            try:
                fd = os.open("/proc/self/task/%d/stat" % nid, os.O_RDONLY)
            except OSError:
                fd = -1
        info = [fd, 0]
        self._threads[ident] = info
        return info

    def _housekeep(self, now: float) -> None:
        try:
            self.telemetry.tick_cost_model(now)
        except Exception:
            pass
        self._gc_threads()

    def _gc_threads(self) -> None:
        live = sys._current_frames()
        dead = [i for i in self._threads if i not in live]
        live = None  # same frame-cycle decref as _sample_once
        for ident in dead:
            info = self._threads.pop(ident, None)
            if info is not None and info[0] >= 0:
                try:
                    os.close(info[0])
                except OSError:
                    pass
            clear_thread(ident)

    # ---- read side ----

    def stage_cpu_ms(self) -> Dict[str, float]:
        """Per-stage on-CPU milliseconds (sample count x period)."""
        period_ms = 1000.0 / self.hz
        out: Dict[str, float] = {}
        totals = self.trie.stage_totals()
        for name, t in totals.items():
            out[name] = t["on"] * period_ms
        return out
