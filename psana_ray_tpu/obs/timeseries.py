"""Per-process metrics HISTORY: a bounded ring of registry snapshots.
# lint: hot-path

PR 1/PR 4 gave every process counters, stage histograms, traces and a
flight recorder — each an INSTANTANEOUS, single-process view. ISSUE 13
adds the time axis: a :class:`HistorySampler` thread periodically
flattens the process's :class:`~psana_ray_tpu.obs.registry.
MetricsRegistry` snapshot (the exact flattening grammar the Prometheus
renderer uses — :func:`~psana_ray_tpu.obs.registry.flatten_numeric`)
into per-key :class:`SeriesRing` buffers.

Design rules (the self-tuning controller of ROADMAP item 3 reads these
rings at high rate, and the sampler rides every process):

- **bounded**: one ring per key, fixed capacity, preallocated
  ``array('d')`` storage — memory is ``O(keys x capacity)`` forever;
- **zero-alloc on sample**: :meth:`SeriesRing.append` is index
  arithmetic into the preallocated arrays (``# lint: sample-path``,
  enforced by the ``telemetry-discipline`` checker). A ring is
  allocated ONCE, the first time its key appears;
- **views at read time**: delta / windowed rate / EWMA / percentile are
  computed from the ring when ASKED (:meth:`TimeSeriesStore.rate` and
  friends) — the sample path stays counter arithmetic, the analysis
  cost lands on the reader (console, controller, collector), never the
  pipeline.

The flight recorder appends :meth:`TimeSeriesStore.tail` to every dump
(ISSUE 13 satellite): a postmortem shows the minutes BEFORE the
trigger, not just the instant.

Pure stdlib, importable without numpy/jax (every process pays the
import).
"""

from __future__ import annotations

import threading
import time
from array import array
from typing import Dict, List, Optional, Tuple

from psana_ray_tpu.obs.registry import MetricsRegistry, flatten_numeric

__all__ = [
    "SeriesRing",
    "TimeSeriesStore",
    "HistorySampler",
    "add_history_args",
    "configure_history_from_args",
    "default_history",
]

DEFAULT_CAPACITY = 600  # 10 min of history at the default 1 s interval
DEFAULT_INTERVAL_S = 1.0


class SeriesRing:
    """Fixed-capacity (t, value) ring for ONE key: preallocated twin
    ``array('d')`` columns, append = two indexed stores + counter
    arithmetic (no allocation — pinned by the telemetry-discipline
    checker's sample-path rule and tests/test_timeseries.py)."""

    __slots__ = ("_t", "_v", "_cap", "_n", "_i")

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity <= 1:
            raise ValueError("SeriesRing capacity must be > 1")
        self._cap = int(capacity)
        self._t = array("d", [0.0]) * self._cap
        self._v = array("d", [0.0]) * self._cap
        self._n = 0  # samples held (saturates at _cap)
        self._i = 0  # next write slot

    def append(self, t: float, v: float) -> None:  # lint: sample-path
        i = self._i
        self._t[i] = t
        self._v[i] = v
        self._i = i + 1 if i + 1 < self._cap else 0
        if self._n < self._cap:
            self._n += 1

    def __len__(self) -> int:
        return self._n

    @property
    def capacity(self) -> int:
        return self._cap

    def samples(self, n: Optional[int] = None) -> List[Tuple[float, float]]:
        """The last ``n`` (t, value) pairs in time order (all when None).
        Read-time allocation is fine — this is the VIEW side."""
        count = self._n if n is None else min(int(n), self._n)
        if count <= 0:
            return []
        start = (self._i - count) % self._cap
        out = []
        for k in range(count):
            j = (start + k) % self._cap
            out.append((self._t[j], self._v[j]))
        return out

    def last(self) -> Optional[Tuple[float, float]]:
        if not self._n:
            return None
        j = (self._i - 1) % self._cap
        return (self._t[j], self._v[j])


class TimeSeriesStore:
    """``{key: SeriesRing}`` + the read-time views (delta / rate / EWMA /
    percentile). One per process (:func:`default_history`), one per
    federated peer inside the collector."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._capacity = int(capacity)
        self._lock = threading.Lock()
        self._rings: Dict[str, SeriesRing] = {}  # guarded-by: _lock
        self._samples_total = 0  # sweeps recorded  # guarded-by: _lock
        self._last_t = 0.0  # guarded-by: _lock

    # -- sample path -------------------------------------------------------
    def record(self, tree: dict, now: Optional[float] = None) -> int:
        """Flatten one registry snapshot tree and append every numeric
        leaf to its ring (allocating a ring only on FIRST sight of a
        key). Returns the number of keys written."""
        now = time.time() if now is None else now
        leaves: List[Tuple[str, float]] = []
        flatten_numeric((), tree, leaves)
        with self._lock:
            rings = self._rings
            for key, value in leaves:
                ring = rings.get(key)
                if ring is None:  # first sight only: steady state allocates nothing
                    ring = rings[key] = SeriesRing(self._capacity)
                ring.append(now, value)
            self._samples_total += 1
            self._last_t = now
        return len(leaves)

    # -- read-time views ---------------------------------------------------
    def keys(self) -> List[str]:
        with self._lock:
            return sorted(self._rings)

    def series(self, key: str, n: Optional[int] = None) -> List[Tuple[float, float]]:
        # the copy-out happens UNDER the lock: a concurrent record()
        # advancing the ring head mid-read would otherwise tear the view
        with self._lock:
            ring = self._rings.get(key)
            return ring.samples(n) if ring is not None else []

    def last(self, key: str) -> Optional[float]:
        with self._lock:
            ring = self._rings.get(key)
            lt = ring.last() if ring is not None else None
        return lt[1] if lt is not None else None

    def delta(self, key: str, window_s: Optional[float] = None) -> Optional[float]:
        """value[last] - value[first sample inside the window] (whole ring
        when ``window_s`` is None). None with <2 samples."""
        pts = self._window(key, window_s)
        if len(pts) < 2:
            return None
        return pts[-1][1] - pts[0][1]

    def rate(self, key: str, window_s: Optional[float] = None) -> Optional[float]:
        """delta / elapsed over the window — the counter-to-rate view
        (e.g. ``queue_server.default.puts`` -> puts/s)."""
        pts = self._window(key, window_s)
        if len(pts) < 2 or pts[-1][0] <= pts[0][0]:
            return None
        return (pts[-1][1] - pts[0][1]) / (pts[-1][0] - pts[0][0])

    def ewma(self, key: str, alpha: float = 0.2,
             window_s: Optional[float] = None) -> Optional[float]:
        pts = self._window(key, window_s)
        if not pts:
            return None
        acc = pts[0][1]
        for _, v in pts[1:]:
            acc += alpha * (v - acc)
        return acc

    def percentile(self, key: str, q: float,
                   window_s: Optional[float] = None) -> Optional[float]:
        pts = self._window(key, window_s)
        if not pts:
            return None
        vals = sorted(v for _, v in pts)
        return vals[min(len(vals) - 1, max(0, int(q * len(vals))))]

    def _window(self, key: str, window_s: Optional[float]) -> List[Tuple[float, float]]:
        pts = self.series(key)
        if window_s is None or not pts:
            return pts
        cutoff = pts[-1][0] - window_s
        return [p for p in pts if p[0] >= cutoff]

    def tail(self, n: int = 32, keys: Optional[List[str]] = None) -> Dict[str, list]:
        """The last ``n`` samples per key as JSON-safe rows — what the
        flight recorder appends to a dump (the minutes BEFORE the
        trigger)."""
        out: Dict[str, list] = {}
        for key in (keys if keys is not None else self.keys()):
            pts = self.series(key, n)
            if pts:
                out[key] = [[round(t, 3), v] for t, v in pts]
        return out

    # -- registry source ---------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {
                "keys": len(self._rings),
                "capacity": self._capacity,
                "samples_total": self._samples_total,
                "last_sample_age_s": round(time.time() - self._last_t, 3)
                if self._last_t else -1.0,
            }


class HistorySampler:
    """The per-process sampling loop: every ``interval_s`` take ONE
    registry snapshot and record it into the store. A daemon thread with
    a bounded Event wait; ``sample_once`` is exposed so tests drive
    time explicitly."""

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        store: Optional[TimeSeriesStore] = None,
        interval_s: float = DEFAULT_INTERVAL_S,
        capacity: int = DEFAULT_CAPACITY,
    ):
        if interval_s <= 0:
            raise ValueError("interval_s must be positive (0 = don't build one)")
        self.registry = registry  # None = resolve default() per sample
        self.store = store if store is not None else TimeSeriesStore(capacity)
        self.interval_s = float(interval_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._sweeps = 0  # guarded-by: _lock
        self._last_ms = 0.0  # cost of the last sweep  # guarded-by: _lock
        self._max_ms = 0.0  # guarded-by: _lock

    def sample_once(self, now: Optional[float] = None) -> int:
        reg = self.registry if self.registry is not None else MetricsRegistry.default()
        t0 = time.perf_counter()
        n = self.store.record(reg.snapshot(), now=now)
        ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            self._sweeps += 1
            self._last_ms = ms
            if ms > self._max_ms:
                self._max_ms = ms
        return n

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.sample_once()
            except Exception:  # noqa: BLE001 — history must outlive a bad source
                pass

    def start(self) -> "HistorySampler":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="history-sampler"
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def __enter__(self) -> "HistorySampler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- registry source (the observer observes itself) --------------------
    def snapshot(self) -> dict:
        with self._lock:
            out = {
                "interval_s": self.interval_s,
                "sweeps_total": self._sweeps,
                "sweep_last_ms": round(self._last_ms, 3),
                "sweep_max_ms": round(self._max_ms, 3),
            }
        out.update(self.store.snapshot())
        return out


# -- process-global wiring ---------------------------------------------------
_default_lock = threading.Lock()
_default_sampler: Optional[HistorySampler] = None


def default_history() -> Optional[TimeSeriesStore]:
    """The process's history store, or None when no sampler was started
    (the flight recorder asks on every dump — absent history must cost
    nothing and fail nothing)."""
    with _default_lock:
        return _default_sampler.store if _default_sampler is not None else None


def start_default_history(
    interval_s: float = DEFAULT_INTERVAL_S,
    capacity: int = DEFAULT_CAPACITY,
    registry: Optional[MetricsRegistry] = None,
) -> HistorySampler:
    """Start (or return) THE process-global sampler and register it as
    the ``timeseries`` registry source. Idempotent: the first caller's
    interval/capacity win (one history per process)."""
    global _default_sampler
    with _default_lock:
        if _default_sampler is None:
            _default_sampler = HistorySampler(
                registry=registry, interval_s=interval_s, capacity=capacity
            ).start()
            reg = registry if registry is not None else MetricsRegistry.default()
            reg.register("timeseries", _default_sampler)
        return _default_sampler


def stop_default_history() -> None:
    """Stop + forget the process-global sampler (tests)."""
    global _default_sampler
    with _default_lock:
        sampler, _default_sampler = _default_sampler, None
    if sampler is not None:
        sampler.stop()


# -- CLI wiring --------------------------------------------------------------
def add_history_args(parser) -> None:
    """The shared ``--history_interval`` / ``--history_samples`` pair
    every long-running CLI exposes (one definition, like
    ``add_metrics_args``)."""
    parser.add_argument(
        "--history_interval", type=float, default=DEFAULT_INTERVAL_S,
        help="sample the metrics registry into the in-process "
        "time-series history ring every N seconds (feeds flight-dump "
        "tails, the federation collector, and `python -m "
        "psana_ray_tpu.obs.top`); 0 = off",
    )
    parser.add_argument(
        "--history_samples", type=int, default=DEFAULT_CAPACITY,
        help="bounded per-key ring capacity for --history_interval "
        "(memory is O(keys x samples), preallocated)",
    )


def configure_history_from_args(args) -> Optional[HistorySampler]:
    """CLI entry: start the process-global history sampler from the
    ``add_history_args`` flags (None when ``--history_interval 0``)."""
    interval = getattr(args, "history_interval", 0.0) or 0.0
    if interval <= 0:
        return None
    return start_default_history(
        interval_s=interval,
        capacity=max(2, int(getattr(args, "history_samples", DEFAULT_CAPACITY))),
    )
