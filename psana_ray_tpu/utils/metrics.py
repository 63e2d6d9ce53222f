"""Metrics: throughput meters, latency quantiles, queue-depth gauges.

The reference's only observability is per-event log lines and an uncalled
``Queue.size()`` (SURVEY.md §5 "Metrics: ... no metrics export, no
counters"). This module provides the counters the runbook needs: frames/s,
bytes/s, p50/p95/p99 latency (reservoir), queue depth snapshots, and the
per-stage latency histograms (:class:`StageTimes`) the pipeline threads
through the record envelope. Export (Prometheus text format over HTTP) and
stall detection live in :mod:`psana_ray_tpu.obs`; this module stays pure
stdlib and thread-safe so every process can afford it.
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence


def probe_queue_stats(queue) -> Dict:
    """One queue-health probe for every observability surface: the full
    ``stats()`` dict when the backing provides it (RingBuffer,
    ShmRingBuffer, TcpQueueClient), depth-only otherwise. Raises whatever
    the backing raises — error policy (skip / report closed / drop the
    source) belongs to the caller."""
    stats = getattr(queue, "stats", None)
    if callable(stats):
        return dict(stats())
    return {"depth": queue.size()}


class Meter:
    """Monotonic counter + windowed rate."""

    def __init__(self, name: str = ""):
        self.name = name
        self._lock = threading.Lock()
        self._count = 0
        self._t0 = time.monotonic()
        self._window: deque = deque()  # (t, cumulative)

    def add(self, n: int = 1):
        with self._lock:
            self._count += n
            now = time.monotonic()
            self._window.append((now, self._count))
            cutoff = now - 10.0
            while self._window and self._window[0][0] < cutoff:
                self._window.popleft()

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    def rate(self) -> float:
        """Events/s over the trailing 10 s window (lifetime rate if the
        window has <2 samples)."""
        with self._lock:
            if len(self._window) >= 2:
                (t_a, c_a), (t_b, c_b) = self._window[0], self._window[-1]
                if t_b > t_a:
                    return (c_b - c_a) / (t_b - t_a)
            dt = time.monotonic() - self._t0
            return self._count / dt if dt > 0 else 0.0

    def snapshot(self) -> Dict[str, float]:
        return {"total": self.count, "per_second": round(self.rate(), 3)}


# Exemplar bucket boundaries (ms, upper-inclusive; the last bucket is
# +inf). Log-scaled like a Prometheus latency histogram: an operator
# asking "what is IN the bad bucket" gets one retained trace id per
# bucket (Dapper-style exemplars, ISSUE 13) — `trace_merge --exemplar
# <id>` resolves it to the frame's cross-host timeline.
EXEMPLAR_BUCKETS_MS = (
    1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0,
    2500.0, float("inf"),
)


def _bucket_of(ms: float) -> int:
    for i, le in enumerate(EXEMPLAR_BUCKETS_MS):
        if ms <= le:
            return i
    return len(EXEMPLAR_BUCKETS_MS) - 1


class LatencyStats:
    """Reservoir-sampled latency quantiles (fixed memory, unbiased).

    The sorted view is CACHED and invalidated on ``observe``, so a burst of
    quantile reads (``summary_ms`` used to sort three times per status
    line) pays for at most one sort per new sample.

    ``observe(seconds, exemplar=...)`` additionally retains the LAST
    exemplar (a trace id) seen per latency bucket
    (:data:`EXEMPLAR_BUCKETS_MS`) — bounded memory (one slot per
    bucket), zero cost for callers that never pass one.
    """

    def __init__(self, reservoir_size: int = 4096, seed: int = 0):
        self._lock = threading.Lock()
        self._size = reservoir_size
        self._n = 0
        self._sum = 0.0
        self._samples: List[float] = []
        self._sorted: Optional[List[float]] = None
        self._rng = random.Random(seed)
        # bucket index -> (exemplar trace id, observed ms)
        self._exemplars: Dict[int, tuple] = {}  # guarded-by: _lock

    def observe(self, seconds: float, exemplar: Optional[int] = None):
        with self._lock:
            self._n += 1
            self._sum += seconds
            if exemplar is not None:
                self._exemplars[_bucket_of(seconds * 1e3)] = (
                    exemplar, seconds * 1e3,
                )
            if len(self._samples) < self._size:
                self._samples.append(seconds)
                self._sorted = None
            else:
                j = self._rng.randrange(self._n)
                if j < self._size:
                    self._samples[j] = seconds
                    # rejected samples (the common case once n >> size)
                    # leave the reservoir untouched — keep the cache hot
                    self._sorted = None

    def exemplars(self) -> Dict[str, Dict[str, float]]:
        """``{"le_<bound_ms>": {"trace_id": "0x...", "ms": ...}}`` — the
        retained exemplar per non-empty latency bucket. Trace ids render
        as hex strings (the form ``trace_merge --exemplar`` accepts);
        the whole ``exemplars`` subtree is excluded from the numeric
        flatten (``obs.registry.flatten_numeric``), so exemplars reach
        /healthz and the drill-down tooling but never mint Prometheus
        gauges or history rings."""
        with self._lock:
            items = list(self._exemplars.items())
        out: Dict[str, Dict[str, float]] = {}
        for idx, (tid, ms) in items:
            le = EXEMPLAR_BUCKETS_MS[idx]
            label = "le_inf" if le == float("inf") else f"le_{le:g}"
            out[label] = {"trace_id": f"{int(tid):#x}", "ms": round(ms, 3)}
        return out

    def _sorted_view(self) -> List[float]:
        # guarded-by-caller: _lock
        if self._sorted is None:
            self._sorted = sorted(self._samples)
        return self._sorted

    def quantile(self, q: float) -> float:
        with self._lock:
            s = self._sorted_view()
            if not s:
                return float("nan")
            return s[min(len(s) - 1, max(0, int(q * len(s))))]

    def quantiles(self, qs: Sequence[float]) -> List[float]:
        """All requested quantiles under ONE lock acquisition / sort."""
        with self._lock:
            s = self._sorted_view()
            if not s:
                return [float("nan")] * len(qs)
            return [s[min(len(s) - 1, max(0, int(q * len(s))))] for q in qs]

    @property
    def count(self) -> int:
        with self._lock:
            return self._n

    @property
    def mean(self) -> float:
        """Lifetime mean over ALL observations (not just the reservoir) —
        the exact-decomposition half of the stage-timing story: per-stage
        means telescope to the e2e mean, quantiles do not."""
        with self._lock:
            return self._sum / self._n if self._n else float("nan")

    def summary_ms(self) -> Dict[str, float]:
        p50, p95, p99 = self.quantiles((0.50, 0.95, 0.99))
        return {"p50_ms": p50 * 1e3, "p95_ms": p95 * 1e3, "p99_ms": p99 * 1e3}

    def snapshot(self) -> Dict[str, float]:
        """JSON-safe summary; quantile keys only when samples exist (no
        NaN leaks into exported JSON/Prometheus)."""
        with self._lock:
            n, total = self._n, self._sum
            s = self._sorted_view()
        out: Dict[str, float] = {"count": n}
        if not s:
            return out
        out["mean_ms"] = round((total / n) * 1e3, 6)
        for name, q in (("p50_ms", 0.50), ("p95_ms", 0.95), ("p99_ms", 0.99)):
            out[name] = round(s[min(len(s) - 1, max(0, int(q * len(s))))] * 1e3, 6)
        ex = self.exemplars()
        if ex:
            out["exemplars"] = ex
        return out


class StageTimes:
    """Named per-stage latency histograms (one :class:`LatencyStats` per
    stage, created on first observation).

    The pipeline threads monotonic hop timestamps through each record
    (:func:`psana_ray_tpu.records.mark_hop`); consecutive hop differences
    land here under the canonical stage names of
    :mod:`psana_ray_tpu.obs.stages` plus the ``e2e`` pseudo-stage, so the
    end-to-end latency decomposes exactly: the per-stage means sum to the
    e2e mean over the same records."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: Dict[str, LatencyStats] = {}

    def observe(self, stage: str, seconds: float, exemplar: Optional[int] = None):
        st = self._stats.get(stage)
        if st is None:
            with self._lock:
                st = self._stats.setdefault(stage, LatencyStats())
        st.observe(seconds, exemplar=exemplar)

    def stat(self, stage: str) -> Optional[LatencyStats]:
        with self._lock:
            return self._stats.get(stage)

    def stages(self) -> List[str]:
        with self._lock:
            return sorted(self._stats)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            items = list(self._stats.items())
        return {name: st.snapshot() for name, st in items}


class PipelineMetrics:
    """One bundle per producer/consumer process; renders a status line."""

    def __init__(self, queue=None):
        self.frames = Meter("frames")
        self.bytes = Meter("bytes")
        self.batches = Meter("batches")
        # batches appended before the NEXT batch's launch began, or the
        # stream's end was seen (SfxPipeline.run found the result ready
        # between two turns of the batcher): over ``batches`` near 1 when
        # the device outruns the frames, 0 at saturation
        self.drained_ahead = Meter("drained_ahead")
        # real frames whose bytes had been put on the device before their
        # batch's launch began (SfxPipeline.run stages frames that land
        # without filling the arena): over ``frames`` (B-1)/B where
        # frames trickle in, 0 where every pop fills a batch
        self.frames_staged_ahead = Meter("frames_staged_ahead")
        self.step_latency = LatencyStats()
        self.stages = StageTimes()
        # counters a step's own statistics feed, by name: a model whose
        # work per token depends on the data says how (expert load, live
        # attention tiles: ``models.decoder.fold_step_stats``)
        self.counters: Dict[str, float] = {}  # guarded-by: _counters_lock
        self._counters_lock = threading.Lock()
        self._queue = queue

    def attach_queue(self, queue):
        """Late-bind the transport queue whose depth the status line and
        snapshot report (the consumer CLI connects after metrics exist)."""
        self._queue = queue

    @property
    def has_queue(self) -> bool:
        return self._queue is not None

    def observe_frame(self, nbytes: int = 0):
        self.frames.add(1)
        if nbytes:
            self.bytes.add(nbytes)

    def observe_batch(self, n_frames: int, latency_s: float, nbytes: int = 0):
        self.batches.add(1)
        self.frames.add(n_frames)
        if nbytes:
            self.bytes.add(nbytes)
        self.step_latency.observe(latency_s)

    def add_counter(self, name: str, amount: float = 1.0):
        """Add to the named counter (created at 0 on first use); it shows
        in :meth:`snapshot` under its name."""
        with self._counters_lock:
            self.counters[name] = self.counters.get(name, 0.0) + amount

    def raise_counter(self, name: str, value: float):
        """Raise the named counter to ``value`` where that is larger (created
        at ``value`` on first use): a running maximum beside the sums."""
        with self._counters_lock:
            self.counters[name] = max(self.counters.get(name, value), value)

    def _queue_stats(self) -> Optional[dict]:
        q = self._queue
        if q is None:
            return None
        try:
            return probe_queue_stats(q)
        except Exception:
            return None

    def snapshot(self) -> dict:
        """JSON-safe nested dict — the per-process half of the cluster
        registry's :meth:`psana_ray_tpu.obs.MetricsRegistry.snapshot`."""
        out = {
            "frames_total": self.frames.count,
            "frames_per_second": round(self.frames.rate(), 3),
            "bytes_total": self.bytes.count,
            "bytes_per_second": round(self.bytes.rate(), 3),
            "batches_total": self.batches.count,
            "batches_per_second": round(self.batches.rate(), 3),
            "drained_ahead_total": self.drained_ahead.count,
            "frames_staged_ahead_total": self.frames_staged_ahead.count,
            "step_latency": self.step_latency.snapshot(),
        }
        with self._counters_lock:
            out.update(self.counters)
        stages = self.stages.snapshot()
        if stages:
            out["stages"] = stages
        qs = self._queue_stats()
        if qs is not None:
            out["queue"] = qs
        return out

    def status_line(self) -> str:
        lat = self.step_latency.summary_ms()
        depth = ""
        if self._queue is not None:
            try:
                depth = f" depth={self._queue.size()}"
            except Exception:
                depth = " depth=?"
        gbps = self.bytes.rate() * 8 / 1e9
        return (
            f"frames={self.frames.count} ({self.frames.rate():.1f}/s, {gbps:.2f} Gbit/s)"
            f" batches={self.batches.count}"
            f" p50={lat['p50_ms']:.2f}ms p99={lat['p99_ms']:.2f}ms{depth}"
        )
