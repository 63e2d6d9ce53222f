"""Per-frame cost model + CPU saturation source (the ``prof`` source).

ROADMAP item 2 ("break the single-core Python ceiling") will be judged
by a number nothing measured before this PR: how much host CPU and how
many Python-touched bytes each frame costs. This module derives both by
differencing two counters the repo already pays for — process CPU time
(``os.times``) and the wire copy counters (``utils.bufpool.WIRE``) —
about once a second on the sampler's housekeeping tick:

- ``cpu_frac``     — process CPU seconds per wall second (saturation
  signal for ROADMAP item 4's elasticity controller; also appended to
  a local SeriesRing so spools carry the full utilisation timeline);
- ``cpu_ns_per_frame``   — CPU nanoseconds burned per wire frame;
- ``py_bytes_per_frame`` — bytes memcpy'd through Python per frame
  (the "per-frame Python bytes touched ~0" acceptance number).

Registered as the ``prof`` source on the MetricsRegistry, every value
here is numeric, so it flows unmodified through ``flatten_numeric``
into Prometheus, the PR 13 history rings and federation metrics. The
non-numeric profile summary (hot frame NAMES)
deliberately lives outside this source — see
``registry.federation_payload``'s ``profile`` key — because the metric
grammar drops strings.

Deltas are computed against injected ``frames_fn`` / ``bytes_fn`` when
the caller has a better frame counter than the wire totals (a caller
that counts its own frames scores exactly the window it measured).
"""

from __future__ import annotations

import os
import threading
import time

from psana_ray_tpu.obs.timeseries import SeriesRing

__all__ = ["ProfTelemetry", "CPU_SERIES_CAPACITY"]

CPU_SERIES_CAPACITY = 600  # ~10 min of 1 Hz ticks, same budget as history rings


class ProfTelemetry:
    """Cost-model state; obs source protocol via :meth:`snapshot`.

    Written from the sampler thread's ~1 Hz housekeeping tick
    (:meth:`tick_cost_model`), read from scrape/federation threads —
    all mutable state is guarded by ``_lock``.
    """

    def __init__(self, sampler=None, frames_fn=None, bytes_fn=None):
        self._sampler = sampler
        self._frames_fn = frames_fn
        self._bytes_fn = bytes_fn
        self._lock = threading.Lock()
        self.cpu_frac = 0.0  # guarded-by: _lock
        self.cpu_ns_per_frame = 0.0  # guarded-by: _lock
        self.py_bytes_per_frame = 0.0  # guarded-by: _lock
        self.frames_seen = 0  # guarded-by: _lock
        self.ticks_total = 0  # guarded-by: _lock
        self._last_mono = 0.0  # guarded-by: _lock
        self._last_cpu = 0.0  # guarded-by: _lock
        self._last_frames = 0  # guarded-by: _lock
        self._last_bytes = 0  # guarded-by: _lock
        self.cpu_series = SeriesRing(CPU_SERIES_CAPACITY)  # guarded-by: _lock

    def _frame_counters(self):
        """(frames_total, bytes_total) from the injected counters or the
        process-wide wire counters."""
        if self._frames_fn is not None:
            frames = int(self._frames_fn())
            nbytes = int(self._bytes_fn()) if self._bytes_fn is not None else 0
            return frames, nbytes
        try:
            from psana_ray_tpu.utils.bufpool import WIRE

            s = WIRE.stats()
            return int(s["copies_total"]), int(s["bytes_copied_total"])
        except Exception:
            return 0, 0

    def tick_cost_model(self, now=None) -> None:
        """One cost-model step: difference CPU/frames/bytes since the
        previous tick. Called ~1 Hz off the sampler's housekeeping (or
        directly by tests); cold path, allocation is fine here."""
        if now is None:
            now = time.monotonic()
        t = os.times()
        cpu = t.user + t.system
        frames, nbytes = self._frame_counters()
        with self._lock:
            dt = now - self._last_mono
            if self._last_mono > 0.0 and dt > 0.0:
                d_cpu = max(0.0, cpu - self._last_cpu)
                self.cpu_frac = d_cpu / dt
                d_frames = frames - self._last_frames
                if d_frames > 0:
                    self.cpu_ns_per_frame = d_cpu * 1e9 / d_frames
                    self.py_bytes_per_frame = (nbytes - self._last_bytes) / float(d_frames)
            self._last_mono = now
            self._last_cpu = cpu
            self._last_frames = frames
            self._last_bytes = nbytes
            self.frames_seen = frames
            self.ticks_total += 1
            self.cpu_series.append(now, self.cpu_frac)

    def cpu_timeline(self):
        """``[(mono, cpu_frac), ...]`` ticks for spool export."""
        with self._lock:
            return self.cpu_series.samples()

    # ---- obs registry source protocol ----

    def snapshot(self) -> dict:
        s = self._sampler
        with self._lock:
            out = {
                "enabled": 1 if (s is not None and s.running) else 0,
                "cpu_frac": self.cpu_frac,
                "cpu_ns_per_frame": self.cpu_ns_per_frame,
                "py_bytes_per_frame": self.py_bytes_per_frame,
                "frames_seen": self.frames_seen,
                "ticks_total": self.ticks_total,
            }
        if s is not None:
            trie = s.trie
            out["hz"] = s.hz
            out["samples_total"] = trie.samples_total
            out["on_cpu_total"] = trie.on_cpu_total
            out["waiting_total"] = trie.waiting_total
            out["nodes"] = trie.n_nodes
            out["overflow_total"] = trie.overflow_total
            out["stage_cpu_ms"] = s.stage_cpu_ms()
        return out

    def stats(self) -> dict:
        return self.snapshot()
