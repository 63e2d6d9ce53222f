"""Process-wide metrics registry: aggregate + render as Prometheus text.

One :class:`MetricsRegistry` per process collects every metrics-bearing
object (``PipelineMetrics`` bundles, ``Meter``/``LatencyStats``
singletons, queue ``stats()`` callables, stall detectors) under a source
name; :meth:`snapshot` returns the whole tree as a JSON-safe dict (tests)
and :meth:`render_prometheus` flattens the same tree
into Prometheus exposition text-format 0.0.4 for the HTTP exporter
(:mod:`psana_ray_tpu.obs.exporter`).

Naming: nested dict paths join with ``_`` under the ``psana_ray`` prefix
and the top-level source name becomes the ``source`` label, e.g.::

    psana_ray_frames_total{source="producer"} 4096
    psana_ray_stages_queue_dwell_p99_ms{source="infeed.epix"} 1.84

Names ending in ``_total`` are typed ``counter``; everything else is a
``gauge``. Pure stdlib, no prometheus_client dependency.
"""

from __future__ import annotations

import math
import os
import re
import socket
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

from psana_ray_tpu.utils.metrics import LatencyStats, Meter, PipelineMetrics, StageTimes

_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")

Source = Union[PipelineMetrics, Meter, LatencyStats, StageTimes, dict, Callable[[], dict]]


def _sanitize(name: str) -> str:
    name = _NAME_RE.sub("_", name)
    if name and name[0].isdigit():
        name = "_" + name
    return name


def _escape_label(value: str) -> str:
    return value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def flatten_numeric(
    path: Tuple[str, ...], value: Any, out: List[Tuple[str, float]]
) -> None:
    """Flatten a snapshot tree's numeric leaves into ``(dotted.path,
    float)`` pairs — ONE flattening grammar shared by the Prometheus
    renderer and the time-series history ring
    (:mod:`psana_ray_tpu.obs.timeseries`), so the history key for a
    metric is its /metrics name with ``.`` for the sanitized ``_``
    joins. Bools become 0/1; non-finite and non-numeric leaves are
    skipped. The ``exemplars`` subtree of a latency snapshot is skipped
    WHOLE: an exemplar is a retained (trace id, value) LINK for the
    drill-down tooling, not a series — flattening its numeric half
    would mint a bogus mostly-static gauge per bucket on /metrics and
    a history ring per bucket in every sampling process."""
    if isinstance(value, dict):
        for k, v in value.items():
            if k == "exemplars":
                continue
            flatten_numeric(path + (str(k),), v, out)
        return
    if isinstance(value, bool):
        out.append((".".join(path), 1.0 if value else 0.0))
        return
    if isinstance(value, (int, float)):
        v = float(value)
        if math.isfinite(v):
            out.append((".".join(path), v))


def snapshot_source(src: Source) -> dict:
    """One source -> JSON-safe dict. Objects with ``snapshot()`` win
    (PipelineMetrics, Meter, LatencyStats, StageTimes, StallDetector);
    bare dicts pass through; callables (queue ``stats`` methods, lambdas)
    are invoked; anything with ``stats()`` (transport queues) is asked."""
    snap = getattr(src, "snapshot", None)
    if callable(snap):
        return snap() or {}
    if isinstance(src, dict):
        return dict(src)
    if callable(src):
        return src() or {}
    stats = getattr(src, "stats", None)
    if callable(stats):
        return stats() or {}
    raise TypeError(f"not a metrics source: {type(src)!r}")


class MetricsRegistry:
    """Named metrics sources + the two export surfaces.

    Distinct from the transport-rendezvous
    :class:`psana_ray_tpu.transport.registry.Registry` — this one holds
    observability objects, not queues. ``default()`` is the process-global
    instance every CLI registers into; tests build their own."""

    _global: Optional["MetricsRegistry"] = None
    _global_lock = threading.Lock()

    def __init__(self, prefix: str = "psana_ray"):
        self.prefix = _sanitize(prefix)
        self._lock = threading.Lock()
        self._sources: Dict[str, Source] = {}

    @classmethod
    def default(cls) -> "MetricsRegistry":
        with cls._global_lock:
            if cls._global is None:
                cls._global = MetricsRegistry()
            return cls._global

    @classmethod
    def reset_default(cls):
        with cls._global_lock:
            cls._global = None

    def register(self, name: str, source: Source) -> Source:
        """Add (or replace — last registration wins, so restarted
        pipelines under a stable name just take over the series) a source."""
        with self._lock:
            self._sources[name] = source
        return source

    def unregister(self, name: str):
        with self._lock:
            self._sources.pop(name, None)

    def sources(self) -> List[str]:
        with self._lock:
            return sorted(self._sources)

    def snapshot(self) -> Dict[str, dict]:
        """The whole tree as a JSON-safe dict: ``{source_name: {...}}``.
        A source that raises contributes an ``error`` entry instead of
        poisoning the scrape (one dead queue must not blind the cluster)."""
        with self._lock:
            items = list(self._sources.items())
        out: Dict[str, dict] = {}
        for name, src in items:
            try:
                out[name] = snapshot_source(src)
            except Exception as e:  # noqa: BLE001 — scrape must survive
                out[name] = {"error": repr(e)}
        return out

    # -- Prometheus text format ------------------------------------------
    def render_prometheus(self) -> str:
        """Exposition text-format 0.0.4: numeric leaves of the snapshot
        tree, grouped per metric family with HELP/TYPE headers, the source
        name as a label. Non-finite values and non-numeric leaves are
        skipped (a scrape is never malformed)."""
        families: Dict[str, List[Tuple[str, float]]] = {}
        for source, tree in self.snapshot().items():
            leaves: List[Tuple[str, float]] = []
            flatten_numeric((), tree, leaves)
            for path, value in leaves:
                metric = f"{self.prefix}_{_sanitize(path)}"
                families.setdefault(metric, []).append((source, value))
        lines: List[str] = []
        for metric in sorted(families):
            mtype = "counter" if metric.endswith("_total") else "gauge"
            lines.append(f"# HELP {metric} psana-ray-tpu pipeline metric")
            lines.append(f"# TYPE {metric} {mtype}")
            for source, value in sorted(families[metric]):
                label = _escape_label(source)
                lines.append(f'{metric}{{source="{label}"}} {_format_value(value)}')
        return "\n".join(lines) + "\n" if lines else ""


def _format_value(v: float) -> str:
    if v == int(v) and abs(v) < 2**53:
        return str(int(v))
    return repr(v)


def federation_payload(registry: Optional[MetricsRegistry] = None) -> dict:
    """One host-tagged registry snapshot — the federation unit of ISSUE
    13, served identically by the queue server's 'N' ``{"op":
    "metrics"}`` RPC and the HTTP exporter's ``/federate`` route, so the
    collector merges queue servers and producer/consumer CLIs into the
    same host-tagged series store."""
    reg = registry if registry is not None else MetricsRegistry.default()
    payload = {
        "ok": True,
        "host": socket.gethostname(),
        "pid": os.getpid(),
        "wall": time.time(),
        "mono": time.monotonic(),
        "metrics": reg.snapshot(),
    }
    # multi-worker data plane (ISSUE 17): a forked queue-server worker
    # tags its payload so the collector/console can label per-worker
    # rows (a pulled TCP connection pins to ONE worker for its life,
    # so each peer's series is per-worker consistent)
    try:
        from psana_ray_tpu.transport.workers import current_worker_id

        wid = current_worker_id()
        if wid is not None:
            payload["worker"] = wid
    except Exception:
        pass
    # continuous-profiler summary (ISSUE 16) rides OUTSIDE "metrics":
    # hot-frame NAMES are strings and flatten_numeric would drop them.
    # Absent/broken profiler must cost nothing — peers render "-".
    try:
        from psana_ray_tpu.obs.profiling import profile_summary

        payload["profile"] = profile_summary()
    except Exception:
        payload["profile"] = None
    return payload
