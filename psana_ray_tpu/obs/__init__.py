"""Cluster-wide observability: metrics export, stage timing, stall detection.

The three legs (ISSUE 1 / SURVEY.md §5 — the reference has no
observability story at all):

- **Export** — :class:`MetricsRegistry` aggregates every process-local
  metrics object and serves Prometheus text format over a stdlib HTTP
  thread (:class:`MetricsServer`, ``--metrics_port`` on every CLI);
- **Stage timing** — :mod:`psana_ray_tpu.obs.stages` names the pipeline
  boundaries; monotonic hop stamps threaded through the record envelope
  decompose end-to-end latency into per-stage histograms;
- **Health** — :class:`StallDetector` turns queue counters into
  structured backpressure / stall / liveness warnings, and the queue
  server answers a stats RPC (``transport.tcp`` opcode ``T``).

Plus the per-frame layer (ISSUE 4):

- **Tracing** — :mod:`psana_ray_tpu.obs.tracing`: sampled per-frame
  distributed traces across producer/queue-server/consumer, merged into
  a Perfetto-loadable timeline by ``python -m psana_ray_tpu.obs.
  trace_merge``;
- **Flight recorder** — :mod:`psana_ray_tpu.obs.flight`: bounded event
  ring + dump-on-stall/exception/SIGUSR2 postmortem black box.

And the telemetry plane (ISSUE 13):

- **History** — :mod:`psana_ray_tpu.obs.timeseries`: a bounded,
  zero-alloc-on-sample ring of periodic registry snapshots per process
  (rates/percentiles computed at read time; flight dumps append the
  tail);
- **Federation** — :mod:`psana_ray_tpu.obs.collector`: one collector
  pulls every queue server ('N' metrics RPC) and CLI (``/federate``)
  into a host-tagged series store, with SLO burn-rate alerts;
- **Console** — ``python -m psana_ray_tpu.obs.top``: the live fleet
  pane over the federated history (``--once`` for scripts/tests);
- **Exemplars** — latency histograms retain a sampled trace id per
  bucket; ``trace_merge --exemplar <id>`` resolves a bad bucket to the
  frame's merged cross-host timeline.

And the continuous profiling plane (ISSUE 16):

- **Flame sampling** — :mod:`psana_ray_tpu.obs.profiling`: an always-on
  97 Hz stack sampler folding every thread into a bounded zero-alloc
  trie, with on-CPU/waiting discrimination and per-stage attribution
  via the obs/stages vocabulary;
- **Cost model** — the ``prof`` registry source: per-process cpu_frac,
  per-stage cpu_ms, and cpu_ns_per_frame / py_bytes_per_frame against
  the wire counters;
- **Merge** — ``python -m psana_ray_tpu.obs.prof_merge``: cluster-wide
  flamegraphs (collapsed/speedscope) and cpu_frac counter tracks
  overlaid on the trace_merge Perfetto timeline.

Everything here is pure stdlib and importable without JAX.
"""

from psana_ray_tpu.obs.exporter import (  # noqa: F401
    MetricsServer,
    add_metrics_args,
    start_metrics_server,
)
from psana_ray_tpu.obs.registry import MetricsRegistry, snapshot_source  # noqa: F401
from psana_ray_tpu.obs.stages import (  # noqa: F401
    STAGE_BATCH,
    STAGE_DEQUEUE,
    STAGE_DEVICE_PUT,
    STAGE_DISPATCH,
    STAGE_E2E,
    STAGE_ENQUEUE,
    STAGE_QUEUE_DWELL,
    STAGES,
    StageTimes,
    observe_record_stages,
)
from psana_ray_tpu.obs.stall import (  # noqa: F401
    EVENT_BACKPRESSURE,
    EVENT_CONSUMER_STALL,
    EVENT_PRODUCER_IDLE,
    StallDetector,
    StallEvent,
)
from psana_ray_tpu.obs.flight import FLIGHT, FlightRecorder  # noqa: F401
from psana_ray_tpu.obs.timeseries import (  # noqa: F401
    HistorySampler,
    SeriesRing,
    TimeSeriesStore,
    add_history_args,
    configure_history_from_args,
    default_history,
)
from psana_ray_tpu.obs.collector import ClusterCollector  # noqa: F401
from psana_ray_tpu.obs.profiling import (  # noqa: F401
    FlameSampler,
    ProfTelemetry,
    StackTrie,
    add_profile_args,
    configure_profiling_from_args,
    default_profiler,
    profile_summary,
    profile_top,
    start_default_profiler,
    stop_default_profiler,
)
from psana_ray_tpu.obs.tracing import (  # noqa: F401
    TRACER,
    TraceContext,
    Tracer,
    add_trace_args,
    configure_from_args as configure_tracing_from_args,
    exchange_anchors,
    obs_status_suffix,
)
