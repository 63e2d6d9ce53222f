"""SLO-aware serving gateway (ISSUE 12): the layer between the queue
transport and the device consumers.

Three cooperating mechanisms, all driven by MEASUREMENT (the tf.data
"measure-then-control" philosophy, PAPERS.md):

- :class:`SloPolicy` — the latency/throughput frontier (device time
  per batch size, refined online from every dispatch) as a control law: pick the batch size per dispatch from the
  current backlog so an idle system serves B1 latency and a loaded one
  serves B8 throughput, always keeping predicted queue-wait + device
  time inside the p99 SLO budget;
- :class:`ServingGateway` — admission control with deadline shedding
  (shed at the front door BEFORE spending batcher/device time, re-check
  at dequeue — an aged-out frame is dropped loudly, never processed
  late) plus weighted deficit round-robin dispatch across per-tenant
  queues;
- :class:`GatewayTelemetry` — the obs source (``gateway``): per-tenant
  admitted/shed/goodput/p99 and SLO attainment, the degraded gauge the
  StallDetector escalation flips.
"""

from psana_ray_tpu.serving.gateway import ServingGateway, make_batch_dispatch
from psana_ray_tpu.serving.policy import DEFAULT_OPERATING_POINTS, SloPolicy
from psana_ray_tpu.serving.telemetry import (
    GatewayTelemetry,
    PATH_ADMISSION,
    PATH_DEADLINE,
    PATH_STALL,
    SHED_PATHS,
)

__all__ = [
    "DEFAULT_OPERATING_POINTS",
    "GatewayTelemetry",
    "PATH_ADMISSION",
    "PATH_DEADLINE",
    "PATH_STALL",
    "SHED_PATHS",
    "ServingGateway",
    "SloPolicy",
    "make_batch_dispatch",
]
