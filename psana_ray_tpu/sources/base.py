"""DataSource protocol + detector geometry registry + shard assignment.

The reference delegates event sharding across MPI ranks to psana's
Smd (smalldata) reader — each rank's ``iter_events`` yields a disjoint shard
(``producer.py:150``, SURVEY.md §2 parallelism table). Here sharding is an
explicit, testable policy: strided assignment by (shard_rank, num_shards),
so rank r sees events r, r+N, r+2N, ... Deterministic and order-stable.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Protocol, Tuple, runtime_checkable

import numpy as np

from psana_ray_tpu.config import RetrievalMode


@dataclasses.dataclass(frozen=True)
class DetectorSpec:
    """Geometry + signal statistics of a detector family."""

    name: str
    panels: int
    height: int
    width: int
    # assembled-image shape for mode='image' (approximate mosaic)
    adu_offset: float = 100.0  # pedestal level in raw ADUs
    adu_gain: float = 35.0  # ADUs per photon
    bad_pixel_fraction: float = 0.003

    @property
    def frame_shape(self) -> Tuple[int, int, int]:
        return (self.panels, self.height, self.width)

    @property
    def pixels(self) -> int:
        return self.panels * self.height * self.width


# Real LCLS detector geometries (domain facts; epix10k2M geometry cited in
# SURVEY.md §3.3/§6: 16 panels of 352x384; Jungfrau4M: 8 panels of 512x1024).
DETECTORS = {
    "epix10k2M": DetectorSpec("epix10k2M", panels=16, height=352, width=384),
    "jungfrau4M": DetectorSpec("jungfrau4M", panels=8, height=512, width=1024),
    "cspad": DetectorSpec("cspad", panels=32, height=185, width=388),
    "epix100": DetectorSpec("epix100", panels=1, height=704, width=768),
    # tiny lane-aligned geometries for off-TPU smoke runs (tests, examples)
    "smoke_a": DetectorSpec("smoke_a", panels=2, height=16, width=128),
    "smoke_b": DetectorSpec("smoke_b", panels=1, height=32, width=128),
}


@runtime_checkable
class DataSource(Protocol):
    """The surface the producer consumes (reference ``producer.py:81,88,
    150-154``), plus indexed iteration so the producer can stamp global
    event ids without a parallel index stream (the reference counts a local
    ``idx`` per rank, ``producer.py:88,101``)."""

    def iter_events(self, mode: str = RetrievalMode.CALIB) -> Iterator[Tuple[np.ndarray, float]]:
        ...

    def iter_indexed_events(
        self, mode: str = RetrievalMode.CALIB
    ) -> Iterator[Tuple[int, np.ndarray, float]]:
        ...

    def create_bad_pixel_mask(self) -> np.ndarray:
        ...


def shard_indices(num_events: int, shard_rank: int, num_shards: int) -> np.ndarray:
    """Strided shard: rank r gets events r, r+N, ... Disjoint + exhaustive."""
    if not (0 <= shard_rank < num_shards):
        raise ValueError(f"shard_rank {shard_rank} not in [0, {num_shards})")
    return np.arange(shard_rank, num_events, num_shards)


def open_source(
    exp: str,
    run: int,
    detector_name: str,
    shard_rank: int = 0,
    num_shards: int = 1,
    **kwargs,
):
    """Dispatch to a backend by experiment name.

    - ``synthetic`` / ``synthetic-*`` -> :class:`SyntheticSource`
    - ``replay:<path>`` -> :class:`ReplaySource`
    - anything else: try a real psana wrapper (only on LCLS hosts), else
      raise with guidance.
    """
    from psana_ray_tpu.sources.synthetic import SyntheticSource
    from psana_ray_tpu.sources.replay import ReplaySource

    if exp.startswith("synthetic"):
        return SyntheticSource(
            exp, run, detector_name, shard_rank=shard_rank, num_shards=num_shards, **kwargs
        )
    if exp.startswith("replay:"):
        return ReplaySource(
            exp.split(":", 1)[1],
            detector_name=detector_name,
            shard_rank=shard_rank,
            num_shards=num_shards,
            **kwargs,
        )
    try:  # real LCLS host with psana installed
        from psana_ray_tpu.sources.psana_compat import PsanaSource  # noqa: PLC0415
    except ImportError as e:
        raise RuntimeError(
            f"experiment {exp!r} requires psana (LCLS host). For local runs use "
            f"exp='synthetic' or exp='replay:<path.npz>'."
        ) from e
    return PsanaSource(
        exp, run, detector_name, shard_rank=shard_rank, num_shards=num_shards, **kwargs
    )
