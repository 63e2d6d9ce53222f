"""Thread-local pipeline-stage tags for the continuous profiler.

The flame sampler (:mod:`psana_ray_tpu.obs.profiling.sampler`) bills
every stack sample to the CANONICAL stage vocabulary the latency
histograms already speak (:data:`psana_ray_tpu.obs.stages.STAGES`):
each worker thread publishes "which stage am I executing right now" as
one small-int tag in a plain dict keyed by thread ident, written at the
EXISTING instrumentation points (the producer put path, the consumer
drain loop, ``utils.trace.phase`` regions, the event-loop dispatch
pass). The sampler reads the dict from its own thread — a
``threading.local`` would hide the value from the reader, so the tag
table is deliberately a shared dict: CPython dict stores are atomic
under the GIL, and overwriting an existing key allocates nothing.

Tags are SMALL INTS (0..N_TAGS-1, all in CPython's small-int cache) so
setting one on the per-record hot path is a single dict store with zero
allocation. Tag 0 is "untagged": threads that never declared a stage
(interpreter main thread, import machinery, third-party pools) bill
there, and the ISSUE 16 attribution acceptance measures how little of
the busy pipeline that is.

This module imports NOTHING project-side (only ``threading``) so the
transport and infeed layers can tag unconditionally without import
cycles; a test pins ``TAG_NAMES[1:]`` to ``obs.stages.STAGES`` so the
vocabularies cannot drift apart.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

__all__ = [
    "TAG_UNTAGGED",
    "TAG_ENQUEUE",
    "TAG_QUEUE_DWELL",
    "TAG_DEQUEUE",
    "TAG_BATCH",
    "TAG_DEVICE_PUT",
    "TAG_DISPATCH",
    "TAG_NAMES",
    "TAG_OF_STAGE",
    "N_TAGS",
    "set_stage",
    "swap_stage",
    "current_tag",
    "clear_thread",
    "stage_region",
]

# Tag ids: 0 = no declared stage; 1.. mirror obs.stages.STAGES order,
# then the loop phases that are not also stage names, in obs.stages.PHASES
# order (pinned by tests/test_profiling.py so the vocabularies cannot drift).
TAG_UNTAGGED = 0
TAG_ENQUEUE = 1
TAG_QUEUE_DWELL = 2
TAG_DEQUEUE = 3
TAG_BATCH = 4
TAG_DEVICE_PUT = 5
TAG_DISPATCH = 6

# The names from "queue_wait" on are loop phases beyond the hop-stage
# names (obs.stages.PHASES; the phase ``device_put`` shares the stage's
# tag): looked up by name, TAG_OF_STAGE.
TAG_NAMES = (
    "untagged",
    "enqueue",
    "queue_dwell",
    "dequeue",
    "batch",
    "device_put",
    "dispatch",
    "queue_wait",
    "decode",
    "copy",
    "prefetch_full",
    "h2d_tail",
    "infeed_wait",
    "launch",
    "device_wait",
    "fold",
    "append",
    "gc",
    "put_ahead",
)
N_TAGS = len(TAG_NAMES)

#: stage name -> tag id (the ``utils.trace.phase`` bridge; unknown names
#: map to untagged rather than raising — a new stage name must never
#: break the data path it instruments).
TAG_OF_STAGE = {name: i for i, name in enumerate(TAG_NAMES)}

# thread ident -> tag id. Written by the tagged thread, read by the
# sampler thread; single dict store / lookup per operation, GIL-atomic.
_TAGS: Dict[int, int] = {}


def set_stage(tag: int) -> None:
    """Declare the calling thread's current stage (hot path: one dict
    store of a cached small int, no allocation on an existing key)."""
    _TAGS[threading.get_ident()] = tag


def swap_stage(tag: int) -> int:
    """Set the calling thread's tag and return the PREVIOUS one (0 when
    none) — the save/restore half used by scoped instrumentation so
    nested stages unwind correctly."""
    ident = threading.get_ident()
    prev = _TAGS.get(ident, TAG_UNTAGGED)
    _TAGS[ident] = tag
    return prev


def current_tag(ident: Optional[int] = None) -> int:
    """The tag a thread last declared (its own by default)."""
    if ident is None:
        ident = threading.get_ident()
    return _TAGS.get(ident, TAG_UNTAGGED)


def clear_thread(ident: Optional[int] = None) -> None:
    """Drop a thread's entry (sampler GC for dead threads; tests)."""
    _TAGS.pop(threading.get_ident() if ident is None else ident, None)


class stage_region:
    """Context manager: tag the calling thread with a stage FOR THE
    SCOPE, optionally wrapping an inner context manager (a device
    profiler ``TraceAnnotation``) so one ``with`` statement feeds both
    the device timeline and the continuous profiler (the serving loops'
    ``utils.trace.phase`` does the same and records the duration too). Restores the previous tag on exit — nested
    regions (dispatch > device_put) unwind to the enclosing stage."""

    __slots__ = ("_tag", "_inner", "_prev")

    def __init__(self, stage: str, inner=None):
        self._tag = TAG_OF_STAGE.get(stage, TAG_UNTAGGED)
        self._inner = inner
        self._prev = TAG_UNTAGGED

    def __enter__(self):
        self._prev = swap_stage(self._tag)
        if self._inner is not None:
            self._inner.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        try:
            if self._inner is not None:
                return self._inner.__exit__(exc_type, exc, tb)
            return False
        finally:
            set_stage(self._prev)
