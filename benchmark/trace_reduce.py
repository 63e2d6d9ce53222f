"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

What a v5e trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per run of
a compiled program, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (one event
per HLO operation, named by its full HLO text, ``%fusion.12 = ...``; a
Pallas kernel is ``%<kernel name>[.n] = ... custom_call_target=
"tpu_custom_call"``) and ``Async XLA Ops`` (copies that overlap compute);
and one host plane, ``/host:CPU``, with a line per thread whose events
include every ``jax.profiler.TraceAnnotation``. Every event has a start
and a duration in nanoseconds on one clock for all planes.

The benchmark keeps a trace as plain lists (:class:`Trace`), which load
from the profiler's ``.xplane.pb`` and from a small JSON recording alike:
``check_reduce.py`` holds the reduction to a recorded trace.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
LINE_OPS = "XLA Ops"
LINE_MODULES = "XLA Modules"
UNATTRIBUTED = "no host annotation (waiting for a frame, or host code without a stage.* span)"


@dataclasses.dataclass
class Trace:
    """``device[chip][line]`` and ``host[thread line]`` -> events sorted
    by start."""

    device: Dict[int, Dict[str, List[Event]]]
    host: Dict[str, List[Event]]

    def to_json(self) -> dict:
        return {
            "device": {str(c): lines for c, lines in self.device.items()},
            "host": self.host,
        }

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        return Trace(
            device={
                int(c): {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                for c, lines in obj["device"].items()
            },
            host={ln: [tuple(e) for e in evs] for ln, evs in obj["host"].items()},
        )


def load_recording(path: str) -> Trace:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return Trace.from_json(json.load(f))


def load_xplane(path: str) -> Trace:
    """Read a profiler ``.xplane.pb`` with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[int, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if m is not None and line.name not in (LINE_OPS, LINE_MODULES):
                continue
            events = sorted(
                ((e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events),
                key=lambda e: e[1],
            )
            if m is not None:
                device.setdefault(int(m.group(1)), {})[line.name] = events
            elif events:
                # several threads can share a name ("python"): keep them all
                key, n = line.name, 1
                while key in host:
                    n += 1
                    key = f"{line.name}#{n}"
                host[key] = events
    return Trace(device=device, host=host)


# ---------------------------------------------------------------------------
# windows and intervals
# ---------------------------------------------------------------------------

def annotation_events(trace: Trace, name: str) -> List[Event]:
    """Every host event called ``name``, from all threads, by start."""
    out = [e for evs in trace.host.values() for e in evs if e[0] == name]
    return sorted(out, key=lambda e: e[1])


def window_of(trace: Trace, annotation: str) -> Tuple[float, float]:
    """The traced window: the span of the host annotation the harness
    held open while it measured."""
    evs = annotation_events(trace, annotation)
    if not evs:
        raise ValueError(f"the trace holds no {annotation!r} annotation")
    _, start, dur = max(evs, key=lambda e: e[2])
    return start, start + dur


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    """Intervals of ``events`` cut to ``[t0, t1]``."""
    out = []
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def device_ops(trace: Trace, chip: int) -> List[Event]:
    lines = trace.device[chip]
    return lines.get(LINE_OPS) or lines.get(LINE_MODULES) or []


def busy_seconds(trace: Trace, t0: float, t1: float) -> float:
    """Seconds of ``[t0, t1]`` in which an operation ran on the device:
    the union of the op intervals, averaged over the chips in the trace."""
    if not trace.device:
        return 0.0
    per_chip = [
        sum(b - a for a, b in union(clip(device_ops(trace, c), t0, t1)))
        for c in sorted(trace.device)
    ]
    return sum(per_chip) / len(per_chip) / 1e9


# ---------------------------------------------------------------------------
# kernels and programs by name
# ---------------------------------------------------------------------------

def op_label(hlo_text: str) -> str:
    """``%fusion.3 = bf16[8,16]{...} fusion(...)`` -> ``%fusion.3 bf16[8,16]
    fusion``: enough to find the operation again, short enough to print."""
    m = re.match(r"^(%?[\w.\-]+) = \(?([a-z0-9]+\[[^\]]*\])?", hlo_text)
    if m is None:
        return hlo_text[:96]
    op = re.search(r"[}\)] ([a-z][\w\-]*)\(", hlo_text)
    target = re.search(r'custom_call_target="([^"]+)"', hlo_text)
    parts = [m.group(1), m.group(2) or "", (target.group(1) if target else (op.group(1) if op else ""))]
    return " ".join(p for p in parts if p)[:96]


def named_events(
    trace: Trace, pattern: str, line: str, t0: float, t1: float
) -> Dict[int, List[Event]]:
    """Per chip, the events of ``line`` whose name matches ``pattern`` at
    its start and which lie wholly inside ``[t0, t1]``."""
    rx = re.compile(pattern)
    out = {}
    for chip, lines in trace.device.items():
        out[chip] = [
            e for e in lines.get(line, [])
            if e[1] >= t0 and e[1] + e[2] <= t1 and rx.match(e[0])
        ]
    return out


def top_device_ops(trace: Trace, t0: float, t1: float, k: int = 10) -> List[List]:
    """The operations that took most device time in the window, summed by
    label over all their runs and averaged over chips: ``[label, s]``."""
    total: Dict[str, float] = {}
    for chip in trace.device:
        for name, s, d in device_ops(trace, chip):
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                label = op_label(name)
                total[label] = total.get(label, 0.0) + (b - a)
    n = max(1, len(trace.device))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[label, ns / n / 1e9] for label, ns in ranked]


# ---------------------------------------------------------------------------
# idle gaps, billed to what the host was doing
# ---------------------------------------------------------------------------

def idle_gaps(trace: Trace, chip: int, t0: float, t1: float) -> List[Tuple[float, float]]:
    busy = union(clip(device_ops(trace, chip), t0, t1))
    gaps, cursor = [], t0
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if t1 > cursor:
        gaps.append((cursor, t1))
    return gaps


def host_spans(trace: Trace, prefixes: Sequence[str]) -> List[Event]:
    return sorted(
        (e for evs in trace.host.values() for e in evs if e[0].startswith(tuple(prefixes))),
        key=lambda e: e[1],
    )


def bill_idle_gaps(
    trace: Trace, t0: float, t1: float, prefixes: Sequence[str] = ("stage.", "bench.sink"),
    k: int = 10,
) -> List[List]:
    """Idle seconds of the device (chip 0 of the trace; on a mesh every
    chip runs the same program in step) by the host annotation that
    covered them: each gap's time goes to the annotations overlapping it,
    for as long as each overlaps (the first to start wins where several
    do), and what none covers to ``UNATTRIBUTED``. ``[label, s]`` by
    seconds."""
    if not trace.device:
        return []
    chip = min(trace.device)
    spans = host_spans(trace, prefixes)
    billed: Dict[str, float] = {}
    for g0, g1 in idle_gaps(trace, chip, t0, t1):
        cursor = g0
        for name, s, d in spans:
            if s >= g1:
                break
            a, b = max(s, cursor), min(s + d, g1)
            if b > a:
                if a > cursor:
                    billed[UNATTRIBUTED] = billed.get(UNATTRIBUTED, 0.0) + (a - cursor)
                billed[name] = billed.get(name, 0.0) + (b - a)
                cursor = b
        if g1 > cursor:
            billed[UNATTRIBUTED] = billed.get(UNATTRIBUTED, 0.0) + (g1 - cursor)
    ranked = sorted(billed.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if len(values) else None
