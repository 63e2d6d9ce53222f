"""Device time per run of a compiled program, in ms, of the operations
under one ``jax.named_scope``: ``find_peaks_ms`` is what the ops under
``find_peaks`` take of each SFX step, ``peaknet_ms`` the U-Net's.

Where an operation's scope comes from (looked at by hand on a v5e trace,
PR 24): not from the ``XLA Ops`` events — their names are the HLO text
without its metadata, and their stats hold times only — but from the
profile all the same. Its ``/host:metadata`` plane carries the HLO proto
of every program that ran, and there each instruction has its
``metadata.op_name``: the name stack JAX gave it,
``jit(_device_step)/find_peaks/jit(...)/top_k``. An event's name starts
with its instruction's (``%custom-call = ...``), which ties the two. No
table of fusion numbers is kept anywhere: a refactor that renumbers the
fusions renumbers both sides.

The profile is the ``.xplane.pb`` under ``<work>/trace``, beside the
span spool's ``<work>/spans``. It is read here with a few lines of
protobuf wire format (``ProfileData`` shows no plane's metadata), which
``check_scope.py`` holds to a recorded profile. A profile whose programs
name no such scope — the parent of the PR that added the scopes — gives
nothing to read, and the metric is left out.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Dict, Iterator, Optional, Tuple

from benchmark import trace_reduce
from benchmark.readers.trace_event_time import resolve

METADATA_PLANE = "/host:metadata"
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


# ---------------------------------------------------------------------------
# protobuf wire format, as much of it as two messages need
# ---------------------------------------------------------------------------

def fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an int for a
    varint, the bytes of a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not a protobuf message")
        yield number, wire, value


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _sub(buf: bytes, number: int) -> Iterator[bytes]:
    return (v for n, w, v in fields(buf) if n == number and w == 2)


def hlo_protos(xspace: bytes) -> Iterator[bytes]:
    """The serialized ``HloProto`` of every program in the profile:
    ``XSpace.planes[name == /host:metadata].event_metadata[*].stats[*]
    .bytes_value`` (tsl ``xplane.proto``: planes 1; XPlane name 2,
    event_metadata 4, a map entry's value 2; XEventMetadata stats 5;
    XStat bytes_value 6)."""
    for plane in _sub(xspace, 1):
        if next(_sub(plane, 2), b"").decode("utf-8", "replace") != METADATA_PLANE:
            continue
        for entry in _sub(plane, 4):
            for event_metadata in _sub(entry, 2):
                for stat in _sub(event_metadata, 5):
                    yield from _sub(stat, 6)


def instruction_scopes(hlo_proto: bytes) -> Dict[str, str]:
    """``{instruction name: metadata.op_name}`` over every computation of
    a program (xla ``hlo.proto``: HloProto hlo_module 1; HloModuleProto
    computations 3; HloComputationProto instructions 2;
    HloInstructionProto name 1, metadata 7; OpMetadata op_name 2)."""
    out = {}
    for module in _sub(hlo_proto, 1):
        for computation in _sub(module, 3):
            for instruction in _sub(computation, 2):
                name = next(_sub(instruction, 1), b"").decode("utf-8", "replace")
                op_name = b""
                for metadata in _sub(instruction, 7):
                    op_name = next(_sub(metadata, 2), b"")
                out[name] = op_name.decode("utf-8", "replace")
    return out


def load_scopes(path: str) -> Dict[str, str]:
    """Instruction -> op_name over all programs of a profile file
    (``.xplane.pb``, or the same gzipped: the recording)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        xspace = f.read()
    scopes: Dict[str, str] = {}
    for proto in hlo_protos(xspace):
        scopes.update(instruction_scopes(proto))
    return scopes


# ---------------------------------------------------------------------------
# the metric
# ---------------------------------------------------------------------------

def in_scope(op_name: str, scope: str) -> bool:
    """Is ``scope`` one component of the name stack (``a/scope/b``)?"""
    return f"/{scope}/" in f"/{op_name}/"


def scope_ms(trace, scopes: Dict[str, str], scope: str, within: str,
             t0: float, t1: float) -> Optional[float]:
    """Per run of the program matching ``within`` that lies wholly inside
    ``[t0, t1]``: the summed duration of the ``XLA Ops`` events that start
    inside the run and whose instruction is under ``scope``; the median
    over runs (all chips' runs on a mesh), in ms. None when no operation
    of the profile is under the scope, or no run lies in the window."""
    wanted = {name for name, op_name in scopes.items() if in_scope(op_name, scope)}
    if not wanted:
        return None
    runs = trace_reduce.named_events(trace, within, trace_reduce.LINE_MODULES, t0, t1)
    totals = []
    for chip, events in runs.items():
        scoped = [
            (s, d) for name, s, d in trace.device[chip].get(trace_reduce.LINE_OPS, [])
            if (m := INSTRUCTION.match(name)) is not None and m.group(1) in wanted
        ]
        for _, start, duration in events:
            totals.append(sum(d for s, d in scoped if start <= s < start + duration))
    med = trace_reduce.median(totals)
    return None if med is None else med / 1e6


def read(ctx, scope: str, within: str = "@step"):
    if ctx.trace is None or not ctx.spool_path:
        return None
    work = os.path.dirname(os.path.dirname(ctx.spool_path))
    paths = glob.glob(os.path.join(work, "trace", "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    t0, t1 = ctx.trace_window
    return scope_ms(ctx.trace, load_scopes(paths[0]), scope, resolve(ctx, within), t0, t1)
