#!/usr/bin/env python3
"""Hold the scope reader to profiles whose answers are known.

    python3 benchmark/check_scope.py      # exit 0 = the reader stands

Two profiles, as ``check_reduce.py`` has two traces: one written by hand
below (an ``XSpace`` of a dozen fields, encoded here, small enough to
work out on paper), and one recorded on a TPU v5e
(``testdata/recorded_scopes.xplane.pb.gz``: three runs of the SFX step of
PR 24, driven by hand, the profiler's own file gzipped), on which the
reader is compared with figures read off the profile by a second way
(the full protobuf schema, outside this repo) and with what has to hold
of any profile: the scopes of the step share out its operations, and no
scope takes longer than the step. Run it after any change to
``readers/trace_scope_time.py``."""

import gzip
import os
import shutil
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import trace_scope_time as ts  # noqa: E402

RECORDING = os.path.join(HERE, "testdata", "recorded_scopes.xplane.pb.gz")
STEP = "^%?jit__device_step"
failures = []


def expect(what: str, got, want, tol: float = 1e-9):
    ok = got is not None and abs(got - want) <= tol * max(1.0, abs(want))
    print(f"{'ok  ' if ok else 'FAIL'} {what}: got {got!r}, want {want!r}")
    if not ok:
        failures.append(what)


def expect_true(what: str, cond):
    print(f"{'ok  ' if cond else 'FAIL'} {what}")
    if not cond:
        failures.append(what)


# -- a protobuf ENCODER, for the hand-made profile only ----------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def ld(number: int, payload: bytes) -> bytes:  # a length-delimited field
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def vi(number: int, value: int) -> bytes:  # a varint field
    return varint(number << 3) + varint(value)


def instruction(name: str, op_name: str) -> bytes:
    meta = ld(1, b"some_type") + ld(2, op_name.encode()) if op_name else b""
    # opcode (2), a fixed64 (wire type 1) and a fixed32 (5) the reader must skip
    body = ld(1, name.encode()) + ld(2, b"fusion") + varint(9 << 3 | 1) + bytes(8)
    body += varint(10 << 3 | 5) + bytes(4) + vi(35, 7)
    return body + (ld(7, meta) if meta else b"")


def by_hand():
    """One program, two computations, four instructions: ``a`` under
    x/find_peaks/y, ``b.1`` under find_peaks_more (NOT the scope: a
    component has to match whole), ``c`` under peaknet, ``d`` without
    metadata. Window 0..1000 ns; runs of jit_step at [100,400) and
    [500,800), and one at [900,1100) that leaves the window. Ops: run 1
    holds a (50), c (100), b.1 (30); run 2 holds a (70), d (40), c (90).
    find_peaks per run: 50, 70 -> median 60 ns; peaknet: 100, 90 -> 95."""
    comp1 = ld(1, b"main") + ld(2, instruction("a", "jit(step)/x/find_peaks/y/top_k"))
    comp1 += ld(2, instruction("b.1", "jit(step)/find_peaks_more/add"))
    comp2 = ld(1, b"fused") + ld(2, instruction("c", "jit(step)/peaknet/jit(apply)/conv"))
    comp2 += ld(2, instruction("d", ""))
    hlo = ld(1, ld(1, b"jit_step") + ld(3, comp1) + ld(3, comp2))
    stat = vi(1, 4) + ld(6, hlo)
    event_metadata = vi(1, 99) + ld(2, b"jit_step(99)") + ld(5, stat)
    metadata_plane = vi(1, 3) + ld(2, b"/host:metadata") + ld(4, vi(1, 99) + ld(2, event_metadata))
    other_plane = vi(1, 1) + ld(2, b"/device:TPU:0") + ld(4, vi(1, 5) + ld(2, ld(5, ld(6, b"junk"))))
    xspace = ld(1, other_plane) + ld(1, metadata_plane)
    scopes = {}
    for proto in ts.hlo_protos(xspace):
        scopes.update(ts.instruction_scopes(proto))
    expect_true("hand: four instructions, their op_names", scopes == {
        "a": "jit(step)/x/find_peaks/y/top_k", "b.1": "jit(step)/find_peaks_more/add",
        "c": "jit(step)/peaknet/jit(apply)/conv", "d": ""})
    trace = tr.Trace(
        device={0: {
            "XLA Modules": [("jit_step(99)", 100.0, 300.0), ("jit_step(99)", 500.0, 300.0),
                            ("jit_step(99)", 900.0, 200.0)],
            "XLA Ops": [("%a = f32[8]{0} custom-call(x)", 100.0, 50.0),
                        ("%c = f32[8]{0} fusion(x)", 150.0, 100.0),
                        ("%b.1 = f32[8]{0} fusion(x)", 250.0, 30.0),
                        ("%a = f32[8]{0} custom-call(x)", 500.0, 70.0),
                        ("%d = f32[8]{0} copy(x)", 570.0, 40.0),
                        ("%c = f32[8]{0} fusion(x)", 610.0, 90.0),
                        ("%a = f32[8]{0} custom-call(x)", 900.0, 60.0)],
        }},
        host={"python": [("bench.window", 0.0, 1000.0)]},
    )
    w = tr.window_of(trace, "bench.window")
    expect("hand: find_peaks ms", ts.scope_ms(trace, scopes, "find_peaks", "^%?jit_step", *w), 60.0 / 1e6)
    expect("hand: peaknet ms", ts.scope_ms(trace, scopes, "peaknet", "^%?jit_step", *w), 95.0 / 1e6)
    expect_true("hand: a scope no program names gives nothing",
                ts.scope_ms(trace, scopes, "calib", "^%?jit_step", *w) is None)
    expect_true("hand: no run in the window gives nothing",
                ts.scope_ms(trace, scopes, "peaknet", "^%?jit_other", *w) is None)
    # through the front door, with a context as run.py builds it
    with tempfile.TemporaryDirectory() as work:
        os.makedirs(os.path.join(work, "trace", "plugins"))
        os.makedirs(os.path.join(work, "spans"))
        with open(os.path.join(work, "trace", "plugins", "hand.xplane.pb"), "wb") as f:
            f.write(xspace)
        ctx = types.SimpleNamespace(
            trace=trace, trace_window=w, cfg={"trace_names": {"step": "jit_step"}},
            spool_path=os.path.join(work, "spans", "bench.trace.jsonl"))
        expect("hand: read() finds the profile beside the spool", ts.read(ctx, "find_peaks"), 60.0 / 1e6)
        ctx.spool_path = None
        expect_true("hand: an untraced run reads nothing", ts.read(ctx, "find_peaks") is None)


def recorded():
    scopes = ts.load_scopes(RECORDING)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "recorded.xplane.pb")
        with gzip.open(RECORDING, "rb") as src, open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        trace = tr.load_xplane(path)
    w = tr.window_of(trace, "bench.window")
    runs = tr.named_events(trace, STEP, "XLA Modules", *w)[0]
    expect("recorded: runs of the step", len(runs), 3)
    step_ms = tr.median([d for _, _, d in runs]) / 1e6
    parts = {s: ts.scope_ms(trace, scopes, s, STEP, *w) for s in ("calib", "peaknet", "find_peaks")}
    for name, want in RECORDED_MS.items():
        expect(f"recorded: {name} ms per step (read off with the full schema)",
               parts[name], want, tol=1e-5)
    # what holds of any profile
    ops_ms = sum(d for _, s, d in trace.device[0]["XLA Ops"]
                 if any(r[1] <= s < r[1] + r[2] for r in runs)) / len(runs) / 1e6
    unscoped = ops_ms - sum(parts.values())
    print(f"     step {step_ms:.3f} ms; its ops {ops_ms:.3f} ms: calib {parts['calib']:.3f} + "
          f"peaknet {parts['peaknet']:.3f} + find_peaks {parts['find_peaks']:.3f} + "
          f"outside the three scopes {unscoped:.3f}")
    expect_true("recorded: the three scopes leave under 3% of the step's ops unnamed",
                0.0 <= unscoped <= 0.03 * ops_ms)
    expect_true("recorded: no scope is longer than the step, nor all three together",
                sum(parts.values()) <= step_ms)
    expect_true("recorded: the calibration scope is the calibration kernel and its copies",
                parts["calib"] >= tr.median([e[2] for e in tr.named_events(
                    trace, r"^%?fused_calibrate", "XLA Ops", *w)[0]]) / 1e6)


# Read off the recording with the protobuf schema itself (tensorflow's
# xplane_pb2 and hlo_pb2, which the benchmark does not use): per-step sums
# of the XLA Ops under each scope, median over the three runs.
RECORDED_MS = {"calib": 1.028382266, "peaknet": 89.745060156, "find_peaks": 20.173367344}


if __name__ == "__main__":
    by_hand()
    if os.path.exists(RECORDING):
        recorded()
    else:
        failures.append("no recording")
        print(f"FAIL no recording at {RECORDING}")
    print("FAILED: " + ", ".join(failures) if failures else "OK")
    raise SystemExit(1 if failures else 0)
