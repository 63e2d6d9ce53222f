#!/usr/bin/env python3
"""Hold the trace reduction to traces whose answers are known.

    python3 benchmark/check_reduce.py      # exit 0 = the reduction stands

Two traces: one written by hand below, small enough to work out on
paper; and one recorded on a TPU v5e (``testdata/recorded_trace.json.gz``:
three runs of the SFX step from PR 23's probe), on which the reduction
is compared with a second, slower way of getting the same number
(rasterising the op intervals at 1 us) and with figures read off the
trace by hand. Run it after any change to ``trace_reduce.py`` or to a
reader that takes a trace."""

import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import trace_event_time, trace_gap, trace_idle_share  # noqa: E402

failures = []


def expect(what: str, got, want, tol: float = 1e-9):
    ok = abs(got - want) <= tol * max(1.0, abs(want))
    print(f"{'ok  ' if ok else 'FAIL'} {what}: got {got!r}, want {want!r}")
    if not ok:
        failures.append(what)


def ctx_for(trace, window, cfg=None):
    c = types.SimpleNamespace()
    c.trace, c.trace_window, c.cfg = trace, window, cfg or {"trace_names": {"step": "jit_step"}}
    return c


def by_hand():
    """Window 0..1000 ns on two chips. Chip 0: ops [100,300) [250,400)
    (overlapping: union 300) and [600,700): busy 400. Chip 1: one op
    [0,500): busy 500. Mean busy 450 ns, idle share 55%. Chip 0's gaps:
    [0,100) [400,600) [700,1000). Host: stage.device_put [50,120) covers
    50 ns of the first gap; bench.sink [450,650) covers 150 of the second;
    the rest (50 + 50 + 300 = 400) is no one's."""
    trace = tr.Trace(
        device={
            0: {"XLA Ops": [("%a = f32[8]{0} fusion(x)", 100.0, 200.0),
                            ("%kern.1 = bf16[4]{0} custom-call(y), custom_call_target=\"tpu_custom_call\"", 250.0, 150.0),
                            ("%a = f32[8]{0} fusion(x)", 600.0, 100.0)],
                "XLA Modules": [("jit_step(1)", 100.0, 300.0), ("jit_step(1)", 600.0, 100.0)]},
            1: {"XLA Ops": [("%a = f32[8]{0} fusion(x)", 0.0, 500.0)],
                "XLA Modules": [("jit_step(1)", 0.0, 500.0)]},
        },
        host={"python": [("bench.window", 0.0, 1000.0), ("bench.sink", 450.0, 200.0)],
              "thread-2": [("stage.device_put", 50.0, 70.0)]},
    )
    w = tr.window_of(trace, "bench.window")
    expect("hand: window", w[1] - w[0], 1000.0)
    expect("hand: busy seconds (mean of chips)", tr.busy_seconds(trace, *w), 450e-9)
    expect("hand: idle share %", trace_idle_share.read(ctx_for(trace, w)), 55.0)
    expect("hand: step median ms over both chips' runs",
           trace_event_time.read(ctx_for(trace, w), "@step"), 300.0 / 1e6)
    kern = tr.named_events(trace, r"^%?kern", "XLA Ops", *w)
    expect("hand: kernel events on chip 0", len(kern[0]), 1)
    expect("hand: kernel time", kern[0][0][2], 150.0)
    billed = dict(tr.bill_idle_gaps(trace, *w))
    expect("hand: gap billed to stage.device_put", billed["stage.device_put"], 50e-9)
    expect("hand: gap billed to bench.sink", billed["bench.sink"], 150e-9)
    expect("hand: gap billed to no one", billed[tr.UNATTRIBUTED], 400e-9)
    # step end 400 -> sink start 450
    expect("hand: step end to sink start ms",
           trace_gap.read(ctx_for(trace, w), "@step", "bench.sink"), 50.0 / 1e6)
    top = tr.top_device_ops(trace, *w)
    expect("hand: top op is %a, mean over chips", top[0][1], (300.0 + 500.0) / 2 / 1e9)
    assert top[0][0].startswith("%a f32[8] fusion"), top
    assert tr.op_label(trace.device[0]["XLA Ops"][1][0]) == "%kern.1 bf16[4] tpu_custom_call"


def recorded():
    trace = tr.load_recording(os.path.join(HERE, "testdata", "recorded_trace.json.gz"))
    w = tr.window_of(trace, "bench.window")
    expect("recorded: window ms", (w[1] - w[0]) / 1e6, 310.0)
    # the slow way: paint every op onto a 1 us raster
    n = int((w[1] - w[0]) / 1000.0) + 1
    raster = bytearray(n)
    for _, s, d in trace.device[0]["XLA Ops"]:
        a, b = max(s, w[0]), min(s + d, w[1])
        if b > a:
            i0, i1 = int((a - w[0]) / 1000.0), int((b - w[0]) / 1000.0)
            raster[i0:max(i1, i0 + 1)] = b"\x01" * (max(i1, i0 + 1) - i0)
    painted = sum(raster) * 1e-6
    busy = tr.busy_seconds(trace, *w)
    expect("recorded: busy seconds against the raster", busy, painted, tol=2e-3)
    modules = sum(d for _, _, d in trace.device[0]["XLA Modules"]) / 1e9
    expect("recorded: ops fill their three programs", busy, modules, tol=1e-2)
    expect("recorded: step median ms (read off the trace: 55.808)",
           trace_event_time.read(ctx_for(trace, w, {"trace_names": {"step": "jit__device_step"}}),
                                 "@step"), 55.808228, tol=1e-6)
    calib = tr.named_events(trace, r"^%?fused_calibrate", "XLA Ops", *w)[0]
    expect("recorded: one calibration kernel per step", len(calib), 3)
    billed = tr.bill_idle_gaps(trace, *w)
    expect("recorded: billed gaps add up to the idle time",
           sum(s for _, s in billed), (w[1] - w[0]) / 1e9 - busy, tol=1e-6)
    # sink starts 190.765, 273.792 ms after step ends 175.213, 250.893; the
    # third sink (355.598) lies outside the 310 ms recording
    expect("recorded: step end to sink start ms (median of 15.552, 22.899)",
           trace_gap.read(ctx_for(trace, w, {"trace_names": {"step": "jit__device_step"}}),
                          "@step", "bench.sink"), (15.551892 + 22.898999) / 2, tol=1e-5)


if __name__ == "__main__":
    by_hand()
    recorded()
    print("FAILED: " + ", ".join(failures) if failures else "OK")
    raise SystemExit(1 if failures else 0)
