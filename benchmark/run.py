#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One cell of ``BENCHMARK.json`` in a new process: find the TPU the cell
asks for (anything else is an error, never a CPU fallback), create the
transport, start the JAX-free load generator (``generator.py``), build
the program under test through its adapter (``programs/<name>.py``) with
weights made on the device from ``--seed``, warm the cell's own shapes,
then stream for ``--seconds`` and print, last, one JSON object: the
cell's end-to-end metrics (``--trace 0``) or its per-layer metrics from a
profiler trace and the program's own spans and counters (``--trace 1``).

Beside every run sleeps a child (``stops.py``), started before anything
else, that notes when the sandbox stood still. ``setup_s`` is process
start -> window start LESS the stopped seconds in between (printed
beside it as ``setup_stopped_s``) and less what is left of the call that
opens the TPU (``device_open_s``): ``metrics/setup_s.json`` says which
phases go. The stopped milliseconds inside the window are the per-layer
``stopped_ms``, and every final line carries the stops and set-up's
phases under ``"stops"``, so that a reading can be told from the
machine's state.

Nothing here names a cell, a configuration or a metric: a cell names its
configuration file (``configs/``), its traffic file (``traffic/``) and,
through the manifest, its metrics, each a data file (``metrics/``) naming
a reader (``readers/``). A later PR adds files and manifest entries.

``--rehearse`` runs the same path on the CPU at the configuration's tiny
``rehearse`` size (virtual devices for a mesh cell) to check paths and the
final line's shape. It prints NO metric value and its line is marked
``"rehearsal": true``: it can never stand as a proof run.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # setup_s counts from here, less the sandbox's stops

import argparse
import dataclasses
import glob
import importlib
import json
import os
import shutil
import subprocess
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WINDOW_ANNOTATION = "bench.window"


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def die(msg: str, code: int = 2):
    print(f"[bench] ERROR: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass
class Plan:
    """One run, as the command line and the data files describe it."""

    args: argparse.Namespace
    cell: dict
    cfg: dict
    traffic: dict
    chips: int
    platform: str
    traced: bool
    wanted: list  # the manifest entries of the metrics this run reports
    work: str  # scratch directory inside the checkout, removed at exit
    stopwatch: subprocess.Popen  # the sleeping child (stops.py)
    marks: list  # (phase, instant it ended, CPU seconds so far): set-up, cut end to end


@dataclasses.dataclass
class Window:
    t_start: float
    t_end: float
    stop_at: float  # the generator's last offer


class Context:
    """What a metric reader may look at. Host-clock series are cut to the
    window here, once; the trace is reduced to plain lists."""

    def __init__(self):
        self.cfg = None
        self.peaks = None
        self.window = (0.0, 0.0)
        self.window_s = 0.0
        self.t_process = None  # the instant setup_s counts from
        self.stops = None  # the sleeping child's gaps, (start, seconds) each
        self.phases = {}  # set-up phase -> (start, end), end to end from t_process
        self.series = {}
        self.metrics = None
        self.spool_path = None
        self.trace = None
        self.trace_window = (0.0, 0.0)
        self.results = None  # (rank, idx, done_t) of every result at the sink
        self.generated = None  # {"due", "sent", "blocked"} per frame, by event_idx


def cell_metrics(manifest: dict, cell: str, kind: str) -> list:
    return [
        m for m in manifest[kind]
        if "workloads" not in m or cell in m["workloads"]
    ]


def read_metric(ctx: Context, entry: dict):
    """One metric through its data file and reader; ``None`` when the
    reader found nothing to read (the metric is then left out)."""
    spec = load_json(HERE, "metrics", entry["name"] + ".json")
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(ctx, **spec.get("args", {}))


def start_stopwatch() -> subprocess.Popen:
    """The sleeping child, off the program's path: no site packages, no
    JAX, nothing of the repo but ``stops.py``."""
    return subprocess.Popen(
        [sys.executable, "-S", os.path.join(HERE, "stops.py")],
        stdin=subprocess.DEVNULL, stdout=subprocess.PIPE, text=True, cwd=ROOT,
    )


def end_stopwatch(child: subprocess.Popen) -> list:
    """Tell the child to end and take its gaps; a run without its record
    has no ``setup_s`` to give and dies."""
    child.terminate()
    try:
        out, _ = child.communicate(timeout=30.0)
        record = json.loads(out.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, IndexError, ValueError) as e:
        die(f"the sleeping child gave no record of the sandbox's stops "
            f"(exit code {child.poll()}): {e!r}", 3)
    if record["first"] - T_PROCESS > 2.0:
        die(f"the sleeping child took {record['first'] - T_PROCESS:.1f} s to its first "
            f"tick: the stops before it are unseen", 3)
    return [(float(a), float(b)) for a, b in record["gaps"]]


def mark(marks: list, phase: str, at: float = None) -> None:
    """One more phase of set-up has ended (now, or at the instant given)."""
    marks.append((phase, time.monotonic() if at is None else at, time.process_time()))


def phase_spans(marks: list) -> dict:
    """``{phase: (start, end, CPU seconds of this process)}``: the marks
    cut set-up end to end, the first phase starting with the process."""
    spans, t_prev, cpu_prev = {}, T_PROCESS, 0.0
    for name, t, cpu in marks:
        spans[name] = (t_prev, t, cpu - cpu_prev)
        t_prev, cpu_prev = t, cpu
    return spans


def main(argv=None) -> int:
    stopwatch = start_stopwatch()
    try:
        return run(argv, stopwatch)
    finally:
        if stopwatch.poll() is None:
            stopwatch.kill()
        stopwatch.wait()


def run(argv, stopwatch: subprocess.Popen) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, no metric values: checks paths only")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "psana_ray_tpu")):
        die("no psana_ray_tpu package beside benchmark/: the benchmark measures "
            "the program, and a directory without it has nothing to measure")
    sys.path.insert(0, ROOT)
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        die(f"unknown workload {args.workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[args.workload]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT, cfg_entry["file"])
    chips = int(cell["chips"])
    platform = "tpu"
    if args.rehearse:
        cfg.update(cfg["rehearse"])
        platform = "cpu"
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={chips}"
        ).strip()
    traced = bool(args.trace)
    work = os.path.join(ROOT, ".bench_work", str(os.getpid()))
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    plan = Plan(
        args=args, cell=cell, cfg=cfg, chips=chips, platform=platform, traced=traced, work=work,
        stopwatch=stopwatch, marks=[],
        traffic=load_json(HERE, "traffic", cell["traffic"] + ".json"),
        wanted=cell_metrics(manifest, cell["name"], "per_layer" if traced else "end_to_end"),
    )
    try:
        return run_cell(plan)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def run_cell(plan: Plan) -> int:
    """The transport and the generator child around :func:`measure`; both
    are gone when this returns, whatever happened in between."""
    from psana_ray_tpu.transport.shm_ring import ShmRingBuffer
    from psana_ray_tpu.utils.hostmem import enable_large_alloc_reuse

    enable_large_alloc_reuse()  # what every CLI of the program does first
    transport = plan.cfg["transport"]
    if transport["scheme"] != "shm":
        die(f"transport scheme {transport['scheme']!r} has no opener here yet")
    mark(plan.marks, "imports")
    ring_name = f"bench_{os.getpid()}"
    ring = ShmRingBuffer.create(ring_name, maxsize=int(transport["slots"]))
    mark(plan.marks, "native_ring")
    monitor = ShmRingBuffer.attach(ring_name, retries=0) if plan.traced else None
    report_path = os.path.join(plan.work, "generator.npz")
    spec = {
        "address": f"shm://{ring_name}", "detector": plan.cfg["detector"],
        "traffic": plan.traffic, "seed": plan.args.seed, "report_path": report_path,
        "slots": int(transport["slots"]),
        "traced": plan.traced and int(plan.traffic.get("trace_every", 1)) > 0,
    }
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "generator.py"), json.dumps(spec)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env, cwd=ROOT,
    )
    aux_stop = threading.Event()
    try:
        return measure(plan, ring, monitor, child, report_path, aux_stop)
    finally:
        aux_stop.set()
        if child.poll() is None:
            child.kill()
        child.wait()
        if monitor is not None:
            monitor.disconnect()
        ring.destroy()


def child_line(child, what: str, timeout_s: float) -> dict:
    """One protocol line from the generator, or the run dies."""
    box = {}

    def _read():
        box["line"] = child.stdout.readline()

    t = threading.Thread(target=_read, daemon=True)
    t.start()
    t.join(timeout_s)
    line = box.get("line")
    if not line:
        die(f"the generator did not report {what!r} within {timeout_s:.0f} s "
            f"(exit code {child.poll()})", 3)
    msg = json.loads(line)
    if what not in msg:
        die(f"the generator said {msg!r}, not {what!r}", 3)
    return msg


def measure(plan: Plan, ring, monitor, child, report_path, aux_stop) -> int:
    import numpy as np

    args, cfg, traffic, traced = plan.args, plan.cfg, plan.traffic, plan.traced
    marks = plan.marks
    mark(marks, "generator_spawn")
    import jax

    from benchmark import harness
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache, device_summary

    mark(marks, "jax_import")
    cache_dir = configure_compile_cache()
    # every program of a run, however quick to compile, comes from the
    # cache after the cell's first run in a checkout (JAX's default keeps
    # only what took over a second, and recompiles the rest each start)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        dev = device_summary()
    except RuntimeError as e:
        die(f"JAX found no device: {e}")
    peaks_table = load_json(HERE, "peaks.json")["devices"]
    if dev["platform"] != plan.platform or dev["count"] != plan.chips:
        die(f"this cell needs {plan.chips} {plan.platform} device(s); JAX found {dev}")
    if not args.rehearse and dev["kind"] not in peaks_table:
        die(f"device kind {dev['kind']!r} is not in benchmark/peaks.json: no peak to "
            f"hold a roofline share against, and no default")
    devices = jax.devices()
    mark(marks, "device_open")  # the cache directory, then jax.devices(): the TPU opens

    compiles = []  # (monotonic instant, name, seconds) of every backend compile
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append((time.monotonic(), name, secs))
        if "backend_compile" in name else None
    )

    program = importlib.import_module(f"benchmark.programs.{cfg['program']}").Program(
        cfg, args.seed, plan.work, devices
    )
    mark(marks, "build")  # weights made on the device, constants
    frames = harness.make_check_frames(cfg["detector"], min(8, program.frames_per_batch), args.seed)
    program.warm(frames)
    mark(marks, "warm_up")  # compile or cache load, two batches
    spool_path = None
    if traced:
        from psana_ray_tpu.obs.tracing import TRACER

        TRACER.configure(os.path.join(plan.work, "spans"), sample_every=1, process="bench")
        spool_path = TRACER.spool_path
    ready = child_line(child, "ready", 120.0)

    # the stream runs this long before the window opens; a configuration
    # whose loop needs longer to reach its steady state (batch arenas that
    # fault in on their first fill) states its own, and the longer holds
    lead_s = max(float(traffic["lead_s"]), float(cfg.get("stream_lead_s", 0.0)))
    mark(marks, "generator_ready")
    t_go = marks[-1][1] + 0.25
    t_start = t_go + lead_s
    mark(marks, "lead", at=t_start)
    t_end = t_start + args.seconds
    win = Window(t_start, t_end, t_end + float(traffic["tail_s"]))
    child.stdin.write(json.dumps({"t_go": t_go, "stop_at": win.stop_at}) + "\n")
    child.stdin.flush()
    say(f"device {dev}; compile cache {cache_dir}")
    say(f"set-up by the wall clock {t_start - T_PROCESS:.2f} s (its phases, less the sandbox's "
        f"stops, follow the window); generator pool {ready['pool_s']:.2f} s + ring pre-fault "
        f"{ready['prefault_s']:.2f} s (in the child, overlapped)")

    depth = []
    trace_dir = os.path.join(plan.work, "trace")
    trace_s = min(float(traffic["trace_window_s"]), 0.6 * args.seconds)
    threads = [threading.Thread(
        target=watchdog, args=(child, ring, win.stop_at + 120.0, aux_stop), daemon=True)]
    if traced:
        threads.append(threading.Thread(
            target=sample_depth, args=(monitor, depth, t_start, t_end, aux_stop), daemon=True))
        threads.append(threading.Thread(
            target=take_trace, daemon=True,
            args=(jax, trace_dir, t_start + 0.25 * (args.seconds - trace_s), trace_s, aux_stop)))
    for t in threads:
        t.start()

    seen = program.run(ring)  # blocks until the generator's end of stream
    t_drained = time.monotonic()
    gaps = end_stopwatch(plan.stopwatch)
    aux_stop.set()
    for t in threads:
        t.join(timeout=120.0)
    done = child_line(child, "done", 60.0)
    child.wait(timeout=30)
    if traced:
        TRACER.close()
    memory_peak = max(
        (s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0))
        for s in (d.memory_stats() or {} for d in devices)
    )

    gen = dict(np.load(report_path))
    rank, idx, done_t = program.sink.log.arrays()
    file_rows = program.sink.close_and_count()
    sent_n = len(gen["due"])
    lost = sent_n - len(np.intersect1d(idx, np.arange(sent_n)))
    duplicated = len(idx) - len(np.unique(idx))
    foreign = int(np.sum((idx < 0) | (idx >= sent_n)))
    in_window_compiles = [c for c in compiles if t_start <= c[0] < t_end]
    closed = traffic["loop"] == "closed"
    offered = gen["sent"] if closed else gen["due"]
    attempted = int(np.sum((offered >= t_start) & (offered < t_end)))
    failed = int(lost + duplicated + foreign)
    say(f"stream: sent {sent_n} (generator says {done['sent']}), results {len(idx)}, "
        f"pipeline saw {seen}, lost {lost}, duplicated {duplicated}, foreign {foreign}, "
        f"sink rows {file_rows} (warm-up {program.warm_rows}), drained "
        f"{t_drained - win.stop_at:.2f} s after the last offer; compiles inside the window: "
        f"{len(in_window_compiles)}")
    say_stalls(np, program.sink.log, gen, win)
    spans = phase_spans(marks)
    stops_record = say_stops(gaps, spans, win)
    if not closed:
        say_backlog(np, traffic, gen, idx, done_t, win)

    t0 = time.monotonic()
    check = program.check(frames)
    say(f"correct check ({time.monotonic() - t0:.1f} s): {json.dumps(check)}")
    correct = bool(
        check["ok"] and failed == 0 and not in_window_compiles
        and file_rows == program.warm_rows + len(idx) and seen == sent_n == done["sent"]
    )

    ctx = Context()
    ctx.cfg = cfg
    ctx.peaks = peaks_table.get(dev["kind"])
    ctx.window, ctx.window_s = (t_start, t_end), args.seconds
    ctx.t_process, ctx.stops = T_PROCESS, gaps
    ctx.phases = {name: (a, b) for name, (a, b, _) in spans.items()}
    ctx.metrics, ctx.spool_path = program.metrics, spool_path
    ctx.results, ctx.generated = (rank, idx, done_t), gen
    log = program.sink.log
    append_t = np.asarray(log.append_t)
    due_in = (gen["due"] >= t_start) & (gen["due"] < t_end)
    sent_in = (gen["sent"] >= t_start) & (gen["sent"] < t_end)
    ctx.series = {
        "gen.late_s": (gen["sent"] - gen["due"])[due_in],
        "gen.blocked_s": gen["blocked"][sent_in],
        "sink.append_s": np.asarray(log.append_s)[(append_t >= t_start) & (append_t < t_end)],
    }
    if depth:
        ctx.series["ring.depth"] = np.asarray(depth, np.float64)

    device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
              "memory_peak_bytes": int(memory_peak)}
    breakdown = None
    if traced:
        if program.metrics is not None:
            stages = program.metrics.stages
            say("program stage histograms of traced frames, median ms: " + json.dumps(
                {st: stages.stat(st).quantile(0.5) * 1e3 for st in stages.stages()}))
        breakdown = reduce_trace(ctx, trace_dir, device, args.rehearse)

    metrics = {}
    for entry in plan.wanted:
        value = read_metric(ctx, entry)
        if value is None:
            say(f"metric {entry['name']}: nothing to read, left out")
            continue
        metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.rehearse:
        # a CPU run gives no time, rate or share: names only, no values
        result.update(rehearsal=True, metrics={}, would_report=sorted(metrics))
    else:
        result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None and not args.rehearse:
        result["breakdown"] = breakdown
    result.update(cell=plan.cell["name"], seed=args.seed, seconds=args.seconds, trace=int(traced),
                  stops=stops_record)
    # each number compared, beside its limit, ends the standard error too:
    # where a run is not correct, that is what the driver's record keeps
    print(f"[bench] correct={correct}: failed {failed} (limit 0), compiles inside the window "
          f"{len(in_window_compiles)} (limit 0), sink rows {file_rows} (must be "
          f"{program.warm_rows + len(idx)}), pipeline saw {seen} of {sent_n} sent; against the "
          f"reference: {json.dumps(check)}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def say_stalls(np, log, gen, win: Window) -> None:
    """Where a stall sat, if there was one: the sink's longest silence, the
    slowest append, the generator's longest wait on a full ring."""
    log_t = np.asarray(log.append_t)
    if len(log_t) <= 2:
        return
    in_w = log_t[(log_t >= win.t_start) & (log_t < win.t_end)]
    gaps = np.diff(in_w) if len(in_w) > 1 else np.zeros(1)
    top = np.argsort(gaps)[::-1][:3]
    longest = ", ".join(
        f"{gaps[k] * 1e3:.1f} ms at window second {in_w[k] - win.t_start:.2f}" for k in top)
    fifths = np.histogram(in_w, bins=np.linspace(win.t_start, win.t_end, 6))[0]
    say(f"stalls: longest silences at the sink {longest} (median {np.median(gaps) * 1e3:.1f}); "
        f"appends in each fifth of the window {fifths.tolist()}; slowest append "
        f"{max(log.append_s) * 1e3:.1f} ms; generator's longest wait on a full ring "
        f"{gen['blocked'].max() * 1e3:.1f} ms, latest send "
        f"{(gen['sent'] - gen['due']).max() * 1e3:.1f} ms after due")


def say_stops(gaps, spans: dict, win: Window) -> dict:
    """What the sleeping child saw: set-up phase by phase, by the wall
    clock and stopped, and every stop inside the window. The record rides
    on the final line under ``"stops"`` (no metric: the readers take
    theirs from the same gaps)."""
    from benchmark import stops

    phases = {name: [b - a, stops.overlap_s(gaps, a, b), cpu]
              for name, (a, b, cpu) in spans.items()}
    wall = win.t_start - T_PROCESS
    in_setup = stops.inside(gaps, T_PROCESS, win.t_start)
    stopped = sum(g[1] for g in in_setup)
    say(f"set-up: wall {wall:.2f} s, stopped {stopped:.2f} s; phases, wall (stopped; "
        f"CPU seconds of this process): " + ", ".join(
            f"{name} {wall:.2f} ({stop:.2f}; {cpu:.2f})"
            for name, (wall, stop, cpu) in phases.items()))
    in_window = stops.inside(gaps, win.t_start, win.t_end)
    say(f"stops: {len(in_setup)} in set-up; in the window "
        f"{len(in_window)}, {sum(g[1] for g in in_window) * 1e3:.1f} ms: " + (", ".join(
            f"{g[1] * 1e3:.1f} ms at window second {g[0]:.2f}" for g in in_window) or "none"))
    return {"setup_wall_s": wall, "setup_stopped_s": stopped, "setup_phases": phases,
            "window": in_window, "setup": in_setup}


def say_backlog(np, traffic, gen, idx, done_t, win: Window) -> None:
    """Open loop: is the backlog growing? The same latency in both halves
    of the window says no."""
    ok = (idx >= 0) & (idx < len(gen["due"]))
    due = gen["due"][idx[ok]]
    lat = done_t[ok] - due
    mid = 0.5 * (win.t_start + win.t_end)
    first = lat[(due >= win.t_start) & (due < mid)]
    second = lat[(due >= mid) & (due < win.t_end)]
    if len(first) and len(second):
        say(f"open loop at {traffic['rate_fps']} frames/s: median latency "
            f"{np.median(first) * 1e3:.1f} ms in the first half of the window, "
            f"{np.median(second) * 1e3:.1f} ms in the second; generator late p95 "
            f"{np.quantile(gen['sent'] - gen['due'], 0.95) * 1e3:.2f} ms; the plain median over "
            f"the window {np.median(np.concatenate([first, second])) * 1e3:.2f} ms")


def reduce_trace(ctx: Context, trace_dir: str, device: dict, rehearse: bool) -> dict:
    """Load the profiler's trace into ``ctx``, put the device's busy
    seconds and the traced window's length into ``device``, and return the
    breakdown. A traced run without a trace, or without a device operation
    in it, is an error and not a result."""
    from benchmark import trace_reduce

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        die("--trace 1 but the profiler wrote no trace", 3)
    ctx.trace = trace_reduce.load_xplane(paths[0])
    ctx.trace_window = w0, w1 = trace_reduce.window_of(ctx.trace, WINDOW_ANNOTATION)
    busy = trace_reduce.busy_seconds(ctx.trace, w0, w1)
    if not rehearse and busy <= 0:
        die("the trace holds no device operation inside the window", 3)
    device["busy_s"], device["window_s"] = busy, (w1 - w0) / 1e9
    return {
        "device_ops": trace_reduce.top_device_ops(ctx.trace, w0, w1),
        "idle_gaps": trace_reduce.bill_idle_gaps(ctx.trace, w0, w1),
    }


def sample_depth(monitor, out: list, t_start: float, t_end: float, stop: threading.Event):
    """Slots occupied, every 10 ms of the window, over a handle of the
    parent's own (never the data path's)."""
    while time.monotonic() < t_start and not stop.is_set():
        time.sleep(0.005)
    while time.monotonic() < t_end and not stop.is_set():
        try:
            out.append(monitor.stats()["depth"])
        except Exception:  # noqa: BLE001 — the ring went away: the stream is over
            return
        time.sleep(0.01)


def take_trace(jax, trace_dir: str, at: float, seconds: float, stop: threading.Event):
    """A profiler trace of ``seconds`` of the window, python tracer off
    (its events would swamp the device's), with the traced span held
    open as a host annotation so that the reduction knows its bounds on
    the profiler's own clock."""
    while time.monotonic() < at and not stop.is_set():
        time.sleep(0.005)
    if stop.is_set():
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
            time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()


def watchdog(child, ring, deadline: float, stop: threading.Event):
    """A generator that died, or a stream that never ends, must not hang
    the run: closing the ring ends the consumer's loop."""
    while not stop.is_set():
        rc = child.poll()
        if (rc is not None and rc != 0) or time.monotonic() > deadline:
            print(f"[bench] watchdog: generator exit code {rc}, closing the ring",
                  file=sys.stderr, flush=True)
            ring.close()
            return
        time.sleep(0.2)


if __name__ == "__main__":
    raise SystemExit(main())
