"""Headline benchmark + per-config diagnostics for the streaming pipeline.

Headline (stdout, ONE JSON line): BASELINE config 2 — the full epix10k2M
calibration chain (pedestal + gain + common-mode + mask, the reference's
only per-event compute, `producer.py:92-95` writ large) as the fused
Pallas kernel:

    {"metric": "epix10k2M frames/sec/chip (fused calibration)",
     "value": N, "unit": "frames/s", "vs_baseline": R, ...extras}

vs_baseline: the north-star target is >=10,000 frames/s on v5e-16
(BASELINE.md), i.e. 625 frames/s/chip — R = value / 625. The reference
itself publishes no numbers. Extra keys carry the other BASELINE configs
(passthrough fps, e2e p50, ResNet-50 fps, U-Net fps, fan-in fps).

Measurement methodology (PERF_NOTES.md): JAX dispatch is asynchronous and
a host clock around a dispatch also times the host. The clock used for
device configs is the device's own: each one runs THREE warm dispatches
on distinct-content inputs (device-side rolls) under
``jax.profiler.trace`` and takes the MEDIAN per-dispatch module time off
the trace, recording n/min/max in the artifact. Host-side streaming
numbers (passthrough, e2e, fan-in) are honest wall-clock — they measure
the host pipeline, not the device.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import sys
import tempfile
import threading
import time

import numpy as np

PER_CHIP_TARGET_FPS = 10_000 / 16  # v5e-16 north star, per chip

# Artifact-survival budgets (seconds). The driver kills the whole bench at
# some unknown timeout (round 2 died at rc=124 with zero parseable output);
# our own watchdog must always fire first, emit the current JSON, and exit 0.
GLOBAL_BUDGET_S = float(os.environ.get("BENCH_GLOBAL_BUDGET_S", "2700"))
HEADLINE_BUDGET_S = float(os.environ.get("BENCH_HEADLINE_BUDGET_S", "240"))
SECTION_BUDGET_S = float(os.environ.get("BENCH_SECTION_BUDGET_S", "240"))
# Budget rationale: a section timeout os._exit()s the whole bench (a hung
# C call cannot be interrupted any other way), which forfeits every LATER
# section — so budgets carry cold-compile headroom (fused U-Net + oracle
# + s4 compile in ~2-4 min on an empty .jax_cache); a warm full run is
# ~8-9 min, but a COLD full run measured 18+ min in r5 (the
# old 1080 s global fired mid-quality-probe and forfeited every later
# section), so the global budget covers the cold case WITH margin: the
# r5 additions (320-step quality probe, trained MoE-ViT leg) put a
# clean warm-cache run at ~25 min, so cold ≈ 35 min — 2700 s leaves
# ~10 min of slack rather than zero. The driver's
# own kill timeout is UNKNOWN (round 2 died at rc=124): the defense
# there is not the budget but the emission discipline — the headline
# prints before any diagnostic and every section re-emits, so stdout's
# last line is a complete-so-far artifact at any kill point (round 2
# printed nothing until the very end, which is why its timeout produced
# parsed=null).


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Indestructible-artifact machinery.  The final JSON line is held in _FINAL
# and (re)printed after the headline and after every diagnostic section; the
# driver takes the LAST parseable line, so each emit supersedes the previous
# with strictly more data.  A watchdog thread enforces per-section + global
# deadlines with os._exit(0) — a raw syscall that works even when the main
# thread is wedged inside a C extension (the round-2 failure mode: the TPU
# backend went UNAVAILABLE and a diagnostic hung until the driver's kill).
# ---------------------------------------------------------------------------

_FINAL = {
    "metric": "epix10k2M frames/sec/chip (fused calibration)",
    "value": 0.0,
    "unit": "frames/s",
    "vs_baseline": 0.0,
}

# The driver captures only a TAIL WINDOW of stdout (~2000 chars) and parses
# the last line it can. Round 4's full-extras line outgrew that window and
# the captured line was HEAD-truncated — parsed=null, the whole round's
# numbers invisible. So the LAST line is now a compact headline hard-capped
# at _COMPACT_CAP bytes (cap + one full-extras line before it << window),
# built from this priority-ordered key list; the complete dict goes to
# bench_full.json (rewritten on every emit).
_COMPACT_CAP = 1400
_COMPACT_KEYS = (
    "watchdog_fired",
    "sections_soft_cancelled",
    "backend_degraded",
    "smoke_mode",
    "device_calib_ms_per_frame",
    "device_resnet50_fps",
    "device_resnet50_accuracy",
    "device_unet_fps",
    "device_unet_recall",
    "device_unet_precision",
    "device_unet_threshold",
    "device_unet_s4_fps",
    "device_unet_s4_recall",
    "device_unet_s4_precision",
    "device_unet_s4_threshold",
    "device_vit_fps",
    "device_vit_accuracy",
    "device_moe_vit_fps",
    "device_moe_vit_accuracy",
    "device_latency_operating_point",
    "device_sfx_pipeline_fps",
    "device_calib_jungfrau4M_fps",
    "host_passthrough_fps",
    "host_fanin_volume_fps",
    "host_fanin_record_rate_fps",
    "e2e_fps",
    "host_cpu_cores",
)


def _compact_line() -> bytes:
    """The always-parseable final line: headline fields + as many priority
    keys as fit under _COMPACT_CAP. Built freshly on every emit (no shared
    mutable state — signal-handler reentrant); self-checked by parsing the
    exact bytes written, so a malformed final line is impossible."""
    # snapshot first (atomic C-level copy under the GIL): the watchdog
    # thread emits while the main thread may be inserting keys, and
    # ITERATING a mutating dict raises — the copy cannot
    snap = dict(_FINAL)
    compact = {k: snap.get(k) for k in ("metric", "value", "unit", "vs_baseline")}
    compact["full_extras"] = "bench_full.json"
    for k in _COMPACT_KEYS:
        if k not in snap:
            continue
        candidate = dict(compact)
        candidate[k] = snap[k]
        if len(json.dumps(candidate)) > _COMPACT_CAP:
            continue  # oversized value (e.g. a dict): skip, try smaller keys
        compact = candidate
    line = json.dumps(compact)
    json.loads(line)  # self-check: the emitted artifact must parse
    if len(line) > _COMPACT_CAP:  # unreachable by construction; belt+braces
        line = json.dumps({k: compact[k] for k in ("metric", "value", "unit", "vs_baseline")})
    return (line + "\n").encode()


def emit_final():
    # unbuffered os.write, NO lock: this is called from the main thread,
    # the watchdog thread, and the SIGTERM handler (which runs on the main
    # thread and would self-deadlock on any non-reentrant lock the
    # interrupted emit already holds). ONLY the compact line goes to
    # stdout — it is < _COMPACT_CAP < PIPE_BUF, so every stdout write is
    # atomic on pipes even with the watchdog emitting concurrently; the
    # full dict (which outgrew the driver's tail window in round 4 and is
    # heading past PIPE_BUF) lives in bench_full.json instead. stdout goes
    # FIRST: a hung filesystem blocking the side-file open must not stall
    # the artifact of record (or the watchdog's path to os._exit).
    os.write(1, _compact_line())
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_full.json"), "w") as f:
            json.dump(dict(_FINAL), f)
    except Exception:
        pass  # side file is best-effort; stdout is the artifact of record


# ---------------------------------------------------------------------------
# Baseline regression gate (ISSUE 13): `bench.py --baseline BENCH_rXX.json`
# compares this run's key rows against a prior artifact and embeds a
# `regressions` list in bench_full.json. The gate is DATA, not an exit
# code — the driver (and the tier-1 test on a synthetic pair) reads the
# list; a flaky box must not turn the bench red by itself.
# ---------------------------------------------------------------------------

# (rule name, key predicate, direction, relative tolerance, absolute floor).
# Direction "higher": current < baseline*(1-tol) is a regression;
# "lower": current > baseline*(1+tol). The absolute floor suppresses
# noise on near-zero values (copies/allocs pins use it as the whole
# tolerance).
_BASELINE_RULES = (
    ("fps", lambda k: k.endswith("_fps") or k.endswith("fps_at_operating_point")
     or k == "value", "higher", 0.15, 1e-9),
    ("latency_ms", lambda k: k.endswith("p99_ms") or k.endswith("p95_ms")
     or k.endswith("p50_ms") or k.endswith("_ms_per_frame")
     or k.endswith("ms_per_dispatch"), "lower", 0.25, 1e-9),
    ("copies_per_frame", lambda k: k.endswith("copies_per_frame"),
     "lower", 0.0, 0.05),
    ("allocs_per_frame", lambda k: k.endswith("allocs_per_frame"),
     "lower", 0.0, 0.05),
    # host-CPU cost per frame (ISSUE 16 cost model): regression-gated
    # like fps — ROADMAP item 2 is judged by this number going DOWN
    ("cpu_ns_per_frame", lambda k: k.endswith("cpu_ns_per_frame"),
     "lower", 0.15, 1e-9),
    # kernel pass-through (ISSUE 17): the brokered spliced path keeps
    # payload bytes out of the interpreter — ZERO relative tolerance;
    # the absolute floor (bytes/frame) absorbs header/bookkeeping
    # noise only, never a payload. Relay fps rows (data_plane_*_fps)
    # ride the existing fps rule (regression = lower, 15%).
    ("spliced_py_bytes", lambda k: k.endswith("py_bytes_per_frame")
     and "spliced" in k, "lower", 0.0, 4096.0),
    ("compression_ratio", lambda k: "ratio" in k.rsplit(".", 1)[-1],
     "higher", 0.15, 1e-9),
    ("quality", lambda k: k.endswith("accuracy") or k.endswith("recall")
     or k.endswith("precision"), "higher", 0.0, 0.02),
    ("lost_frames", lambda k: k.endswith("_lost") or k.endswith(".lost"),
     "lower", 0.0, 0.0),
    # model-checker counterexamples (ISSUE 18): ZERO tolerance, zero
    # floor — a single counterexample is a protocol bug, not noise.
    # exhausted_all rides the same gate via the bool-as-0/1 grammar
    # ("higher", so a truncated fleet reads as a regression too).
    ("model_counterexamples",
     lambda k: k.endswith("lint.model.counterexamples"),
     "lower", 0.0, 0.0),
    ("model_exhausted", lambda k: k.endswith("lint.model.exhausted_all"),
     "higher", 0.0, 0.0),
)


def _flatten_artifact(tree) -> dict:
    """Numeric leaves of a bench artifact as {dotted.key: float} — THE
    shared flattening grammar (obs.registry.flatten_numeric: bools as
    0/1, exemplars subtree skipped, non-finite/non-numeric dropped), so
    the baseline gate compares exactly the keys the history rings and
    /metrics record. Lists are ignored by the grammar (row dumps)."""
    from psana_ray_tpu.obs.registry import flatten_numeric

    leaves: list = []
    flatten_numeric((), tree if isinstance(tree, dict) else {}, leaves)
    return dict(leaves)


def load_baseline_artifact(path: str) -> dict:
    """A prior artifact's comparable dict: accepts a driver round file
    (``BENCH_rXX.json`` — the numbers live under ``parsed``) or a
    ``bench_full.json``. Raises on unreadable/unparseable input — the
    caller decides whether that kills anything (main() never lets it)."""
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        return doc["parsed"]
    if not isinstance(doc, dict):
        raise ValueError(f"baseline {path} is not a JSON object")
    return doc


def compare_baseline(current: dict, baseline: dict) -> list:
    """Key-row regression list between two artifacts (see
    ``_BASELINE_RULES``). Only keys present AND numeric in both compare;
    each regression carries the rule, both values, and the relative
    change so the driver/README can render it without re-deriving."""
    cur = _flatten_artifact(current)
    base = _flatten_artifact(baseline)
    out = []
    for key in sorted(set(cur) & set(base)):
        b, c = base[key], cur[key]
        for rule, match, direction, rel_tol, abs_floor in _BASELINE_RULES:
            if not match(key):
                continue
            bound = max(abs(b) * rel_tol, abs_floor)
            regressed = (
                (b - c) > bound if direction == "higher" else (c - b) > bound
            )
            if regressed:
                out.append(
                    {
                        "key": key,
                        "rule": rule,
                        "direction": direction,
                        "baseline": b,
                        "current": c,
                        "change_pct": round((c - b) / b * 100.0, 2)
                        if b else None,
                        "tolerance": round(bound, 6),
                    }
                )
            break  # first matching rule owns the key
    return out


def apply_baseline_gate(extras: dict, path) -> None:
    """Embed the regression comparison in the artifact (never raises —
    the gate must not cost the run its numbers)."""
    if not path:
        return
    try:
        baseline = load_baseline_artifact(path)
        regressions = compare_baseline(extras, baseline)
        cur_keys = set(_flatten_artifact(extras))
        base_keys = set(_flatten_artifact(baseline))
        extras["baseline_compared"] = {
            "path": str(path),
            "rows_compared": len(cur_keys & base_keys),
            "regression_count": len(regressions),
        }
        extras["regressions"] = regressions
        if regressions:
            log(f"baseline gate vs {path}: {len(regressions)} regression(s)")
            for r in regressions[:20]:
                # change_pct is None when the baseline is 0 — the
                # lost_frames rule's canonical case; render the
                # absolute delta instead of a garbage "None%"
                change = (
                    f"{r['change_pct']}%" if r["change_pct"] is not None
                    else f"{r['current'] - r['baseline']:+g} abs"
                )
                log(
                    f"  REGRESSION [{r['rule']}] {r['key']}: "
                    f"{r['baseline']} -> {r['current']} "
                    f"({change}, tol {r['tolerance']})"
                )
        else:
            log(
                f"baseline gate vs {path}: clean over "
                f"{extras['baseline_compared']['rows_compared']} shared rows"
            )
    except Exception as e:  # noqa: BLE001 — the gate is advisory data
        extras["baseline_error"] = repr(e)
        log(f"baseline gate failed: {e!r}")


class SectionTimeout(BaseException):
    """Async-injected by the watchdog into the main thread when a section
    exceeds its budget. BaseException so library-level ``except
    Exception`` blocks inside the stalled section cannot swallow it;
    ``run_section`` catches it explicitly and moves on."""


# Grace between the soft cancel and the hard os._exit: long enough for a
# backend stall to resolve (observed stalls were 1-3 min), short enough
# that a truly dead backend still exits with the artifact intact.
# When the env var is UNSET, the grace adapts upward with global-budget
# headroom (see Watchdog._run: up to ADAPTIVE_GRACE_CAP_S, keeping
# GLOBAL_EXIT_MARGIN_S to exit cleanly) — waiting is free once the final
# line is emitted, and a backend that recovers wins later sections back. An
# EXPLICIT env value disables the adaptation and is honored exactly, so
# an operator can still force a fast exit on a known-dead backend.
SOFT_CANCEL_GRACE_S = float(os.environ.get("BENCH_SOFT_GRACE_S", "180"))
_GRACE_PINNED = "BENCH_SOFT_GRACE_S" in os.environ
ADAPTIVE_GRACE_CAP_S = 600.0
GLOBAL_EXIT_MARGIN_S = 120.0


class Watchdog:
    """Per-section + global deadline enforcement from a daemon thread.

    Two-stage section enforcement (the r5e lesson: one multi-minute
    backend stall inside ``device_time_ms`` tripped the latency section
    and the old one-stage os._exit forfeited every later section even
    though the stall would have resolved):

    1. section deadline → SOFT cancel: ``PyThreadState_SetAsyncExc``
       raises :class:`SectionTimeout` in the main thread. While the
       thread is blocked inside a C call (the stall itself) the
       exception is deferred by the interpreter and delivers the moment
       the call returns — exactly when a resolved stall hands control
       back — so the section aborts, ``run_section`` records it, and
       every later section still runs.
    2. soft deadline + grace → HARD exit: if the stall never resolves,
       emit the artifact and ``os._exit`` as before.

    The global deadline always hard-exits (it is the last line of
    defense before the driver's own kill).
    """

    def __init__(self):
        self._deadline = None
        self._section = None
        self._soft_fired = False
        self._grace_s = SOFT_CANCEL_GRACE_S
        # serializes enter/leave against the poller's check-and-inject so
        # a cancel can never be aimed at a section that already left (the
        # residual race — injection delivered between fn() returning and
        # leave()'s pending-clear — is a mislabeled cancel, not a lost
        # bench: the section's keys were already written)
        self._lock = threading.Lock()
        self._main_tid = threading.main_thread().ident
        self._global_deadline = time.monotonic() + GLOBAL_BUDGET_S
        threading.Thread(target=self._run, daemon=True).start()

    def _hard_exit(self, which: str):
        log(f"WATCHDOG: {which} — emitting final JSON and exiting")
        _FINAL["watchdog_fired"] = self._section or "global"
        try:
            emit_final()
        finally:
            # os._exit MUST run even if the emit raises — a dead
            # watchdog thread reinstates the hang-until-driver-kill
            # failure mode this class exists to prevent
            os._exit(0)

    def _run(self):
        import ctypes

        while True:
            time.sleep(0.5)
            now = time.monotonic()
            if now > self._global_deadline:
                self._hard_exit("global budget exceeded")
            with self._lock:
                if self._deadline is None or now <= self._deadline:
                    continue
                if self._soft_fired:
                    self._hard_exit(
                        f"section {self._section!r} still stalled "
                        f"{self._grace_s:.0f} s after soft cancel"
                    )
                # stage 1: soft cancel, extend the deadline by the grace.
                # Inside the lock: enter()/leave() cannot swap the
                # section out from under the injection, and the grace
                # extension cannot clobber a freshly entered section's
                # own deadline.
                # Adaptive grace: while the injected SectionTimeout is
                # undelivered the main thread is wedged in a C call (a
                # backend stall mid-compile) and the final JSON is
                # ALREADY the last stdout line — waiting costs nothing,
                # while a backend that recovers wins every later section
                # back (an r5 rehearsal lost vit/moe/quality/jungfrau to
                # a multi-minute outage under the fixed 180 s grace with
                # ~1500 s of global budget still unspent). Ride it out
                # up to the cap, keeping the exit margin before the
                # global deadline. An explicit BENCH_SOFT_GRACE_S is
                # honored exactly (operator wants THAT grace).
                if _GRACE_PINNED:
                    self._grace_s = SOFT_CANCEL_GRACE_S
                else:
                    self._grace_s = max(
                        SOFT_CANCEL_GRACE_S,
                        min(
                            ADAPTIVE_GRACE_CAP_S,
                            (self._global_deadline - now)
                            - GLOBAL_EXIT_MARGIN_S,
                        ),
                    )
                log(
                    f"WATCHDOG: section {self._section!r} exceeded — soft "
                    f"cancel (SectionTimeout into main thread; hard exit in "
                    f"{self._grace_s:.0f} s if the stall never resolves)"
                )
                self._soft_fired = True
                self._deadline = now + self._grace_s
                ctypes.pythonapi.PyThreadState_SetAsyncExc(
                    ctypes.c_long(self._main_tid), ctypes.py_object(SectionTimeout)
                )

    def enter(self, name: str, budget_s: float):
        with self._lock:
            self._section = name
            self._soft_fired = False
            self._deadline = time.monotonic() + budget_s

    def leave(self):
        import ctypes

        with self._lock:
            self._deadline = None
            self._section = None
            if self._soft_fired:
                # an injected-but-undelivered SectionTimeout would land
                # in whatever runs next (the following section, emit) —
                # clear the pending async exception (exc=NULL)
                ctypes.pythonapi.PyThreadState_SetAsyncExc(
                    ctypes.c_long(self._main_tid), None
                )
            self._soft_fired = False

    def remaining_s(self) -> float:
        """Seconds left before THIS section (or the global budget) fires —
        lets multi-compile sections stop sweeping early and finish
        normally instead of tripping the process-killing watchdog."""
        now = time.monotonic()
        limits = [self._global_deadline - now]
        if self._deadline is not None:
            limits.append(self._deadline - now)
        return min(limits)


def _is_backend_unavailable(e: BaseException) -> bool:
    s = repr(e)
    return "UNAVAILABLE" in s or ("backend" in s.lower() and "setup" in s.lower())


def run_section(wd: Watchdog, name: str, fn, budget_s: float = SECTION_BUDGET_S):
    """Run one diagnostic under the watchdog; failures never sink the
    artifact.  Returns True if the backend died (callers skip further
    device sections fast instead of timing out one by one). A section
    that raises fails once and is skipped."""
    wd.enter(name, budget_s)
    backend_dead = False
    try:
        try:
            fn()
            # leave INSIDE the try, immediately after the work: this
            # clears any injected-but-undelivered soft cancel while
            # SectionTimeout is still catchable here, instead of letting
            # it land in emit_final / the next section
            wd.leave()
        except SectionTimeout:
            _note_soft_cancel(name)
        except Exception as e:
            log(f"{name} diagnostic skipped: {e!r}")
            if _is_backend_unavailable(e):
                _FINAL["backend_degraded"] = True
                backend_dead = True
        finally:
            wd.leave()
    except SectionTimeout:
        # the single in-flight cancel delivered INSIDE a handler or the
        # finally above (injected pre-leave, raised mid-unwind) — same
        # treatment, so it cannot escape run_section and abort the bench.
        # The watchdog injects at most once per section (soft_fired), so
        # one outer net is exhaustive.
        _note_soft_cancel(name)
        wd.leave()
    emit_final()
    return backend_dead


def _note_soft_cancel(name: str):
    """Record a watchdog soft cancel and clean up anything the cancelled
    section may have left dangling (an open profiler trace would fail
    every later section's start_trace)."""
    log(
        f"{name} cancelled by watchdog after its budget (stall "
        f"resolved late) — later sections continue"
    )
    prior = _FINAL.get("sections_soft_cancelled", "")
    _FINAL["sections_soft_cancelled"] = f"{prior},{name}" if prior else name
    try:
        import jax as _jax

        _jax.profiler.stop_trace()
    except Exception:
        pass


def _parse_all_device_module_durs(trace_dir: str):
    """EVERY XLA module's sorted per-dispatch durations (ms) on the
    device lanes of a trace, keyed by module name — one entry per
    dispatch. Used directly by measurements that deliberately interleave
    two compiled programs (the detector-switch cost)."""
    pbs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not pbs:
        return None
    from xprof.convert import raw_to_tool_data as r

    data, _ = r.xspace_to_tool_data(pbs, "trace_viewer", {})
    evs = json.loads(data).get("traceEvents", [])
    dev_pids = {
        e["pid"]
        for e in evs
        if e.get("ph") == "M"
        and e.get("name") == "process_name"
        and str(e.get("args", {}).get("name", "")).startswith("/device:")
    }
    mod_lanes = {
        (e["pid"], e["tid"])
        for e in evs
        if e.get("ph") == "M"
        and e.get("name") == "thread_name"
        and e.get("args", {}).get("name") == "XLA Modules"
        and e["pid"] in dev_pids
    }
    by_name = {}
    for e in evs:
        if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in mod_lanes:
            by_name.setdefault(e["name"], []).append(e["dur"] / 1e3)
    return {k: sorted(v) for k, v in by_name.items()} or None


def _parse_device_module_durs(trace_dir: str):
    """Per-execution durations (ms) of the DOMINANT XLA module of a trace
    — tracing K dispatches yields K samples. Aux modules (tiny converts
    etc.) are excluded by keeping the module with the largest total."""
    by_name = _parse_all_device_module_durs(trace_dir)
    if not by_name:
        return None
    return max(by_name.values(), key=sum)


def device_time_ms(jax, fn, warm_args, fresh_args, label: str, extras=None):
    """Device-clock time of one dispatch of ``fn`` (see module docstring).

    ``fresh_args`` may be one args-tuple or a LIST of them: with a list,
    every dispatch (each on distinct content) runs under one trace and
    the MEDIAN per-dispatch module time is returned, with n/min/max
    recorded in ``extras`` — round 2's single-sample timings had no
    variance estimate. Falls back to host wall clock when trace parsing
    is unavailable — and then downgrades ``extras['measurement']`` so the
    emitted JSON never claims device-clock numbers it doesn't have."""
    samples = fresh_args if isinstance(fresh_args, list) else [fresh_args]
    log(f"compiling {label}...")
    jax.block_until_ready(fn(*warm_args))
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    # python tracer OFF — see utils/trace: its host events can flood the
    # converter's cap and silently cost the device-clock number
    from psana_ray_tpu.utils.trace import start_trace_python_tracer_off

    t0 = time.perf_counter()
    try:
        start_trace_python_tracer_off(jax, tmp)
        for args in samples:
            jax.block_until_ready(fn(*args))
    finally:
        jax.profiler.stop_trace()
    wall_ms = (time.perf_counter() - t0) * 1e3 / len(samples)
    try:
        durs = _parse_device_module_durs(tmp)
    except Exception as e:
        log(f"{label}: trace parse failed ({e!r})")
        durs = None
    if not durs:
        log(f"{label}: NO device trace — falling back to wall clock ({wall_ms:.1f} ms)")
        if extras is not None:
            # per-label downgrade, NOT the global 'measurement' key: one
            # failed trace parse must not retroactively brand the already-
            # measured device-clock numbers as wall-clock
            extras.setdefault("wallclock_fallback_labels", []).append(label)
        return wall_ms
    med = float(np.median(durs))
    if extras is not None and len(durs) > 1:
        key = label.replace(" ", "_").replace("+", "_")
        extras[f"device_{key}_ms_n{len(durs)}_min_med_max"] = [
            round(durs[0], 3), round(med, 3), round(durs[-1], 3)
        ]
    return med


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py")
    ap.add_argument(
        "--baseline", default=os.environ.get("BENCH_BASELINE"),
        help="prior artifact (BENCH_rXX.json driver round or "
        "bench_full.json) to compare key rows against; regressions land "
        "in bench_full.json under `regressions` (ISSUE 13)",
    )
    args = ap.parse_args(argv)
    # emit whatever we have if the driver TERMs us before our own watchdog
    # fires (only helps when the main thread is in Python, but free)
    def _on_term(*_):
        try:
            emit_final()
        finally:
            os._exit(0)  # must exit even if the emit raises

    signal.signal(signal.SIGTERM, _on_term)
    wd = Watchdog()

    # _FINAL doubles as the extras dict: every key lands in the artifact
    extras = _FINAL
    extras["measurement"] = "device-clock (jax.profiler trace)"
    extras["key_namespaces"] = (
        "device_* = TPU device-clock (the framework's numbers); host_* = "
        "host-pipeline wall-clock (scales with host_cpu_cores); "
        "e2e_* / fanin_device_fps = host wall-clock with the device in "
        "the loop, bounded by host pipeline and H2D (h2d_mbps_*)"
    )

    # static cleanliness rides the bench trajectory alongside fps: the
    # full lint registry (stdlib-only, <1 s, runs before jax-init so a
    # wedged backend cannot mask it) lands finding counts BY CHECKER in
    # the artifact — zeros mean "ran clean", an absent key means the
    # lint run itself failed (recorded under lint.error)
    try:
        from psana_ray_tpu.lint import run_lint

        _lint = run_lint()
        _counts = _lint.counts_by_checker()
        extras["lint"] = {
            "clean": _lint.ok,
            "findings_total": len(_lint.findings),
            "counts_by_checker": _counts,
            # the ISSUE 10 flow layer called out separately: per-analysis
            # finding counts ride the bench trajectory so a dialogue/
            # lockset/leak regression shows up next to the fps rows
            "flow_analyses": {
                name: _counts.get(name, 0)
                for name in (
                    "protocol-dialogue",
                    "lockset-inference",
                    "resource-flow",
                )
            },
            "files_scanned": _lint.files_scanned,
            "duration_s": round(_lint.duration_s, 3),
        }
        # the ISSUE 18 model checker at FULL profile (the registry entry
        # above only runs the quick profile): state-space size and wall
        # time ride the trajectory, and counterexamples is baseline-gated
        # at ZERO tolerance — one counterexample is a protocol bug
        from psana_ray_tpu.lint.model import run_models

        _mc = run_models("full")
        extras["lint"]["model"] = {
            "states": sum(r.states for r in _mc),
            "transitions": sum(r.transitions for r in _mc),
            "max_depth": max(r.max_depth for r in _mc),
            "counterexamples": sum(1 for r in _mc if r.violation is not None),
            "exhausted_all": all(r.exhausted for r in _mc),
            "duration_s": round(sum(r.duration_s for r in _mc), 3),
        }
    except Exception as e:  # noqa: BLE001 — lint must never kill the bench
        extras["lint"] = {"error": repr(e)}

    from psana_ray_tpu.utils.hostmem import enable_large_alloc_reuse

    enable_large_alloc_reuse()

    wd.enter("jax-init", HEADLINE_BUDGET_S)
    import jax

    # persistent compile cache: JAX_COMPILATION_CACHE_DIR when set, else
    # <checkout>/.jax_cache — only the first run on a machine compiles
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    import jax.numpy as jnp

    from psana_ray_tpu.ops import fused_calibrate
    from psana_ray_tpu.sources import SyntheticSource

    batch_size = 32
    n_pool = 64
    det = "epix10k2M"
    # BENCH_SMOKE=1: tiny geometry so the FULL artifact path (headline ->
    # diagnostics -> repeated emits) can be validated off-TPU in seconds;
    # numbers produced this way are meaningless and flagged as such
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    if smoke:
        det = "smoke_a"
        _FINAL["smoke_mode"] = True

    log(f"backend={jax.default_backend()} devices={jax.devices()}")

    src = SyntheticSource(num_events=n_pool, detector_name=det, seed=0)
    spec = src.spec
    log(f"generating {n_pool} raw {det} frames host-side (one-time cost)...")
    rng = np.random.default_rng(0)
    ped_np, gain_np = src.pedestal(), src.gain_map()

    def fresh_frames(n):
        photons = rng.poisson(0.08, size=(n, *spec.frame_shape)).astype(np.float32)
        noise = rng.normal(0, 2.5, size=(n, *spec.frame_shape)).astype(np.float32)
        return ped_np + spec.adu_gain * gain_np * photons + noise

    pool = list(fresh_frames(n_pool))

    pedestal = jnp.asarray(ped_np)
    gain = jnp.asarray(gain_np)
    mask = jnp.asarray(src.create_bad_pixel_mask())
    calib = jax.jit(
        lambda f: fused_calibrate(f, pedestal, gain, mask, threshold=10.0)
    )

    # ---------------- headline: device-resident fused calibration --------
    # Measured FIRST and emitted IMMEDIATELY — diagnostics below can only
    # add keys to the artifact, never destroy it.  On an UNAVAILABLE
    # backend, retry once, then emit a degraded headline instead of dying.
    def measure_headline():
        x_warm = jax.device_put(np.stack(pool[:batch_size]))
        x_fresh = jax.device_put(np.stack(pool[batch_size : 2 * batch_size]))
        # distinct-content samples WITHOUT extra H2D: device-side rolls of
        # the fresh batch (every timed dispatch sees different data)
        x_list = [x_fresh] + [jnp.roll(x_fresh, k, axis=0) for k in (1, 2)]
        jax.block_until_ready((x_warm, x_list))
        ms = device_time_ms(
            jax, calib, (x_warm,), [(x,) for x in x_list], "fused calibration", extras
        )
        return ms, x_warm, x_list

    x_warm = x_fresh_list = None
    for attempt in (1, 2):
        wd.enter("headline-calibration", HEADLINE_BUDGET_S)
        try:
            ms, x_warm, x_fresh_list = measure_headline()
            calib_fps = batch_size / (ms / 1e3)
            extras["value"] = round(calib_fps, 1)
            extras["vs_baseline"] = round(calib_fps / PER_CHIP_TARGET_FPS, 3)
            extras["device_calib_ms_per_frame"] = round(ms / batch_size, 4)
            log(
                f"fused calibration: {ms:.2f} ms / {batch_size} frames "
                f"device-time -> {calib_fps:.0f} fps, "
                f"{ms/batch_size:.3f} ms/frame"
            )
            break
        except Exception as e:
            log(f"headline attempt {attempt} failed: {e!r}")
            extras["headline_error"] = repr(e)[:300]
            if not _is_backend_unavailable(e):
                # a code bug, not infra: don't blame the backend, and let
                # the independent sections (which compile their own
                # kernels) still try to run
                break
            if attempt == 2:
                extras["backend_degraded"] = True
                break
            time.sleep(5.0)
        finally:
            wd.leave()
    emit_final()

    backend_dead = extras.get("backend_degraded", False)

    # Device-clock configs run FIRST (they are the judged numbers and are
    # fast once compiled); the host-streaming diagnostics — honest
    # wall-clock — go last
    # so a budget overrun there can only cost host-side extras.

    shared = {}  # cross-section compiled artifacts (resnet infer for latency mode)

    # ---------------- config 4: fused Pallas ResNet-50 -------------------
    if not backend_dead and x_warm is not None:
        backend_dead |= run_section(
            wd,
            "resnet50",
            lambda: _bench_resnet(
                jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, batch_size,
                extras, shared,
            ),
        )

    # ---------------- config 3: U-Net segmentation + peak extraction -----
    if not backend_dead and x_warm is not None:
        backend_dead |= run_section(
            wd,
            "unet",
            lambda: _bench_unet(
                jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras,
                shared,
            ),
        )

    # ---------------- SFX: the assembled stream->CXI serving step --------
    if not backend_dead and x_warm is not None:
        backend_dead |= run_section(
            wd,
            "sfx",
            lambda: _bench_sfx(
                jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras,
                shared,
            ),
        )

    # ---------------- latency operating point (B sweep, device clock) ----
    # after the judged throughput configs: 4 fresh batch-shape compiles on
    # a cold cache must not cost them their numbers via a section timeout
    if not backend_dead and x_warm is not None:
        backend_dead |= run_section(
            wd,
            "latency-mode",
            lambda: _bench_latency_mode(jax, x_fresh_list, extras, shared, wd),
        )

    # ---------------- SP consumer: ViT long-sequence classifier ----------
    if not backend_dead and x_warm is not None:
        backend_dead |= run_section(
            wd,
            "vit",
            lambda: _bench_vit(
                jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras,
                shared,
            ),
        )

    # ---------------- classifier quality: train briefly, re-time ---------
    # AFTER the fps sections (graceful degradation: if this dies, the
    # random-export numbers above stand with their recorded source); the
    # judged fps keys are overwritten here with trained-checkpoint timings
    if not backend_dead and x_warm is not None:
        backend_dead |= run_section(
            wd,
            "classifier-quality",
            lambda: _bench_classifier_quality(
                jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras,
                shared, smoke, wd,
            ),
            # the ResNet TRAIN-step compile alone measured ~2-3 min (the
            # serving re-time is a cache hit) and compile latency
            # varies — 420 s left zero
            # margin and two r5 runs lost the whole section to it. The
            # ViT leg self-skips when the remaining budget is short.
            budget_s=600.0,
        )

    # ---------------- EP consumer: MoE-ViT at detector scale -------------
    if not backend_dead and x_warm is not None:
        backend_dead |= run_section(
            wd,
            "moe-vit",
            lambda: _bench_moe_vit(
                jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras,
                wd, smoke,
            ),
            budget_s=480.0,  # fps + trained-accuracy leg (300 MoE steps
            # + the train-step compile); part 2 self-skips when starved
        )

    # ---------------- s2d quality probe + threshold calibration ----------
    # BEFORE jungfrau + the env-bound sections: these are judged
    # device-clock keys (calibrated thresholds, recall/precision), so
    # the ordering IS the priority list (the r5 shakedown lost this
    # section to a slow jungfrau H2D). One section PER MODE —
    # sharing one budget let a cold first mode starve the second to
    # 64/320 steps in the r5 rehearsal; now a mode's overrun
    # soft-cancels only itself. The shipped s2d=2 serving mode runs
    # first so a global-deadline fire costs the auxiliary s4 keys, not
    # the serving mode's.
    if not backend_dead:
        backend_dead |= run_section(
            wd,
            "unet-quality",
            lambda: _bench_unet_quality(
                jax, jnp, extras, smoke, wd, tag="unet", s2d=2, n_steps=160,
            ),
            budget_s=390.0,  # three cold compiles (train/infer/peaks)
            # + 160 steps + eval; measured ~260 s with warm XLA caches
        )
    # entry gate on the GLOBAL budget (between sections remaining_s()
    # is the global deadline): the global overrun is a hard os._exit,
    # not a soft cancel, and the s4 mode's cold compiles can exceed
    # 200 s — entering without room would forfeit the
    # jungfrau/h2d/e2e/fanin sections; skipping loses only s4's keys
    if not backend_dead:
        if wd.remaining_s() < 420.0:
            log(
                f"unet_s4: probe skipped ({wd.remaining_s():.0f} s global "
                f"budget left < 420 s); later sections' keys survive"
            )
            extras["device_unet_s4_probe_skipped"] = True
        else:
            backend_dead |= run_section(
                wd,
                "unet-quality-s4",
                lambda: _bench_unet_quality(
                    jax, jnp, extras, smoke, wd, tag="unet_s4", s2d=4,
                    n_steps=320,
                ),
                budget_s=390.0,
            )

    # ---------------- second detector: jungfrau4M device ceiling ---------
    if not backend_dead:
        backend_dead |= run_section(
            wd,
            "jungfrau-calib",
            lambda: _bench_jungfrau_calib(
                jax, jnp, calib, list(x_fresh_list or []), extras, smoke,
            ),
            budget_s=300.0,
        )

    # ---------------- environment: H2D bandwidth -------------------------
    if not backend_dead:
        backend_dead |= run_section(
            wd,
            "h2d",
            lambda: _bench_h2d(jax, fresh_frames, extras),
            budget_s=120.0,
        )

    # ---------------- config 1+2: e2e streaming over the shm ring --------
    # host-pipeline section: runs even with a degraded device backend only
    # if the headline succeeded (it needs the compiled calib step)
    if not backend_dead:
        backend_dead |= run_section(
            wd,
            "e2e-streaming",
            lambda: _bench_e2e_streaming(jax, calib, pool, batch_size, extras, wd),
        )

    # ---------------- host datapath: copies/allocs per frame -------------
    # device-free accounting of the zero-copy rework (ISSUE 2): TCP
    # relay fps plus measured copies/frame and steady-state allocs/frame
    run_section(
        wd,
        "host-datapath",
        lambda: _bench_host_datapath(extras, smoke),
    )

    # ---------------- wire compression: bandwidth-bound links ------------
    # device-free (ISSUE 9): negotiated codec A/B through a ~50 MB/s
    # token-bucket throttled proxy + per-codec ratio / MB/s + the
    # copies/allocs pins on the compressed path
    run_section(
        wd,
        "wire-compression",
        lambda: _bench_wire_compression(extras, smoke),
    )

    # ---------------- autotune: controller-on vs best hand-tuned ---------
    # device-free (ISSUE 15): three regimes via the existing fault
    # proxies (50 MB/s throttle, raw loopback, bursty arrivals) — the
    # controller rows carry ZERO per-regime flags (codec=auto + live
    # hill climber) and must hold >= 95% fps / <= 105% p99 vs the best
    # per-regime hand flags, with the zero-copy pins intact
    run_section(
        wd,
        "autotune",
        lambda: _bench_autotune(extras, smoke),
    )

    # ---------------- connection scaling: C10K event-loop server ---------
    # device-free: 16/128/1024 streamed subscribers, event-loop vs
    # thread-per-connection A/B (ISSUE 6)
    run_section(
        wd,
        "connection-scaling",
        lambda: _bench_connection_scaling(extras, smoke),
    )

    # ---------------- data plane: workers + kernel pass-through ----------
    # device-free (ISSUE 17): spliced vs materialized drain (server-side
    # py-bytes/frame MUST read ~0 on the spliced leg), --workers 1 vs 2
    # aggregate relay fps with the rendezvous balance proxy, and the
    # kill -9-every-worker row whose `lost` MUST be 0
    run_section(
        wd,
        "data-plane",
        lambda: _bench_data_plane(extras, smoke),
    )

    # ---------------- cluster scaling: sharded queue service -------------
    # device-free: 1/2/4 queue servers, partitioned logical queue,
    # merged streams + kill-one-server failover row (ISSUE 7)
    run_section(
        wd,
        "cluster-scaling",
        lambda: _bench_cluster_scaling(extras, smoke),
    )

    # ---------------- durability: segment-log overhead + kill-restart ----
    # device-free (ISSUE 8): relay fps log-off vs fsync=none vs
    # fsync=batch (the durability tax, measured not guessed) and a
    # kill -9 + restart row whose `lost` MUST be 0 with resume at the
    # committed offset
    run_section(
        wd,
        "durability",
        lambda: _bench_durability(extras, smoke),
    )

    # ---------------- replication: survive the machine -------------------
    # device-free (ISSUE 11): replication-on vs off A/B (the replicated
    # ack floor's measured price) + the kill-coordinator-AND-delete-its-
    # disk row whose `lost` MUST be 0, with the group state surviving
    # the coordinator failover and replay serving the retained range
    run_section(
        wd,
        "replication",
        lambda: _bench_replication(extras, smoke),
    )

    # ---------------- serving: SLO-aware gateway under overload ----------
    # device-free (ISSUE 12): bursty 3-tenant open-loop load at >= 2x
    # the measured sustainable rate — uncontrolled baseline p99 blows
    # the SLO; the gateway keeps admitted-work p99 inside it with
    # goodput >= 80% of B8 capacity and weight-proportional per-tenant
    # shares, plus the idle row serving at the B1 operating point
    run_section(
        wd,
        "serving",
        lambda: _bench_serving(extras, smoke),
    )

    # ---------------- config 5: multi-detector fan-in --------------------
    # two independent sections: the kHz HOST demonstration must not lose
    # its number to a slow device leg timing out (round-3 run:
    # watchdog fired mid-device-leg inside the shared 'fanin' section)
    run_section(
        wd,
        "fanin-host",
        lambda: _bench_fanin_host(extras, smoke),
    )
    if not backend_dead:
        run_section(
            wd,
            "fanin-device",
            lambda: _bench_fanin_device(
                jax, jnp, pool, pedestal, gain, mask, extras, smoke
            ),
        )
    if backend_dead:
        log("backend degraded — remaining device diagnostics skipped fast")

    # ---------------- baseline regression gate (ISSUE 13) ----------------
    # runs LAST so every section's keys participate; purely additive to
    # the artifact (the driver reads `regressions`, the bench never
    # exits non-zero over it)
    apply_baseline_gate(extras, args.baseline)

    emit_final()


def _bench_unet_quality(jax, jnp, extras, smoke=False, wd=None, tag="unet",
                        s2d=2, n_steps=160):
    """VERDICT r3 #5: what does the s2d=4 throughput mode COST? ONE
    PeakNet-TPU operating point (``tag``/``s2d``) trains on synthetic
    frames (labels: calibrated intensity > 50, the documented
    self-supervised recipe of examples/train_peaknet.py), then peak
    recall/precision@3px is scored on held-out events against the
    source's PLANTED peak centers (SyntheticSource.event_with_truth) at
    min_amplitude=100 — plants below the label threshold are unknowable
    to this label policy and are excluded rather than scored as misses.

    Training budget: 320 steps for s2d=4, 160 for s2d=2 (adaptive — see
    the chunked loop; s2d=2 saturates by ~96 steps, so 160 carries 1.6x
    margin). The r4 probe trained 16 steps, and at that budget s2d=4
    looked architecturally precision-limited (best ~0.2-0.6, unstable
    knee — the r4 "triage mode" verdict). A step sweep on v5e
    (PERF_NOTES r5) showed that was an UNDERTRAINING artifact, not a
    resolution ceiling: 16 -> 0.47/0.46, 96 -> 0.90/0.60,
    192 -> 1.00/0.97, 320 -> 1.00/1.00 recall/precision at the knee. At
    those budgets BOTH operating points saturate the oracle, so the
    judged numbers report what the mode trade actually is — equal oracle
    quality, 3.6x throughput at the shipped batch-8 basis (521 vs 146
    fps) — and the per-step count lands in ``device_{tag}_probe_steps``.

    Each mode runs as its OWN watchdog section (the caller makes two
    calls): the r5 full-run rehearsal had both modes sharing one 600 s
    section and the first mode's cold compiles starved the second to
    64/320 steps (0.776/0.594 in the judged keys with nothing wrong but
    the shared budget). Per-mode sections mean one mode's stall
    or compile overrun soft-cancels only itself; the shipped s2d=2 mode
    runs first so the GLOBAL deadline, if it fires, costs the auxiliary
    throughput mode's keys, not the serving mode's."""
    import optax
    from flax.core import meta

    from psana_ray_tpu.models import PeakNetUNetTPU, host_init, panels_to_nhwc
    from psana_ray_tpu.models.losses import masked_sigmoid_focal
    from psana_ray_tpu.models.peaks import (
        find_peaks,
        peak_metrics,
        split_truth_by_panel,
    )
    from psana_ray_tpu.parallel.steps import TrainState, make_train_step
    from psana_ray_tpu.sources import SyntheticSource

    det = "smoke_a" if smoke else "epix10k2M"
    features = (8, 16) if smoke else (64, 128, 256, 512)
    b = 2
    if smoke:
        n_steps = 3
    n_eval = 2 if smoke else 8
    src = SyntheticSource(num_events=1, detector_name=det, seed=5)
    p, h, w = src.spec.frame_shape

    # calibrated-mode frames (photons): quality isolates the NET, the
    # calibration chain has its own sections. Training frames are unique
    # per step but generated chunk-at-a-time (~37 ms/frame host-side,
    # deterministic by index) — materializing all 640 (s4 mode) up front
    # would hold ~5.5 GB of epix10k2M float32 for the whole section;
    # per-chunk generation keeps <300 MB resident at the cost of
    # re-generating for each mode's section (~36 s across both)
    chunk = 16  # steps per generated/gated chunk (one constant: the
    # generator cap and the training loop stride must stay in sync)

    def train_chunk(c0: int):
        return [
            np.stack([src.event(s * b + j)[0] for j in range(b)])
            for s in range(c0, min(c0 + chunk, n_steps))
        ]

    eval_set = [src.event_with_truth(1000 + i) for i in range(n_eval)]

    def loss_fn(logits, aux):
        targets, valid = aux
        # alpha weights the POSITIVE class: at epix10k2M's ~1e-4 peak-pixel
        # fraction the default 0.25 collapses to all-background in the
        # first dozen steps (measured: recall 0.000 after 16); 0.95 has
        # positives winning from step ~10 on
        return masked_sigmoid_focal(logits, targets, valid, alpha=0.95)

    model = PeakNetUNetTPU(features=features, norm="group", s2d=s2d)
    # host_init (CPU-backend init, one device_put) + tiny optimizer-init
    # graph: the device never compiles a model-init program it runs once
    variables = meta.unbox(host_init(model, (b * p, h, w, 1)))
    opt = optax.adam(3e-3)
    opt_state = jax.jit(opt.init)({"params": variables["params"]})
    state = TrainState(variables, opt_state, jnp.zeros((), jnp.int32))
    step = make_train_step(model, opt, loss_fn)

    @jax.jit
    def prepare(frames):
        x = panels_to_nhwc(frames, mode="batch")
        targets = (x > 50.0).astype(jnp.float32)
        return x, targets

    # Eval programs compile BEFORE training (they depend only on tree
    # STRUCTURE, not trained values), so the in-training budget gate
    # only has to reserve eval EXECUTION time (~35 s warm for 8 events
    # x 8 thresholds), not eval compiles: the cold
    # infer+peaks compiles land here, where the section budget is
    # fullest, instead of after the last training chunk where they
    # could blow the reserve and forfeit the mode's judged keys
    # mid-eval. Training steps are what shrink under pressure — by
    # design (a partially-trained probe with its step count recorded
    # beats losing the section).
    infer_logits = jax.jit(lambda v, x: model.apply(v, x))
    peaks_at = jax.jit(
        lambda lg, thr: find_peaks(
            lg, max_peaks=64, threshold=thr, min_distance=2
        )
    )
    warm_x, _ = prepare(jnp.asarray(eval_set[0][0][None]))
    jax.block_until_ready(
        peaks_at(infer_logits(variables, warm_x), jnp.float32(0.5))
    )

    loss = float("nan")
    # Chunked + budget-gated: normally all n_steps run
    # (~35-60 ms/step hot); if the section is running out of watchdog
    # budget (cold compiles ate the margin), stop early
    # with however many steps fit — a partially-trained probe with
    # its step count recorded beats tripping the section deadline at
    # eval time. The 60 s reserve covers eval EXECUTION only (measured
    # ~35 s for 8 events x 8 thresholds) — the eval compiles already
    # happened in the pre-training warmup, and the other mode has its
    # own section, so nothing else draws on this
    # budget). Each chunk SYNCS before the gate checks the
    # clock: train steps dispatch asynchronously, so without the
    # block the host loop would enqueue all n_steps in seconds and
    # the gate would never see device-side slowness — the deferred
    # stall would then trip the watchdog at eval time anyway.
    steps_done = 0
    for chunk0 in range(0, n_steps, chunk):
        if wd is not None and steps_done > 0:
            jax.block_until_ready(loss)
            if wd.remaining_s() < 60.0:
                log(
                    f"{tag}: stopping training at {steps_done}/{n_steps} "
                    f"steps (watchdog budget reserve)"
                )
                break
        for frames in train_chunk(chunk0):
            x, targets = prepare(jnp.asarray(frames))
            state, loss = step(
                state, x, (targets, jnp.ones((b * p,), jnp.uint8))
            )
            steps_done += 1
    jax.block_until_ready(state.variables)
    extras[f"device_{tag}_probe_steps"] = steps_done
    # Threshold calibration (VERDICT r4 weak #2 / do #4): logits are
    # computed ONCE per eval event, then find_peaks sweeps the sigmoid
    # threshold as a TRACED scalar — one compile for the whole curve
    # (both programs compiled in the pre-training warmup above).
    # The r4 run scored only the 0.5 default, which left the s2d=4
    # throughput mode at precision 0.12 — quantified but uncalibrated.
    eval_logits = []
    for data, _, truth in eval_set:
        x, _ = prepare(jnp.asarray(data[None]))
        eval_logits.append((infer_logits(state.variables, x), truth))
    curve = {}
    for thr in (0.3, 0.5, 0.6, 0.7, 0.8, 0.85, 0.9, 0.97):
        agg = {"recall": 0.0, "precision": 0.0}
        for lg, truth in eval_logits:
            yx, _, n = peaks_at(lg, jnp.float32(thr))
            m = peak_metrics(
                np.asarray(yx), np.asarray(n), split_truth_by_panel(truth, p),
                tolerance=3.0, min_amplitude=100.0,
            )
            agg["recall"] += m["recall"] / len(eval_set)
            agg["precision"] += m["precision"] / len(eval_set)
        curve[str(thr)] = [round(agg["recall"], 3), round(agg["precision"], 3)]
    # operating point = F1 knee of the sweep; the full curve rides in
    # bench_full.json for the operator to pick a different trade.
    # A converged checkpoint saturates F1 across a range of tied
    # thresholds — break ties toward 0.5 (sfx.DEFAULT_THRESHOLDS'
    # shipped value) so the reported operating point is the one the
    # CLI actually runs, not whichever tied sweep point sorts first
    def f1(rp):
        r, pr = rp
        return 2 * r * pr / max(r + pr, 1e-9)

    best_f1 = max(f1(v) for v in curve.values())
    best = min(
        (k for k in curve if f1(curve[k]) >= best_f1 - 1e-6),
        key=lambda k: abs(float(k) - 0.5),
    )
    extras[f"device_{tag}_threshold"] = float(best)
    extras[f"device_{tag}_recall"] = curve[best][0]
    extras[f"device_{tag}_precision"] = curve[best][1]
    extras[f"device_{tag}_pr_curve"] = curve
    log(
        f"{tag} quality (s2d={s2d}, {steps_done} steps, final loss "
        f"{loss:.4f}): calibrated thr={best} -> recall@3px "
        f"{curve[best][0]:.3f} precision {curve[best][1]:.3f}; "
        f"curve {curve}"
    )


def _bench_sfx(jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras, shared):
    """The assembled SFX serving step — fused calib + PeakNet-TPU (s2d=2
    serving form) + find_peaks compiled EXACTLY as the psana-ray-tpu-sfx
    CLI compiles it (sfx.SfxPipeline._device_step, its defaults), so the
    judged number is the shipped pipeline's, not a benchmark look-alike."""
    from psana_ray_tpu.models import PeakNetUNetTPU
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline

    class _NullWriter:
        max_peaks = 128

        def append(self, sets):
            pass

    # same tree the unet section exported (identical ctor/shape); only
    # rebuild if that section was skipped — the orbax round trip is not
    # free on this 1-core host
    variables = shared.get("unet_serving")
    if variables is None:
        variables = _serving_params(PeakNetUNetTPU, (1, 64, 64, 1), extras, "sfx")
    b = SfxConfig.batch_size  # judged at the CLI's shipped default
    pipe = SfxPipeline(
        variables, _NullWriter(), calib=(pedestal, gain, mask),
        config=SfxConfig(batch_size=b),
    )
    x_fresh = x_fresh_list[0]
    # the form the loop serves: the batch as b per-frame arrays
    samples = [
        (tuple(x_fresh[k * b:(k + 1) * b]),)
        for k in range(min(3, len(x_fresh) // b))
    ]
    ms = device_time_ms(
        jax, pipe._step, (tuple(x_warm[:b]),), samples, "sfx-step", extras
    )
    extras["device_sfx_pipeline_fps"] = round(b / (ms / 1e3), 1)
    log(
        f"sfx assembled step (calib+PeakNet+peaks, CLI defaults): "
        f"{ms:.1f} ms / {b} frames device-time -> "
        f"{extras['device_sfx_pipeline_fps']:.1f} fps"
    )


def _bench_vit(jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras, shared):
    """SP-consumer workload (VERDICT r3 #4): calib + ViT hit classifier.
    Each epix10k2M frame becomes ONE 8,448-token sequence (every panel
    patchified, models/vit.py) through a flash-attention trunk — the
    single-chip operating point of the model the ('data','seq') mesh
    serves via ulysses in dryrun_multichip. head_dim=128 so the Pallas
    flash kernel's shape constraints hold on real geometry."""
    from psana_ray_tpu.models import ViTHitClassifier, host_init
    from psana_ray_tpu.ops import fused_calibrate

    b_vit = 2
    model = ViTHitClassifier(num_classes=2)
    variables = host_init(model, (1, *x_warm.shape[1:]))

    @jax.jit
    def infer2(v, frames):
        c = fused_calibrate(
            frames, pedestal, gain, mask, threshold=10.0, out_dtype=jnp.bfloat16
        )
        return jnp.argmax(model.apply(v, c), -1)

    # weights are a traced arg: the classifier-quality section re-measures
    # on TRAINED params through this same compiled program
    shared["vit_infer"] = infer2
    shared["vit_variables"] = variables
    infer = lambda f: infer2(variables, f)  # noqa: E731

    x = x_fresh_list[0]
    samples = [(x[k * b_vit:(k + 1) * b_vit],) for k in range(min(3, len(x) // b_vit))]
    ms = device_time_ms(jax, infer, (x_warm[:b_vit],), samples, "calib+ViT", extras)
    fps = b_vit / (ms / 1e3)
    extras["device_vit_fps"] = round(fps, 1)
    extras["device_vit_tokens_per_frame"] = (
        x_warm.shape[1]
        * (x_warm.shape[2] // model.patch)
        * (x_warm.shape[3] // model.patch)
    )
    log(
        f"calib+ViT (one {extras['device_vit_tokens_per_frame']}-token "
        f"sequence/frame, flash trunk): {ms:.1f} ms / {b_vit} frames "
        f"device-time -> {fps:.1f} fps"
    )


def _raw_hit_batch(src, start: int, n: int):
    """``n`` RAW frames + hit/miss labels from a ``hit_fraction`` corpus
    (label := any planted truth rows) — the shared recipe of the
    classifier-quality and MoE accuracy legs, so the two cannot drift."""
    from psana_ray_tpu.config import RetrievalMode

    frames, labels = [], []
    for i in range(start, start + n):
        data, _, truth = src.event_with_truth(i, RetrievalMode.RAW)
        frames.append(data)
        labels.append(1 if len(truth) else 0)
    return np.stack(frames), np.asarray(labels, np.int32)


def _train_hit_classifier(
    jax, jnp, model, init_variables, calibrate, raw_batches, steps, tag,
    aux_loss_weight=0.0,
):
    """ONE copy of the transformer-classifier training recipe so the
    dense-ViT and MoE-ViT accuracy numbers stay comparable by
    construction: warmup-cosine AdamW (a from-scratch ViT stalls at the
    majority class without the warmup — PERF_NOTES r5), xent loss,
    4-frame chunks pre-calibrated and device-resident so the steps run
    at device speed rather than H2D speed. ``aux_loss_weight>0``
    adds the sown MoE router load-balance loss (the EP training path).
    Returns the trained variables, unboxed."""
    import optax
    from flax.core import meta

    from psana_ray_tpu.models.losses import masked_softmax_xent
    from psana_ray_tpu.parallel.steps import TrainState, make_train_step

    sched = optax.warmup_cosine_decay_schedule(0.0, 6e-4, 20, steps, 1e-5)
    opt = optax.adamw(sched, weight_decay=0.01)
    tv = meta.unbox(init_variables)
    opt_state = jax.jit(opt.init)({"params": tv["params"]})
    state = TrainState(tv, opt_state, jnp.zeros((), jnp.int32))
    step = make_train_step(
        model, opt,
        lambda lg, aux: masked_softmax_xent(lg, aux[0], aux[1]),
        aux_loss_weight=aux_loss_weight,
    )
    dev = []
    for frames, labels in raw_batches:
        for h in range(0, len(labels), 4):
            dev.append(
                (calibrate(jnp.asarray(frames[h:h + 4])),
                 jnp.asarray(labels[h:h + 4]))
            )
    ones4 = jnp.ones((4,), jnp.uint8)
    loss = float("nan")
    for s in range(steps):
        x, lb = dev[s % len(dev)]
        state, loss = step(state, x, (lb, ones4))
    log(
        f"{tag}: trained {steps} warmup-cosine steps "
        f"(final loss {float(loss):.4f})"
    )
    return meta.unbox(state.variables)


def _bench_classifier_quality(
    jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras, shared,
    smoke=False, wd=None,
):
    """VERDICT r4 missing #2: evidence the classifiers CLASSIFY. Both the
    ResNet-50 flagship and the ViT train briefly on-device on the labeled
    hit-finding corpus (SyntheticSource(hit_fraction=0.5): 'hit' = Bragg
    peaks planted, 'miss' = background only — label from the planted
    truth), are exported through the supported train→serve path
    (export_serving_params / save_params + load_params), scored on
    held-out RAW events THROUGH THE SAME compiled calib+model serving
    program the fps sections measure, and that program is then re-timed
    on the trained checkpoints so the judged fps and the accuracy describe
    the same weights. A quality probe (10-16 steps), not a converged-
    training claim — the task (blank vs diffraction) is the reference's
    actual hit-finding deployment shape."""
    import shutil

    import optax
    from flax.core import meta

    from psana_ray_tpu.checkpoint import load_params, save_params
    from psana_ray_tpu.models import (
        ResNet50,
        ViTHitClassifier,
        export_serving_params,
        host_init,
        panels_to_nhwc,
    )
    from psana_ray_tpu.models.losses import masked_softmax_xent
    from psana_ray_tpu.ops import fused_calibrate
    from psana_ray_tpu.parallel.steps import TrainState, make_train_step
    from psana_ray_tpu.sources import SyntheticSource

    det = "smoke_a" if smoke else "epix10k2M"
    n_steps, b, n_eval = (2, 2, 4) if smoke else (10, 8, 16)
    src = SyntheticSource(
        num_events=1, detector_name=det, seed=7, hit_fraction=0.5
    )

    def raw_batch(start, n):
        return _raw_hit_batch(src, start, n)

    calibrate = jax.jit(
        lambda f: fused_calibrate(
            f, pedestal, gain, mask, threshold=10.0, out_dtype=jnp.bfloat16
        )
    )
    train_batches = [raw_batch(s * b, b) for s in range(n_steps)]
    eval_frames, eval_labels = raw_batch(5000, n_eval)
    if len(set(eval_labels.tolist())) < 2:
        log("classifier probe: degenerate eval label split — widen n_eval")

    def loss_fn(logits, aux):
        labels, valid = aux
        return masked_softmax_xent(logits, labels, valid)

    def train(model, sample_of, tag):
        variables = meta.unbox(host_init(model, sample_of(train_batches[0][0][:1]).shape))
        opt = optax.adam(1e-3)
        opt_state = jax.jit(opt.init)({"params": variables["params"]})
        state = TrainState(variables, opt_state, jnp.zeros((), jnp.int32))
        step = make_train_step(model, opt, loss_fn)
        loss = float("nan")
        for frames, labels in train_batches:
            x = sample_of(jnp.asarray(frames))
            state, loss = step(
                state, x, (jnp.asarray(labels), jnp.ones((len(labels),), jnp.uint8))
            )
        log(f"{tag}: trained {n_steps} steps (final loss {float(loss):.4f})")
        return state

    def accuracy_and_fps(infer2, variables, tag, b_fps, eval_chunk=None):
        # load_params hands back host numpy; place it once so the eval +
        # re-time dispatches don't re-upload the tree every call
        variables = jax.device_put(variables)
        ec = eval_chunk or b
        pred = []
        for s in range(0, n_eval, ec):
            pred.append(np.asarray(infer2(variables, jnp.asarray(eval_frames[s:s + ec]))))
        acc = float((np.concatenate(pred) == eval_labels).mean())
        extras[f"device_{tag}_accuracy"] = round(acc, 3)
        # re-time the SAME compiled serving program on the trained params
        # so the judged fps runs on the trained checkpoint
        x = x_fresh_list[0]
        samples = [(x[k * b_fps:(k + 1) * b_fps],) for k in range(min(3, len(x) // b_fps))]
        ms = device_time_ms(
            jax, lambda f: infer2(variables, f), (x_warm[:b_fps],), samples,
            f"{tag}-trained", extras,
        )
        extras[f"device_{tag}_fps"] = round(b_fps / (ms / 1e3), 1)
        log(f"{tag} TRAINED checkpoint: accuracy {acc:.3f} on {n_eval} held-out "
            f"events, {extras[f'device_{tag}_fps']:.1f} fps (re-timed)")

    # ---- ResNet-50 (the flagship, BASELINE config 4) --------------------
    if shared.get("resnet_infer") is not None and not smoke:
        model = ResNet50(num_classes=2, norm="batch")
        state = train(
            model, lambda f: panels_to_nhwc(calibrate(f)), "resnet50",
        )
        path = tempfile.mkdtemp(prefix="bench_trained_resnet_")
        shutil.rmtree(path)
        export_serving_params(state.variables, path)  # fold + save
        trained = load_params(path)
        shutil.rmtree(path, ignore_errors=True)
        accuracy_and_fps(shared["resnet_infer"], trained, "resnet50", len(x_warm))
        extras.setdefault("serving_params_source", {})["resnet50"] = (
            f"TRAINED {n_steps} steps on hit/miss corpus -> fold_batchnorm "
            f"-> save_params -> load_params"
        )
    elif not smoke:
        log("classifier probe: resnet skipped (fps section did not run)")

    # ---- ViT (LayerNorm: trained tree serves directly) ------------------
    # A from-scratch ViT is a slow starter (PERF_NOTES r5: 10-60 steps at
    # any lr / head stays at majority class; ~100-300 warmup-cosine steps
    # reach ~0.94): the SAME 80 frames re-chunked to b=4 are pre-placed on
    # device ONCE so the 300 steps run at device speed (~80 s), not H2D
    # speed. The conv net above needs no such treatment — worth recording.
    if shared.get("vit_infer") is not None and not smoke:
        # entering the ViT leg costs its train-step compile + 300 steps +
        # the trained re-time; with less than ~240 s left that guarantees
        # a mid-leg section deadline (os._exit forfeits every later
        # section) — skip and keep the ResNet keys just recorded
        if wd is not None and wd.remaining_s() < 240.0:
            log(
                f"vit accuracy: skipped ({wd.remaining_s():.0f} s left "
                f"< 240 s reserve); fps-section number stands"
            )
            extras["device_vit_probe_skipped"] = True
            return
        model = ViTHitClassifier(num_classes=2)
        vit_steps = 300
        trained_vars = _train_hit_classifier(
            jax, jnp, model,
            host_init(model, (1, *train_batches[0][0].shape[1:])),
            calibrate, train_batches, vit_steps, "vit",
        )
        path = tempfile.mkdtemp(prefix="bench_trained_vit_")
        shutil.rmtree(path)
        save_params(path, trained_vars)
        trained = load_params(path)
        shutil.rmtree(path, ignore_errors=True)
        accuracy_and_fps(shared["vit_infer"], trained, "vit", 2, eval_chunk=2)
        extras.setdefault("serving_params_source", {})["vit"] = (
            f"TRAINED {vit_steps} steps on hit/miss corpus -> save_params "
            f"-> load_params"
        )
    elif not smoke:
        log("classifier probe: vit skipped (fps section did not run)")
    if smoke:
        # smoke validates the corpus plumbing only (1-core host): labels
        # derive from planted truth and split both ways
        labels = [raw_batch(0, 8)[1]]
        extras["smoke_classifier_labels"] = [int(x) for x in labels[0]]


def _bench_moe_vit(
    jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras, wd=None,
    smoke=False,
):
    """EP consumer at detector scale (VERDICT r4 do #5): the 8,448-token
    ViT with every block's MLP a 4-expert switch MoE. Servable on one
    chip only because of grouped dispatch (parallel/moe.py): the
    monolithic [B, T, E, C] dispatch at this shape is ~1.1 GB f32 PER
    LAYER; grouped (auto G=384) it is ~26 MB.

    Two parts, fps first so a budget-starved run still records the EP
    throughput story: (1) the compiled calib+MoE-ViT serving step timed
    on random weights (throughput does not depend on values; the router
    still routes); (2) the accuracy story — the MoE-ViT trains on the
    same labeled hit/miss corpus as the dense classifiers (classifier-
    quality section), with the router's load-balance aux loss active
    (make_train_step(aux_loss_weight=0.01), the supported EP training
    path), round-trips through save_params/load_params, and the serving
    step is re-timed on the trained checkpoint — so, like ResNet-50 and
    the dense ViT, the judged fps and accuracy describe the same
    weights."""
    import shutil

    from psana_ray_tpu.checkpoint import load_params, save_params
    from psana_ray_tpu.models import ViTHitClassifier, host_init
    from psana_ray_tpu.ops import fused_calibrate
    from psana_ray_tpu.sources import SyntheticSource

    b = 2
    # Training uses the Switch-default capacity factor 2.0 (slack for an
    # unbalanced early router); SERVING runs cf=1.25. Expert capacity is
    # a trace-time constant — the trained tree is capacity-independent —
    # and the expert einsums' rows scale with cf, so lower serving
    # capacity is pure fps: measured on v5e-1, cf 2.0/1.25/1.0 ->
    # 124.6/136.3/140.6 fps (dense ViT: 143.4) with accuracy 1.000 at
    # ALL THREE on the cf=2.0-trained aux-loss-balanced checkpoint.
    # 1.25 is shipped (the Switch paper's serving-side choice): 1.25x
    # capacity slack over perfect balance, within 5% of dense fps.
    serve_cf = 1.25
    model = ViTHitClassifier(num_classes=2, moe_experts=4)
    serve_model = model.clone(moe_capacity_factor=serve_cf)
    variables = host_init(model, (1, *x_warm.shape[1:]))
    extras["device_moe_vit_serving_capacity_factor"] = serve_cf

    calibrate = jax.jit(
        lambda f: fused_calibrate(
            f, pedestal, gain, mask, threshold=10.0, out_dtype=jnp.bfloat16
        )
    )

    @jax.jit
    def infer2(v, frames):
        return jnp.argmax(serve_model.apply(v, calibrate(frames)), -1)

    x = x_fresh_list[0]
    samples = [(x[k * b:(k + 1) * b],) for k in range(min(3, len(x) // b))]
    ms = device_time_ms(
        jax, lambda f: infer2(variables, f), (x_warm[:b],), samples,
        "calib+MoE-ViT", extras,
    )
    extras["device_moe_vit_fps"] = round(b / (ms / 1e3), 1)
    log(
        f"calib+MoE-ViT (4-expert switch MLPs, grouped dispatch, serving "
        f"cf={serve_cf}): {ms:.1f} ms / {b} frames device-time -> "
        f"{extras['device_moe_vit_fps']:.1f} fps"
    )

    # ---- part 2: train with the router aux loss, score, re-time ---------
    # The MoE train-step compile is the expensive unknown;
    # entering with less than ~300 s (more reserve than the
    # dense ViT's 240 s — this leg does strictly more: MoE compile,
    # save/load round trip, re-time) guarantees tripping the section
    # deadline mid-compile, so skip and keep the fps number.
    # Smoke validates the fps plumbing only: the corpus below is real
    # epix10k2M and the 300-step detector-scale MoE train does not
    # belong on the 1-core CPU host.
    if smoke:
        return
    if wd is not None and wd.remaining_s() < 300.0:
        log(
            f"moe_vit accuracy: skipped ({wd.remaining_s():.0f} s left "
            f"< 300 s compile reserve); random-weight fps stands"
        )
        extras["device_moe_vit_probe_skipped"] = True
        return
    src = SyntheticSource(
        num_events=1, detector_name="epix10k2M", seed=7, hit_fraction=0.5
    )

    def raw_batch(start, n):
        return _raw_hit_batch(src, start, n)

    n_eval, moe_steps = 16, 300
    trained_vars = _train_hit_classifier(
        jax, jnp, model,
        variables,  # part 1's init IS this leg's init tree
        calibrate, [raw_batch(s * 8, 8) for s in range(10)], moe_steps,
        "moe_vit (router aux loss on)", aux_loss_weight=0.01,
    )
    path = tempfile.mkdtemp(prefix="bench_trained_moe_")
    shutil.rmtree(path)
    save_params(path, trained_vars)
    # device_put once: load_params returns host numpy, and passing that
    # to jit re-uploads the detector-scale tree on EVERY
    # eval/re-time dispatch
    trained = jax.device_put(load_params(path))
    shutil.rmtree(path, ignore_errors=True)
    eval_frames, eval_labels = raw_batch(5000, n_eval)
    pred = []
    for s in range(0, n_eval, b):
        pred.append(np.asarray(infer2(trained, jnp.asarray(eval_frames[s:s + b]))))
    acc = float((np.concatenate(pred) == eval_labels).mean())
    extras["device_moe_vit_accuracy"] = round(acc, 3)
    ms = device_time_ms(
        jax, lambda f: infer2(trained, f), (x_warm[:b],), samples,
        "moe-vit-trained", extras,
    )
    extras["device_moe_vit_fps"] = round(b / (ms / 1e3), 1)
    extras.setdefault("serving_params_source", {})["moe_vit"] = (
        f"TRAINED {moe_steps} steps (aux_loss_weight=0.01) on hit/miss "
        f"corpus -> save_params -> load_params"
    )
    log(f"moe_vit TRAINED checkpoint: accuracy {acc:.3f} on {n_eval} "
        f"held-out events, {extras['device_moe_vit_fps']:.1f} fps (re-timed)")


def _bench_jungfrau_calib(jax, jnp, epix_calib, epix_x_list, extras, smoke=False):
    """Config 5's second detector gets a FRAMEWORK-ceiling number
    (VERDICT r4 do #8): device-clock fused calibration for the
    jungfrau4M geometry (the r4 record had only the wall-clock
    fan-in device leg), plus the per-detector compiled-step
    SWITCH cost — the fan-in consumer's steady state alternates two
    compiled programs, and this measures whether that alternation costs
    device time vs running each solo (both programs stay HBM-resident,
    so the expected answer, now recorded instead of assumed, is ~0)."""
    from psana_ray_tpu.ops import fused_calibrate
    from psana_ray_tpu.sources import SyntheticSource

    det = "smoke_b" if smoke else "jungfrau4M"
    # b=4: the two fresh arrays are 67 MB each — keeps the H2D share of
    # the section small
    b = 4
    src = SyntheticSource(num_events=8, detector_name=det, seed=11)
    spec = src.spec
    rng = np.random.default_rng(11)
    ped_np, gain_np = src.pedestal(), src.gain_map()

    def fresh(n):
        photons = rng.poisson(0.08, size=(n, *spec.frame_shape)).astype(np.float32)
        return ped_np + spec.adu_gain * gain_np * photons

    pedj, gainj, maskj = (
        jnp.asarray(ped_np), jnp.asarray(gain_np),
        jnp.asarray(src.create_bad_pixel_mask()),
    )

    def jungfrau_calib(f):  # named def: distinct XLA module name for the
        return fused_calibrate(f, pedj, gainj, maskj, threshold=10.0)  # switch trace

    jf_calib = jax.jit(jungfrau_calib)
    x_warm = jax.device_put(fresh(b))
    x = jax.device_put(fresh(b))
    xs = [x] + [jnp.roll(x, k, axis=0) for k in (1, 2)]
    jax.block_until_ready((x_warm, xs))
    ms = device_time_ms(
        jax, jf_calib, (x_warm,), [(a,) for a in xs], "jungfrau calib", extras
    )
    extras["device_calib_jungfrau4M_fps"] = round(b / (ms / 1e3), 1)
    extras["device_calib_jungfrau4M_ms_per_frame"] = round(ms / b, 4)
    log(
        f"jungfrau4M fused calibration: {ms:.2f} ms / {b} frames "
        f"device-time -> {extras['device_calib_jungfrau4M_fps']:.0f} fps"
    )

    # switch cost: alternate the two compiled programs under one trace and
    # compare the jungfrau module's per-dispatch median to its solo median
    if epix_calib is None or not epix_x_list:
        return
    from psana_ray_tpu.utils.trace import start_trace_python_tracer_off

    tmp = tempfile.mkdtemp(prefix="bench_switch_")
    try:
        start_trace_python_tracer_off(jax, tmp)
        for k in range(3):
            jax.block_until_ready(epix_calib(epix_x_list[k % len(epix_x_list)]))
            jax.block_until_ready(jf_calib(xs[k % len(xs)]))
    finally:
        jax.profiler.stop_trace()
    try:
        by_name = _parse_all_device_module_durs(tmp)
    except Exception as e:
        log(f"switch-cost trace parse failed: {e!r}")
        return
    if not by_name:
        return
    jf_mods = [k for k in by_name if "jungfrau" in k.lower()]
    if jf_mods:
        inter_med = float(np.median(by_name[jf_mods[0]]))
        overhead = inter_med - ms
        extras["device_calib_switch_overhead_ms"] = round(overhead, 3)
        log(
            f"detector-switch cost: jungfrau dispatch {inter_med:.2f} ms "
            f"interleaved vs {ms:.2f} ms solo -> {overhead:+.3f} ms"
        )
    else:
        log(f"switch-cost: no jungfrau module in trace ({list(by_name)})")


def _bench_h2d(jax, fresh_frames, extras):
    """Measure the host->device transfer bandwidth as its OWN metric
    (round-3 VERDICT weak #2): the e2e_* streaming numbers cannot beat
    this path, so recording it lets a reader normalize them — e.g.
    e2e_fps <= h2d_mbps / frame_mb when transfer-bound. Distinct content
    per put."""
    nbytes = 0
    for tag in ("cold", "warm"):
        x = fresh_frames(4).astype(np.uint16)
        nbytes = x.nbytes
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(x))
        dt = time.perf_counter() - t0
        extras[f"h2d_mbps_{tag}"] = round(nbytes / dt / 1e6, 1)
        log(f"H2D ({tag}): {nbytes/1e6:.1f} MB in {dt*1e3:.0f} ms -> "
            f"{nbytes/dt/1e6:.1f} MB/s")
    extras["h2d_sample_mb"] = round(nbytes / 1e6, 1)


def _bench_e2e_streaming(jax, calib, pool, batch_size, extras, wd=None):
    """Configs 1-2: producer -> transport -> batcher -> prefetch -> device
    calib, over the shm ring when the native lib builds here (else the
    in-process ring). Records passthrough fps (no device work) and the
    consumer pipeline's p50/p99 step latency."""
    from psana_ray_tpu.infeed import InfeedPipeline
    from psana_ray_tpu.infeed.batcher import batches_from_queue
    from psana_ray_tpu.obs.stages import HOP_ENQ, HOP_SRC
    from psana_ray_tpu.records import EndOfStream, FrameRecord, mark_hop

    try:
        from psana_ray_tpu.transport.shm_ring import ShmRingBuffer, native_available

        use_shm = native_available()
    except Exception:
        use_shm = False

    def make_queue():
        if use_shm:
            return ShmRingBuffer.create(f"bench_{int(time.time()*1e3)}", maxsize=24)
        from psana_ray_tpu.transport import RingBuffer

        return RingBuffer(maxsize=24)

    transport = "shm" if use_shm else "ring"
    n_frames = 64
    # detector-native uint16 ADUs: half the transport + host->device bytes
    # of f32 (real epix/jungfrau raw streams are u16); calib upcasts on
    # device
    pool16 = [np.clip(f, 0, 65535).astype(np.uint16) for f in pool]

    def produce(queue, n=n_frames):
        for i in range(n):
            rec = FrameRecord(0, i, pool16[i % len(pool16)], 9.5)
            # hop stamps ride the in-process ring by reference, so the e2e
            # run below decomposes into named stages (obs.stages); over shm
            # the encode drops them (observability never goes on the wire).
            # enq is stamped BEFORE each put attempt (re-stamped on retry),
            # matching producer._Sender: the consumer thread can pop the
            # record the instant put returns, and a late enq stamp would
            # make queue_dwell = deq - enq negative
            mark_hop(rec, HOP_SRC)
            mark_hop(rec, HOP_ENQ)
            while not queue.put(rec):
                time.sleep(0.0005)
                mark_hop(rec, HOP_ENQ)
        # not inside assert: python -O must not strip the EOS delivery
        if not queue.put_wait(EndOfStream(total_events=n), timeout=300.0):
            raise RuntimeError("EOS delivery timed out")

    # config 1: raw passthrough, host-only (no device transfer/compute).
    # Best of 3 trials: a shared host has transient stalls — a
    # single-trial judged key would record the stall, not the framework
    trials = []
    for _ in range(3):
        # a stalled-host trial can eat ~20 s (measured); keep enough
        # section budget for config 2's streaming run + compile below
        if trials and wd is not None and wd.remaining_s() < 150.0:
            break
        q1 = make_queue()
        t_prod = threading.Thread(target=produce, args=(q1,), daemon=True)
        t0 = time.perf_counter()
        t_prod.start()
        n_seen = 0
        for batch in batches_from_queue(q1, batch_size, poll_interval_s=0.001):
            n_seen += batch.num_valid
        trials.append(n_seen / (time.perf_counter() - t0))
        t_prod.join()
        if use_shm:
            q1.destroy()
    passthrough_fps = max(trials)
    log(
        f"passthrough [{transport}] u16 producer->queue->batcher: "
        f"{passthrough_fps:.0f} fps (best of {[round(t) for t in trials]})"
    )
    extras["host_passthrough_fps"] = round(passthrough_fps, 1)

    # config 2: same stream, consumer runs the fused calibration on-device.
    # Warmup pass first (own queue, one batch): the timed run must not
    # charge XLA compilation to its first batch — with only 2 batches that
    # made p50 a compile measurement, not a latency one
    qw = make_queue()
    # threaded: the ring holds fewer slots than a batch, so a synchronous
    # fill would deadlock against the not-yet-started consumer
    tw = threading.Thread(target=produce, args=(qw, batch_size), daemon=True)
    tw.start()
    InfeedPipeline(qw, batch_size=batch_size, poll_interval_s=0.001).run(
        lambda b: calib(b.frames), block_until_ready=True
    )
    tw.join()
    if use_shm:
        qw.destroy()

    q2 = make_queue()
    t_prod = threading.Thread(target=produce, args=(q2,), daemon=True)
    pipe = InfeedPipeline(q2, batch_size=batch_size, prefetch_depth=2, poll_interval_s=0.001)
    t0 = time.perf_counter()
    t_prod.start()
    n_seen = pipe.run(lambda b: calib(b.frames), block_until_ready=True)
    e2e_fps = n_seen / (time.perf_counter() - t0)
    t_prod.join()
    if use_shm:
        q2.destroy()
    lat = pipe.metrics.step_latency.summary_ms()
    log(
        f"e2e streaming [{transport}] (transport+batcher+prefetch+calib): "
        f"{e2e_fps:.0f} fps wall-clock"
    )
    # e2e_*: host wall clock with the device in the loop — bounded by
    # the slower of the host pipeline and H2D (h2d_mbps_*), not by the
    # device ceiling the device_* keys record
    extras["e2e_fps"] = round(e2e_fps, 1)
    extras["e2e_p50_frame_ms"] = round(lat["p50_ms"] / batch_size, 3)
    extras["e2e_p50_batch_ms"] = round(lat["p50_ms"], 2)
    extras["e2e_p99_batch_ms"] = round(lat["p99_ms"], 2)
    # stage-level decomposition into the bench artifact: register the
    # run's metrics and emit the registry snapshot, so every future
    # BENCH_* round carries per-stage latency (enqueue, queue_dwell,
    # dequeue, batch, device_put, dispatch) alongside the headline fps
    from psana_ray_tpu.obs import MetricsRegistry

    reg = MetricsRegistry.default()
    reg.register("bench.e2e", pipe.metrics)
    stage_pipe = pipe
    if use_shm:
        # config 2b: hop stamps are process-local and do not cross the shm
        # encode, so the timed run above has no stage data here — repeat
        # the stream over the in-process ring (same geometry, compiled
        # calib, untimed: only its DECOMPOSITION is recorded)
        from psana_ray_tpu.transport import RingBuffer

        q3 = RingBuffer(maxsize=24)
        t_prod = threading.Thread(target=produce, args=(q3,), daemon=True)
        stage_pipe = InfeedPipeline(
            q3, batch_size=batch_size, prefetch_depth=2, poll_interval_s=0.001
        )
        t_prod.start()
        stage_pipe.run(lambda b: calib(b.frames), block_until_ready=True)
        t_prod.join()
        reg.register("bench.e2e_stages", stage_pipe.metrics)
    extras["obs_registry_snapshot"] = reg.snapshot()
    stage_means = {
        name: st.get("mean_ms")
        for name, st in stage_pipe.metrics.stages.snapshot().items()
    }
    if stage_means:
        log(f"e2e stage decomposition (mean ms/record): {stage_means}")
    log(
        f"e2e [{transport}] step latency: p50={lat['p50_ms']:.1f}ms "
        f"p99={lat['p99_ms']:.1f}ms per {batch_size}-frame batch "
        f"({lat['p50_ms']/batch_size:.3f} ms/frame p50 amortized)"
    )
    return transport, e2e_fps


def _serving_params(model_ctor, sample_shape, extras, tag):
    """Serving params via the SUPPORTED export path (models/fold.py): a
    norm='batch' parameter form (host-built; weights random — throughput
    does not depend on values) folded into FrozenAffine constants, saved
    with checkpoint.save_params and loaded back — the exact train→serve
    route examples/train_peaknet.py --export-serving produces, exercised
    end to end so the judged numbers run on a checkpoint-consumable form."""
    import shutil

    from psana_ray_tpu.checkpoint import load_params
    from psana_ray_tpu.models import export_serving_params
    from psana_ray_tpu.models.init import eval_shape_init

    train_form = eval_shape_init(model_ctor(norm="batch"), sample_shape)
    path = tempfile.mkdtemp(prefix=f"bench_serving_{tag}_")
    shutil.rmtree(path)  # orbax wants to create the leaf dir itself
    export_serving_params(train_form, path)  # the SAME code path as --export-serving
    loaded = load_params(path)
    shutil.rmtree(path, ignore_errors=True)
    extras.setdefault("serving_params_source", {})[tag] = (
        "fold_batchnorm(norm='batch' form) -> save_params -> load_params"
    )
    return loaded


def _make_resnet_infer(jax, jnp, pedestal, gain, mask):
    """jitted ``(variables, frames) -> class`` — weights are a TRACED
    argument, so swapping random-export params for the trained checkpoint
    (classifier-quality section) reuses the same compiled program."""
    from psana_ray_tpu.models import panels_to_nhwc
    from psana_ray_tpu.models.pallas_resnet import resnet_fused_infer
    from psana_ray_tpu.ops import fused_calibrate

    @jax.jit
    def infer(variables, frames):
        # bf16 calibration output feeds the bf16 model directly — no
        # 277 MB convert pass, and the calib store is half-width
        c = fused_calibrate(
            frames, pedestal, gain, mask, threshold=10.0, out_dtype=jnp.bfloat16
        )
        logits = resnet_fused_infer(variables, panels_to_nhwc(c))
        return jnp.argmax(logits, -1)

    return infer


def _bench_resnet(jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, batch_size, extras, shared):
    """Config 4: calib + fused-Pallas ResNet-50 hit/miss classifier,
    device-resident (models/pallas_resnet.py collapses each bottleneck
    block to one pallas_call; the 120 Hz config-4 stream needs >=120)."""
    from functools import partial

    from psana_ray_tpu.models import ResNet50

    # serving params come from the export path, NOT a frozen-form random
    # init — the judged numbers must run on the parameter form the
    # train→serve workflow actually produces (round-3 VERDICT missing #1)
    variables = _serving_params(
        partial(ResNet50, num_classes=2), (1, 64, 64, x_warm.shape[1]),
        extras, "resnet50",
    )

    infer2 = _make_resnet_infer(jax, jnp, pedestal, gain, mask)
    infer = lambda f: infer2(variables, f)  # noqa: E731
    # reused by the latency-mode + classifier-quality sections (the
    # latter swaps in TRAINED params without recompiling)
    shared["resnet_infer"] = infer2
    shared["resnet_variables"] = variables

    ms = device_time_ms(
        jax, infer, (x_warm,), [(x,) for x in x_fresh_list], "calib+ResNet-50", extras
    )
    fps = batch_size / (ms / 1e3)
    extras["device_resnet50_fps"] = round(fps, 1)
    log(
        f"calib+ResNet-50 (fused Pallas blocks): {ms:.1f} ms / {batch_size} "
        f"device-time -> {fps:.0f} fps"
    )


def _bench_latency_mode(jax, x_fresh_list, extras, shared, wd):
    """BASELINE's second target: p50 per-frame latency < 5 ms. The
    throughput sections dispatch B=32; here the SAME compiled pipeline
    (calib + fused ResNet-50) is swept over small batches on the device
    clock, and the per-frame latency at batch B is the full dispatch time
    (every frame in the batch waits for the batch). Reports the largest B
    meeting <5 ms/frame — larger B at the same latency is more throughput
    at the same responsiveness.

    Each batch shape is a fresh compile; the sweep self-budgets against the watchdog and stops early
    with a partial sweep rather than letting the section deadline
    os._exit the bench and forfeit every later section."""
    infer2 = shared.get("resnet_infer")
    if infer2 is None:
        log("latency-mode skipped: resnet section did not run")
        return
    variables = shared["resnet_variables"]
    infer = lambda f: infer2(variables, f)  # noqa: E731
    x = x_fresh_list[0]
    sweep = {}
    best = None
    # Self-budgeting sweep: each batch shape costs a compile plus
    # trace+parse. Gate
    # each step on the measured cost of the previous one so a warm sweep
    # runs to B=8 while a cold one stops before tripping the watchdog's
    # process-killing section deadline.
    step_cost_s = None  # measured after the first step
    for b in (1, 2, 4, 8):
        # first step: the old fixed 150 s floor (don't over-require when a
        # warm cache would make it cheap); later steps: 1.3x the measured
        # previous step + slack
        needed = 150.0 if step_cost_s is None else 1.3 * step_cost_s + 20.0
        if wd.remaining_s() < needed:
            sweep["stopped_early"] = f"B={b}+ skipped (watchdog budget)"
            log(f"latency sweep stopped before B={b}: "
                f"{wd.remaining_s():.0f} s left < {needed:.0f} needed")
            break
        t_step = time.perf_counter()
        samples = [(x[k * b:(k + 1) * b],) for k in range(min(3, len(x) // b))]
        ms = device_time_ms(jax, infer, (x[:b],), samples, f"latency B{b}", extras)
        step_cost_s = time.perf_counter() - t_step
        sweep[str(b)] = round(ms, 3)
        if ms < 5.0:
            best = {"batch": b, "ms_per_dispatch": round(ms, 3),
                    "fps_at_operating_point": round(b / (ms / 1e3), 1)}
        log(f"latency mode B={b}: {ms:.2f} ms/dispatch ({ms:.2f} ms per-frame latency)")
    extras["device_latency_ms_by_batch"] = sweep
    if best is not None:
        extras["device_latency_operating_point"] = best
        log(
            f"latency operating point: B={best['batch']} at "
            f"{best['ms_per_dispatch']} ms < 5 ms/frame target "
            f"({best['fps_at_operating_point']} fps)"
        )
    else:
        extras["device_latency_operating_point"] = "none under 5 ms"


def _bench_unet(jax, jnp, pedestal, gain, mask, x_warm, x_fresh_list, extras, shared):
    """Config 3: calib + PeakNet segmentation + fixed-shape peak
    extraction, panel-as-batch. Uses PeakNetUNetTPU — the MXU-shaped
    redesign (s2d stem, wide features at half res, d2s logit head;
    models/unet_tpu.py) — per-pixel logits identical in contract to the
    classic PeakNetUNet, but every conv runs at 50-100% MXU shapes
    instead of the 6-25% its 32-channel full-res levels allowed."""
    from psana_ray_tpu.models import PeakNetUNetTPU, panels_to_nhwc
    from psana_ray_tpu.models.pallas_unet import peaknet_tpu_fused_infer
    from psana_ray_tpu.models.peaks import find_peaks

    b_unet = 2  # frames per batch; panels fold into batch: [2*16, H, W, 1]
    model = PeakNetUNetTPU(norm="frozen")  # inference form, folded stats
    # serving params via the supported export path (see _serving_params);
    # stashed for the sfx section (identical ctor/shape — no second export)
    variables = _serving_params(PeakNetUNetTPU, (1, 64, 64, 1), extras, "unet")
    shared["unet_serving"] = variables

    from psana_ray_tpu.ops import fused_calibrate

    def make_seg(apply_fn):
        @jax.jit
        def seg(frames):
            c = fused_calibrate(
                frames, pedestal, gain, mask, threshold=10.0, out_dtype=jnp.bfloat16
            )
            logits = apply_fn(panels_to_nhwc(c, mode="batch"))
            return find_peaks(logits, max_peaks=64)

        return seg

    # fused Pallas encoder kernels first — but only after an ON-DEVICE
    # numerical check against the XLA model: interpret-mode tests cannot
    # catch a Mosaic lowering bug that compiles but computes garbage, and
    # a fast-but-wrong kernel must never become the recorded number.
    # Any failure (lowering error OR mismatch) falls back to XLA.
    use_fused = False
    try:
        nhwc_warm = jax.jit(
            lambda fr: panels_to_nhwc(
                fused_calibrate(
                    fr, pedestal, gain, mask, threshold=10.0, out_dtype=jnp.bfloat16
                ),
                mode="batch",
            )
        )(x_warm[:b_unet])
        lg_fused = jax.jit(
            lambda y: peaknet_tpu_fused_infer(variables, y)
        )(nhwc_warm)
        lg_xla = jax.jit(lambda y: model.apply(variables, y))(nhwc_warm)
        scale = float(jnp.max(jnp.abs(lg_xla)))
        err = float(jnp.max(jnp.abs(lg_fused - lg_xla))) / max(scale, 1e-3)
        if err < 0.05:
            use_fused = True
        else:
            log(f"fused U-Net MISMATCHES XLA on device (rel err {err:.3f}) — using XLA")
            extras["device_unet_fused_relerr"] = round(err, 4)
    except Exception as e:
        log(f"fused U-Net path failed ({e!r}); falling back to XLA model")

    if use_fused:
        seg = make_seg(lambda y: peaknet_tpu_fused_infer(variables, y))
        label, extras["device_unet_path"] = "calib+U-Net(fused)+peaks", "pallas-fused-encoder"
    else:
        seg = make_seg(lambda y: model.apply(variables, y))
        label, extras["device_unet_path"] = "calib+U-Net(xla)+peaks", "xla"
    def slices_of(b):
        """Distinct-content b-frame slices of the fresh pool (full slices
        only — a partial batch would skew the per-frame division)."""
        x_fresh = x_fresh_list[0]
        n = min(len(x_fresh_list), len(x_fresh) // b)
        return [(x_fresh[k * b:(k + 1) * b],) for k in range(n)]

    ms = device_time_ms(jax, seg, (x_warm[:b_unet],), slices_of(b_unet), label, extras)

    fps = b_unet / (ms / 1e3)
    extras["device_unet_fps"] = round(fps, 1)
    log(
        f"calib+U-Net+peak-extraction [{extras['device_unet_path']}]: {ms:.1f} ms "
        f"/ {b_unet} frames device-time -> {fps:.1f} fps"
    )

    # Throughput operating point: quarter-res trunk (s2d=4), same
    # per-pixel logit contract via the depth-to-space head, ~1/4 the
    # FLOPs of the s2d=2 quality mode.  The quality mode above is
    # measured at ~80% MXU utilization (PERF_NOTES round 3), so more
    # fusion cannot buy another multiple — only a FLOP trade can, and
    # that trade is the operator's to make; both numbers are recorded.
    try:
        from functools import partial

        model4 = PeakNetUNetTPU(norm="frozen", s2d=4)
        variables4 = _serving_params(
            partial(PeakNetUNetTPU, s2d=4), (1, 64, 64, 1), extras, "unet_s4"
        )
        seg4 = make_seg(lambda y: model4.apply(variables4, y))
        # throughput mode measures at a throughput batch: B=8 amortizes
        # per-dispatch overheads the 5 ms B=2 dispatch can't (405 -> 521
        # fps/chip measured), while amortized per-frame p50 stays ~2 ms
        b4 = 8
        ms4 = device_time_ms(
            jax, seg4, (x_warm[:b4],), slices_of(b4), "U-Net-s4", extras
        )
        fps4 = b4 / (ms4 / 1e3)
        extras["device_unet_s4_fps"] = round(fps4, 1)
        extras["device_unet_s4_batch"] = b4
        log(
            f"calib+U-Net(s2d=4 throughput mode)+peaks: {ms4:.1f} ms / "
            f"{b4} frames device-time -> {fps4:.1f} fps"
        )
    except Exception as e:
        log(f"U-Net s2d=4 extra skipped: {e!r}")


def _fanin_producer_proc(ring_name: str, det: str, n: int, seed: int):
    """Separate-process producer for the fan-in bench: streams n
    detector-native u16 frames from a small pool into the named shm ring.
    Deliberately jax-free (transport + records only) — real ingest
    processes don't hold a TPU."""
    import numpy as np  # noqa: F811 (fresh interpreter under spawn)

    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.sources.base import DETECTORS
    from psana_ray_tpu.transport.shm_ring import ShmRingBuffer
    from psana_ray_tpu.utils.hostmem import enable_large_alloc_reuse

    enable_large_alloc_reuse()

    shape = DETECTORS[det].frame_shape
    rng = np.random.default_rng(seed)
    pool = [
        rng.integers(0, 4096, size=shape, dtype=np.uint16) for _ in range(4)
    ]
    ring = ShmRingBuffer.attach(ring_name, retries=20, interval_s=0.25)
    for i in range(n):
        rec = FrameRecord(0, i, pool[i % len(pool)], 9.5)
        # a full ring means the consumer is behind: back off long enough
        # not to steal its cores (on a 1-core host a tight producer spin
        # halves the consumer's drain rate)
        while not ring.put(rec):
            time.sleep(0.003)
    if not ring.put_wait(EndOfStream(total_events=n), timeout=300.0):
        raise RuntimeError("EOS delivery timed out")
    ring.disconnect()


def _fanin_host_pass(det_a, det_b, n_a, n_b, batch_a, batch_b, extras, prefix, label):
    """One two-producer-process shm fan-in pass; returns aggregate fps.

    Records ``{prefix}_fps`` / ``{prefix}_counts`` and per-detector batch
    cadence p50 under ``{prefix}_{det}_batch_p50_ms``."""
    import multiprocessing as mp

    from psana_ray_tpu.infeed import DetectorStream, FanInPipeline
    from psana_ray_tpu.sources.base import DETECTORS
    from psana_ray_tpu.transport.shm_ring import ShmRingBuffer

    uid = f"{os.getpid()}_{int(time.time()*1e3)}"
    rings = {}
    procs = []
    ctx = mp.get_context("spawn")
    try:
        for det, n, seed in ((det_a, n_a, 1), (det_b, n_b, 2)):
            frame_bytes = int(np.prod(DETECTORS[det].frame_shape)) * 2
            rings[det] = ShmRingBuffer.create(
                f"fanin_{det}_{uid}", maxsize=16,
                slot_bytes=frame_bytes + 4096,
            )
            procs.append(
                ctx.Process(
                    target=_fanin_producer_proc,
                    args=(f"fanin_{det}_{uid}", det, n, seed),
                    daemon=True,
                )
            )
        # host metric: no device placement (that copy belongs to the
        # device leg, measured separately). Buffer recycling comes from
        # enable_large_alloc_reuse() (heap reuse of the per-batch
        # allocations), not the batcher pool — on the 1-core build host
        # the pool's upfront page-faulting measured as a wash; see
        # PERF_NOTES.md round 3.
        fan = FanInPipeline(
            [
                DetectorStream(det_a, rings[det_a], batch_size=batch_a,
                               poll_interval_s=0.002, place_on_device=False,
                               batcher_buffers=0),
                DetectorStream(det_b, rings[det_b], batch_size=batch_b,
                               poll_interval_s=0.002, place_on_device=False,
                               batcher_buffers=0),
            ]
        )
        arrivals = {det_a: [], det_b: []}
        for p in procs:
            p.start()
        counts = fan.run(
            {
                det_a: lambda b: None,  # host merge rate: no device
                det_b: lambda b: None,
            },
            on_result=lambda name, out, b: arrivals[name].append(
                (time.perf_counter(), b.num_valid)
            ),
        )
        for p in procs:
            p.join(timeout=60)
        # rate over the first->last batch-arrival span, excluding the
        # first batch's frames: spawn/import/attach startup of the
        # producer processes must not be billed to merge throughput
        merged = sorted(t for ts in arrivals.values() for t in ts)
        total = sum(counts.values())
        if len(merged) >= 2:
            span = merged[-1][0] - merged[0][0]
            wall = max(span, 1e-6)
            host_fps = (total - merged[0][1]) / wall
        else:
            wall, host_fps = float("nan"), 0.0
        extras[f"{prefix}_fps"] = round(host_fps, 1)
        extras[f"{prefix}_counts"] = dict(counts)
        for det in (det_a, det_b):
            gaps = np.diff([t for t, _ in arrivals[det]]) * 1e3
            if len(gaps):
                extras[f"{prefix}_{det}_batch_p50_ms"] = round(
                    float(np.percentile(gaps, 50)), 2
                )
        log(
            f"fan-in HOST rate [{label}]: {counts} in {wall:.2f}s -> "
            f"{host_fps:.0f} fps aggregate"
        )
        return host_fps
    finally:
        for r in rings.values():
            try:
                r.destroy()
            except Exception:
                pass


def _bench_host_datapath(extras, smoke=False):
    """Host-datapath accounting (no device): stream detector-native u16
    frames producer-client -> TCP queue server (loopback) -> batched
    consumer, and report — measured, not inferred — the per-frame memory
    discipline of the zero-copy rework alongside its fps:

    - ``host_datapath_tcp_fps``: relay throughput through one server;
    - ``host_datapath_copies_per_frame``: consumer-side payload memcpys
      (utils.bufpool.WIRE counters; 1.0 = wire -> batch-arena only);
    - ``host_datapath_allocs_per_frame``: steady-state pool misses per
      frame past warmup (0.0 = every recv buffer recycled);
    - pool gauges (leases/hits/misses) under ``host_datapath_pool``.

    Producer-side accounting rides the same counters: sendmsg scatter-
    gather means a put performs no payload copy at all, so the producer
    contributes 0 to copies/frame here (the server relay contributes 0
    as well — it forwards the pooled buffer it received into).

    The run doubles as the tracing demonstration (ISSUE 4): sampled
    per-frame tracing is enabled at 1/16 into a scratch spool for the
    request/response stream, and the resulting span summary + flight-
    recorder event counts land in bench_full.json (``trace_summary`` /
    ``flight_events``) — the artifact proves the tracing path works on
    every bench run, and PERF_NOTES records its measured overhead.

    ISSUE 5 adds a ``streaming`` row over the same frames: the consumer
    drains the server-push stream (credit-window delivery, explicit
    cumulative acks) instead of pulling — ``host_datapath_stream_*``
    report its fps, copies/frame (still 1.00) and credit-window
    occupancy from the ``stream`` obs gauges. On loopback the RTT the
    stream hides is tiny, so the two rows should be close; the
    RTT-independence acceptance (>=10x through a 5 ms delay proxy)
    lives in tests/test_tcp_stream.py and PERF_NOTES.
    """
    import tempfile
    import threading as _threading

    from psana_ray_tpu.infeed.batcher import batches_from_queue
    from psana_ray_tpu.obs.flight import FLIGHT
    from psana_ray_tpu.obs.tracing import TRACER
    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.transport import RingBuffer
    from psana_ray_tpu.transport.tcp import STREAM, TcpQueueClient, TcpQueueServer
    from psana_ray_tpu.utils.bufpool import BufferPool, WIRE

    shape = (2, 32, 32) if smoke else (16, 352, 384)  # epix10k2M u16
    n_frames = 32 if smoke else 192
    batch_size = 8 if smoke else 32
    rng = np.random.default_rng(7)
    pool16 = [rng.integers(0, 4096, size=shape, dtype=np.uint16) for _ in range(4)]
    buf_pool = BufferPool.default()

    def run_relay(streaming: bool, obs_hook=None):
        """One producer->server->batched-consumer pass; returns the
        measured (fps, copies/frame, allocs/frame, growth/frame,
        cpu_ns/frame, pool). ``obs_hook(srv)`` (the ISSUE 13
        sampling+collector A/B; ISSUE 16 profiler A/B) may attach
        observers to the live server and return a cleanup."""
        # queue depth bounds the pool's working set (every queued frame
        # holds a pooled lease): one batch of headroom keeps the relay
        # busy without ballooning retained buffers
        srv = TcpQueueServer(
            RingBuffer(batch_size), host="127.0.0.1"
        ).serve_background()
        obs_cleanup = obs_hook(srv) if obs_hook is not None else None
        prod = TcpQueueClient("127.0.0.1", srv.port)
        cons = TcpQueueClient("127.0.0.1", srv.port)

        def produce(warmup: int):
            total = warmup + n_frames
            for i in range(total):
                rec = FrameRecord(
                    0, i, pool16[i % 4], 9.5, trace=TRACER.maybe_trace()
                )
                if not prod.put_wait(rec, timeout=120.0):
                    raise RuntimeError("producer starved out")
            if not prod.put_wait(EndOfStream(total_events=total), timeout=120.0):
                raise RuntimeError("EOS delivery timed out")

        try:
            warmup = 3 * batch_size  # let the pool reach its working-set peak
            t = _threading.Thread(target=produce, args=(warmup,), daemon=True)
            seen = 0
            t0 = time.perf_counter()
            m0 = None
            # copies are exactly per-frame, so count them over the WHOLE
            # stream (a steady-state mark would land mid-pop: the batch
            # source copies a pop's frames before yielding, skewing a
            # windowed ratio); allocs genuinely need the steady window
            c0 = WIRE.stats()
            t.start()
            for batch in batches_from_queue(
                cons, batch_size, poll_interval_s=0.001, prefer_stream=streaming
            ):
                seen += batch.num_valid
                if m0 is None and seen >= warmup:  # steady state begins
                    m0 = buf_pool.stats()
                    cpu0 = os.times()
                    t0 = time.perf_counter()
                    seen_at_mark = seen
            dt = time.perf_counter() - t0
            t.join()
            if m0 is None:  # stream died before steady state: no number
                raise RuntimeError(
                    f"only {seen} frames before EOS; no steady window"
                )
            c1, m1 = WIRE.stats(), buf_pool.stats()
            cpu1 = os.times()
            steady = max(1, seen - seen_at_mark)
            fps = steady / dt
            copies = (c1["copies_total"] - c0["copies_total"]) / max(1, seen)
            # steady-state churn only: a miss that raised the class's
            # concurrency high-water is working-set growth (those buffers
            # never existed before), not a per-frame allocation
            allocs = (m1["churn_misses"] - m0["churn_misses"]) / steady
            growth = (m1["misses"] - m0["misses"]) / steady
            # host-CPU cost per frame over the same steady window: the
            # ISSUE 16 cost model's number, measured here process-wide
            # (producer + consumer threads share this process; the
            # server relay is this process too — the full host bill)
            cpu_ns = (
                (cpu1.user + cpu1.system) - (cpu0.user + cpu0.system)
            ) * 1e9 / steady
            return fps, copies, allocs, growth, cpu_ns, m1
        finally:
            if obs_cleanup is not None:
                try:
                    obs_cleanup()
                except Exception:  # noqa: BLE001 — observer teardown only
                    pass
            for c in (prod, cons):
                try:
                    c.disconnect()
                except Exception:
                    pass
            srv.shutdown()

    # -- request/response row (doubles as the tracing demo) ---------------
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    TRACER.configure(trace_dir, sample_every=16, process="bench")
    try:
        fps, copies, allocs, growth, cpu_ns, m1 = run_relay(streaming=False)
        extras["host_datapath_tcp_fps"] = round(fps, 1)
        extras["host_datapath_copies_per_frame"] = round(copies, 3)
        extras["host_datapath_allocs_per_frame"] = round(allocs, 3)
        extras["host_datapath_pool_growth_per_frame"] = round(growth, 3)
        extras["host_datapath_cpu_ns_per_frame"] = round(cpu_ns, 0)
        extras["host_datapath_pool"] = m1
        log(
            f"host datapath [tcp relay, u16 {shape}]: {fps:.0f} fps, "
            f"{copies:.2f} copies/frame, {allocs:.3f} allocs/frame "
            f"steady-state, {cpu_ns / 1e3:.0f} us CPU/frame "
            f"(pool: {m1['hits']} hits / {m1['misses']} "
            f"misses, {m1['churn_misses']} churn)"
        )
        # the sampled-trace + flight summaries of this very stream:
        # proof in the artifact that the tracing path works end to end
        trace_snap = TRACER.snapshot()
        extras["trace_summary"] = trace_snap
        extras["flight_events"] = FLIGHT.snapshot()
        log(
            f"trace demo [1/{trace_snap['sample_every']} sampling]: "
            f"{trace_snap['spans_total']} spans "
            f"({trace_snap.get('spans_by_name', {})}), flight events: "
            f"{extras['flight_events']['events_total']}"
        )
    finally:
        TRACER.close()
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)  # scratch spool

    # -- streaming row (ISSUE 5: server-push, credit-window delivery) ------
    s0 = STREAM.stats()
    fps_s, copies_s, allocs_s, growth_s, cpu_ns_s, _ = run_relay(streaming=True)
    s1 = STREAM.stats()
    occupancy = {
        "window": s1["credit_window"] or None,  # 0 after clean close
        "inflight_peak": s1["inflight_peak"],
        "frames_pushed": s1["frames_pushed_total"] - s0["frames_pushed_total"],
        "acks": s1["acks_total"] - s0["acks_total"],
        "redelivered": s1["redelivered_total"] - s0["redelivered_total"],
    }
    extras["host_datapath_stream_fps"] = round(fps_s, 1)
    extras["host_datapath_stream_copies_per_frame"] = round(copies_s, 3)
    extras["host_datapath_stream_allocs_per_frame"] = round(allocs_s, 3)
    extras["host_datapath_stream_cpu_ns_per_frame"] = round(cpu_ns_s, 0)
    extras["host_datapath_stream_occupancy"] = occupancy
    log(
        f"host datapath [tcp STREAMING, u16 {shape}]: {fps_s:.0f} fps, "
        f"{copies_s:.2f} copies/frame, {allocs_s:.3f} allocs/frame "
        f"steady-state (window peak {occupancy['inflight_peak']} in "
        f"flight, {occupancy['acks']} acks, "
        f"{occupancy['redelivered']} redelivered)"
    )

    # -- telemetry-plane overhead row (ISSUE 13) ---------------------------
    # the SAME passthrough relay with the history sampler AND the
    # federation collector polling the live server over the 'N' metrics
    # RPC — at 5 Hz each, 5-10x the production default, so the measured
    # delta is an upper bound. Acceptance: fps within noise of the
    # sampling-off row above, copies/frame 1.00 / allocs 0 UNCHANGED
    # (the telemetry plane reads counters; it must never touch frames).
    def _obs_on(srv):
        from psana_ray_tpu.obs.collector import ClusterCollector
        from psana_ray_tpu.obs.timeseries import HistorySampler

        sampler = HistorySampler(interval_s=0.2).start()
        coll = ClusterCollector(
            [f"127.0.0.1:{srv.port}"], interval_s=0.2, register=False
        ).start()

        def _cleanup():
            sampler.stop()
            coll.stop()
            extras["host_datapath_obs_history"] = sampler.snapshot()
            extras["host_datapath_obs_collector"] = coll.snapshot()

        return _cleanup

    fps_o, copies_o, allocs_o, _growth_o, _cpu_ns_o, _ = run_relay(
        streaming=False, obs_hook=_obs_on
    )
    extras["host_datapath_obs_on_fps"] = round(fps_o, 1)
    extras["host_datapath_obs_on_copies_per_frame"] = round(copies_o, 3)
    extras["host_datapath_obs_on_allocs_per_frame"] = round(allocs_o, 3)
    extras["host_datapath_obs_on_delta_pct"] = (
        round((fps_o - fps) / fps * 100.0, 1) if fps else None
    )
    log(
        f"host datapath [tcp relay + 5 Hz sampler + 5 Hz collector]: "
        f"{fps_o:.0f} fps ({extras['host_datapath_obs_on_delta_pct']:+.1f}% "
        f"vs sampling off), {copies_o:.2f} copies/frame, "
        f"{allocs_o:.3f} allocs/frame — the telemetry plane reads "
        f"counters, never frames"
    )

    # -- continuous-profiler overhead row (ISSUE 16) -----------------------
    # the SAME passthrough relay with the 97 Hz flame sampler live in
    # this process (producer + relay server + consumer threads all get
    # sampled). Acceptance: fps within 3% of the profiler-off row,
    # copies/frame 1.00 / allocs 0 UNCHANGED — the sampler walks stacks
    # and preallocated arrays, it never touches frames or allocates.
    def _prof_on(srv):
        from psana_ray_tpu.obs.profiling import FlameSampler

        sampler = FlameSampler(hz=97.0, process="bench", register=False).start()

        def _cleanup():
            sampler.stop(write_spool=False)
            extras["host_datapath_prof"] = {
                "samples": sampler.trie.samples_total,
                "on_cpu": sampler.trie.on_cpu_total,
                "waiting": sampler.trie.waiting_total,
                "nodes": sampler.trie.n_nodes,
                "overflow": sampler.trie.overflow_total,
                "stage_cpu_ms": sampler.stage_cpu_ms(),
            }

        return _cleanup

    fps_p, copies_p, allocs_p, _growth_p, cpu_ns_p, _ = run_relay(
        streaming=False, obs_hook=_prof_on
    )
    extras["host_datapath_prof_on_fps"] = round(fps_p, 1)
    extras["host_datapath_prof_on_copies_per_frame"] = round(copies_p, 3)
    extras["host_datapath_prof_on_allocs_per_frame"] = round(allocs_p, 3)
    extras["host_datapath_prof_on_cpu_ns_per_frame"] = round(cpu_ns_p, 0)
    extras["host_datapath_prof_on_delta_pct"] = (
        round((fps_p - fps) / fps * 100.0, 1) if fps else None
    )
    prof = extras.get("host_datapath_prof", {})
    log(
        f"host datapath [tcp relay + 97 Hz flame sampler]: "
        f"{fps_p:.0f} fps ({extras['host_datapath_prof_on_delta_pct']:+.1f}% "
        f"vs profiler off), {copies_p:.2f} copies/frame, "
        f"{allocs_p:.3f} allocs/frame, {cpu_ns_p / 1e3:.0f} us CPU/frame "
        f"({prof.get('samples', 0)} samples, "
        f"{prof.get('on_cpu', 0)} on-CPU, {prof.get('nodes', 0)} trie nodes)"
    )


def _detector_like_frames(shape, seed, n=4):
    """Raw-stream epix-like u16 content: smooth per-pixel pedestal
    (fixed-pattern), sigma~3 gaussian readout noise, sparse photon
    peaks — the content class detector wire compression exists for
    (uniform noise would flatter nobody; real raw frames are not
    uniform noise)."""
    rng = np.random.default_rng(seed)
    ped = 2000 + 200 * np.sin(
        np.linspace(0, 20, int(np.prod(shape)))
    ).reshape(shape)
    out = []
    for _ in range(n):
        f = (ped + rng.normal(0, 3, shape)).clip(0, 65535).astype(np.uint16)
        hits = rng.random(shape) < 1e-4
        f[hits] += rng.integers(500, 3000, int(hits.sum())).astype(np.uint16)
        out.append(f)
    return out


def _wire_compression_producer(port, codec_name, shape, total, seed):
    """Subprocess body for the wire-compression relay rows: a REAL
    producer process, because compression burns a core the relay and
    consumer must not share — the cross-process topology every
    deployment has (in-process threads would serialize the codec
    stages on the GIL and measure Python, not the transport)."""
    import time as _time

    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.transport.tcp import TcpQueueClient

    pool16 = _detector_like_frames(tuple(shape), seed)
    client = TcpQueueClient(
        "127.0.0.1", port,
        codec=None if codec_name == "none" else codec_name,
    )
    for i in range(total):
        while not client.put_pipelined(
            FrameRecord(0, i, pool16[i % 4], 9.5),
            deadline=_time.monotonic() + 2.0,
        ):
            pass
    client.flush_puts()
    client.put_wait(EndOfStream(total_events=total), timeout=120.0)
    client.disconnect()


def _bench_wire_compression(extras, smoke=False):
    """Wire compression accounting (ISSUE 9, no device): the bandwidth
    wall PERF_NOTES' arithmetic predicts (10x on 4.33 MB epix u16
    frames needs >=3.9 GB/s links; a tunnelled or WAN link gives tens
    of MB/s) attacked with the negotiated per-connection codec layer.

    - ``wire_compression_codecs``: per registered codec, the measured
      compression ratio and compress/decompress MB/s on DETECTOR-LIKE
      u16 frames (per-pixel pedestal fixed-pattern + sigma~3 readout
      noise + sparse photon peaks — the content class the
      shuffle+delta/RLE/bit-pack codec exists for; uniform noise would
      flatter nobody and real raw frames are not uniform noise);
    - ``wire_compression_relay``: A/B fps of the full producer ->
      queue-server -> streamed-consumer relay through a token-bucket
      BANDWIDTH-throttled proxy (tests/faultproxy.ThrottleProxy at
      ~50 MB/s, both directions capped like a real tunnel) —
      uncompressed vs each codec, with the measured speedup and the
      proxy's actual wire byte counts;
    - the zero-copy pins on the COMPRESSED path: copies/frame == 1.00
      (the batch-arena memcpy; codec transforms stage through pool
      leases, not fresh allocations) and steady-state pool churn
      allocs/frame == 0, measured on an instrumented private pool;
    - ``wire_compression_loopback_fps``: the same harness on raw
      loopback WITHOUT negotiation — parity with the host-datapath
      streaming row shows the default path is untouched.

    Acceptance (ISSUE 9): compressed relay >= 2x uncompressed fps
    through the ~50 MB/s proxy; recorded, not assumed.
    """
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests")
    )
    from faultproxy import ThrottleProxy

    from psana_ray_tpu.infeed.batcher import batches_from_queue
    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.transport import RingBuffer
    from psana_ray_tpu.transport.codec import (
        CODEC_STATS,
        available_codecs,
        compress_encoded_parts,
        encode_payload_parts,
        get_codec,
    )
    from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer
    from psana_ray_tpu.utils.bufpool import BufferPool, WIRE

    shape = (2, 32, 32) if smoke else (16, 352, 384)  # epix10k2M u16
    n_frames = 8 if smoke else 24
    warmup = 4 if smoke else 6
    batch_size = 4 if smoke else 8
    rate = 4e6 if smoke else 50e6  # bytes/s per direction
    pool16 = _detector_like_frames(shape, seed=11)
    frame_bytes = pool16[0].nbytes

    # -- codec microbench: ratio + MB/s per registered codec --------------
    codec_rows = {}
    micro_pool = BufferPool()
    for name in available_codecs():
        codec = get_codec(name)
        rec = FrameRecord(0, 0, pool16[0], 9.5)
        parts = encode_payload_parts(rec)
        best_c = best_d = float("inf")
        wire_len = None
        for _ in range(3):
            t0 = time.perf_counter()
            wparts, lease = compress_encoded_parts(rec, parts, codec, micro_pool)
            best_c = min(best_c, time.perf_counter() - t0)
            if lease is None:
                break  # expansion fallback: nothing to time on decode
            wire = b"".join(bytes(p) for p in wparts)
            wire_len = len(wire)
            from psana_ray_tpu.transport.codec import decode_payload

            t0 = time.perf_counter()
            out = decode_payload(wire)
            best_d = min(best_d, time.perf_counter() - t0)
            out.release()
            lease.release()
        raw_len = sum(
            p.nbytes if isinstance(p, memoryview) else len(p) for p in parts
        )
        codec_rows[name] = {
            "ratio": round(raw_len / wire_len, 2) if wire_len else 1.0,
            "compress_mb_s": round(frame_bytes / 1e6 / best_c, 1),
            "decompress_mb_s": (
                round(frame_bytes / 1e6 / best_d, 1)
                if best_d < float("inf")
                else None
            ),
        }
        log(
            f"wire codec [{name}]: ratio {codec_rows[name]['ratio']}x, "
            f"compress {codec_rows[name]['compress_mb_s']} MB/s, "
            f"decompress {codec_rows[name]['decompress_mb_s']} MB/s "
            f"(detector-like u16 {shape})"
        )
    extras["wire_compression_codecs"] = codec_rows

    import subprocess as _subprocess

    repo_root = os.path.dirname(os.path.abspath(__file__))

    def run_relay(codec_name, throttled=True, pool=None):
        """REAL producer process -> throttled proxy -> server ->
        throttled proxy -> streamed consumer (this process); returns
        (fps, copies/frame, churn allocs/frame, proxy wire bytes).
        Cross-process on purpose: the codec stages must burn separate
        cores, as they do in any actual deployment (in-process threads
        would serialize compress and decompress on the GIL)."""
        pool = pool or BufferPool.default()
        srv = TcpQueueServer(
            RingBuffer(batch_size), host="127.0.0.1", pool=pool
        ).serve_background()
        proxy = (
            ThrottleProxy("127.0.0.1", srv.port, rate, burst_s=0.05)
            if throttled
            else None
        )
        port = proxy.port if proxy else srv.port
        codec_arg = None if codec_name == "none" else codec_name
        cons = TcpQueueClient("127.0.0.1", port, pool=pool, codec=codec_arg)
        total = warmup + n_frames
        child_env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = _subprocess.Popen(
            [
                sys.executable, "-c",
                "import sys; sys.path.insert(0, %r); "
                "from bench import _wire_compression_producer as p; "
                "p(%d, %r, %r, %d, 11)"
                % (repo_root, port, codec_name, tuple(shape), total),
            ],
            env=child_env,
        )

        def watch_child():
            # a producer that dies early must kill the drain, not hang it
            rc = proc.wait()
            if rc != 0:
                srv.close_all()

        try:
            c0 = WIRE.stats()
            threading.Thread(target=watch_child, daemon=True).start()
            seen = 0
            t0 = time.perf_counter()
            m0 = None
            seen_at_mark = 0
            for batch in batches_from_queue(
                cons, batch_size, poll_interval_s=0.001, prefer_stream=True
            ):
                seen += batch.num_valid
                if m0 is None and seen >= warmup:
                    m0 = pool.stats()
                    t0 = time.perf_counter()
                    seen_at_mark = seen
            dt = time.perf_counter() - t0
            proc.wait(timeout=60)
            if m0 is None or seen != total:
                raise RuntimeError(f"relay saw {seen}/{total} frames")
            c1, m1 = WIRE.stats(), pool.stats()
            steady = max(1, seen - seen_at_mark)
            copies = (c1["copies_total"] - c0["copies_total"]) / max(1, seen)
            allocs = (m1["churn_misses"] - m0["churn_misses"]) / steady
            wire_bytes = (
                proxy.bytes_forwarded("up") + proxy.bytes_forwarded("down")
                if proxy
                else None
            )
            return steady / dt, copies, allocs, wire_bytes
        finally:
            if proc.poll() is None:
                proc.kill()
            try:
                cons.disconnect()
            except Exception:
                pass
            if proxy:
                proxy.close()
            srv.shutdown()

    def best_of(n, *args, **kw):
        """Best fps over n attempts: this box's CPU share fluctuates on
        a seconds scale (the PR 5 convention for wall-clock rows —
        contention can only slow a run down, never speed it up)."""
        best = None
        for _ in range(n):
            r = run_relay(*args, **kw)
            if best is None or r[0] > best[0]:
                best = r
        return best

    # -- loopback parity row (default path untouched) ----------------------
    fps_loop, _, _, _ = run_relay("none", throttled=False)
    extras["wire_compression_loopback_fps"] = round(fps_loop, 1)
    log(f"wire compression [loopback, uncompressed]: {fps_loop:.1f} fps")

    # -- A/B through the ~50 MB/s bandwidth cap ----------------------------
    relay_rows = {}
    s0 = CODEC_STATS.stats()
    fps_none, _, _, wire_none = best_of(2, "none")
    relay_rows["none"] = {
        "fps": round(fps_none, 2),
        "wire_mb": round(wire_none / 1e6, 1),
    }
    log(
        f"wire compression [throttled {rate / 1e6:.0f} MB/s, none]: "
        f"{fps_none:.2f} fps, {wire_none / 1e6:.1f} MB on the wire"
    )
    for name in available_codecs():
        ipool = BufferPool()  # instrumented: the compressed-path pins
        fps_c, copies, allocs, wire_c = best_of(2, name, pool=ipool)
        relay_rows[name] = {
            "fps": round(fps_c, 2),
            "wire_mb": round(wire_c / 1e6, 1),
            "speedup": round(fps_c / fps_none, 2),
            "copies_per_frame": round(copies, 3),
            "allocs_per_frame": round(allocs, 3),
        }
        log(
            f"wire compression [throttled {rate / 1e6:.0f} MB/s, {name}]: "
            f"{fps_c:.2f} fps = {fps_c / fps_none:.2f}x uncompressed, "
            f"{wire_c / 1e6:.1f} MB on the wire, {copies:.2f} copies/frame, "
            f"{allocs:.3f} allocs/frame"
        )
    extras["wire_compression_relay"] = relay_rows
    s1 = CODEC_STATS.stats()
    extras["wire_compression_telemetry"] = {
        "frames_compressed": s1["frames_compressed_total"]
        - s0["frames_compressed_total"],
        "cache_hits": s1["cache_hits_total"] - s0["cache_hits_total"],
        "expansions": s1["expansions_total"] - s0["expansions_total"],
        "ratio_out": s1["ratio_out"],
    }
    best = max(
        (r["speedup"] for k, r in relay_rows.items() if k != "none"),
        default=1.0,
    )
    extras["wire_compression_speedup"] = best
    if smoke:
        log(
            f"wire compression [smoke]: plumbing exercised; speedup "
            f"{best:.2f}x is NOT meaningful at smoke frame sizes (the "
            f"throttle burst covers the whole run) — the acceptance "
            f"number comes from the full-size section"
        )
    else:
        log(
            f"wire compression: best speedup {best:.2f}x through the "
            f"{rate / 1e6:.0f} MB/s cap (acceptance >= 2x)"
        )


def _autotune_producer(port, codec_name, shape, total, seed, schedule=None):
    """Subprocess body for the autotune A/B rows: a REAL producer
    process (codec CPU on its own core, like every deployment), with an
    optional deterministic arrival schedule (the bursty regime) and the
    send wall-clock riding ``event_idx`` (int64 ns) so the consumer can
    measure per-frame dwell without new wire surface."""
    import time as _time

    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.transport.tcp import TcpQueueClient

    pool16 = _detector_like_frames(tuple(shape), seed)
    client = TcpQueueClient(
        "127.0.0.1", port, codec=codec_name or None
    )
    t0 = _time.monotonic()
    for i in range(total):
        if schedule is not None:
            lag = schedule[i] - (_time.monotonic() - t0)
            if lag > 0:
                _time.sleep(lag)
        rec = FrameRecord(0, _time.time_ns(), pool16[i % 4], 9.5)
        while not client.put_pipelined(rec, deadline=_time.monotonic() + 2.0):
            pass
    client.flush_puts()
    client.put_wait(EndOfStream(total_events=total), timeout=120.0)
    client.disconnect()


def _bench_autotune(extras, smoke=False):
    """Autotune A/B (ISSUE 15): controller-on vs best-hand-tuned across
    THREE regimes through the existing fault proxies —

    - ``slow_link``: ThrottleProxy at ~50 MB/s both directions (the
      slow-link regime wire compression exists for);
    - ``loopback``: raw loopback (where the codec only burns CPU);
    - ``bursty``: open-loop arrival_schedule bursts at a mean rate below
      capacity (the latency regime — the metric is dwell p99, not fps).

    The HAND rows carry each regime's best per-regime flags (codec
    explicitly on for the throttle, off elsewhere — the PR 9 measured
    choices). The CONTROLLER rows carry IDENTICAL flags in all three:
    ``codec="auto"`` (the connect-time link-rate probe decides) plus a
    live hill climber actuating the drain chunk/poll knobs mid-run.
    Acceptance (ROADMAP item 3): controller >= 95% of hand fps in the
    throughput regimes, <= 105% of hand dwell p99 in the bursty one,
    codec auto-OFF at loopback / auto-ON through the throttle, and the
    zero-copy pins (copies/frame 1.00, churn 0) unchanged with the
    controller live."""
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests")
    )
    import subprocess as _subprocess

    from faultproxy import ThrottleProxy, arrival_schedule

    from psana_ray_tpu.autotune.controller import (
        HillClimber,
        Objective,
        default_guardrails,
    )
    from psana_ray_tpu.autotune.knobs import (
        KnobRegistry,
        drain_chunk_knob,
        drain_poll_knob,
    )
    from psana_ray_tpu.infeed.batcher import DrainControl, batches_from_queue
    from psana_ray_tpu.obs.flight import FLIGHT
    from psana_ray_tpu.obs.timeseries import TimeSeriesStore
    from psana_ray_tpu.transport import RingBuffer
    from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer
    from psana_ray_tpu.utils.bufpool import BufferPool, WIRE

    shape = (2, 32, 32) if smoke else (16, 352, 384)  # epix10k2M u16
    n_frames = 8 if smoke else 24
    warmup = 4 if smoke else 6
    batch_size = 4 if smoke else 8
    rate = 4e6 if smoke else 50e6  # slow-link bytes/s per direction
    burst_hz = 40.0 if smoke else 24.0  # bursty mean rate (< capacity)
    repo_root = os.path.dirname(os.path.abspath(__file__))

    def run_row(regime, codec_arg, autotune_on, pool=None):
        """One (regime, config) row. Returns fps (steady), dwell p99 ms,
        copies/frame, churn allocs/frame, consumer codec decision (None
        for explicit codec args), autotune actuation count."""
        pool = pool or BufferPool.default()
        total = warmup + n_frames
        srv = TcpQueueServer(
            RingBuffer(batch_size * 4), host="127.0.0.1", pool=pool
        ).serve_background()
        proxy = None
        schedule = None
        if regime == "slow_link":
            # small burst: the link-rate probe must see the CAP, not the
            # token bucket's initial burst
            proxy = ThrottleProxy("127.0.0.1", srv.port, rate, burst_s=0.005)
        elif regime == "bursty":
            schedule = list(arrival_schedule(
                "burst", burst_hz, total / burst_hz, burst_factor=4.0,
                period_s=0.5,
            ))[:total]
        port = proxy.port if proxy else srv.port
        mark = FLIGHT.count_of("codec_auto_decision")
        cons = TcpQueueClient("127.0.0.1", port, pool=pool, codec=codec_arg)
        decision = None
        if FLIGHT.count_of("codec_auto_decision") > mark:
            # the consumer connect just decided (ring-eviction safe:
            # the decision is the newest event of its kind)
            for e in FLIGHT.events():
                if e["kind"] == "codec_auto_decision":
                    decision = bool(e["codec_on"])
        control = DrainControl(chunk=batch_size, poll_s=0.002)
        reg = KnobRegistry()
        stop_ctl = threading.Event()
        ctl_thread = None
        seen_box = [0]
        if autotune_on:
            reg.register(drain_chunk_knob(control))
            reg.register(drain_poll_knob(control))
            store = TimeSeriesStore()
            hc = HillClimber(
                reg, Objective("bench.frames_total", window_s=2.0),
                store=store, guardrails=default_guardrails(),
                hold_ticks=1, settle_ticks=1, cooldown_ticks=1,
            )

            def _ctl():
                while not stop_ctl.wait(0.25):
                    store.record({"bench": {"frames_total": seen_box[0]}})
                    try:
                        hc.tick()
                    except Exception:  # noqa: BLE001 — tuning never kills a row
                        pass

            ctl_thread = threading.Thread(target=_ctl, daemon=True)
        child_env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = _subprocess.Popen(
            [
                sys.executable, "-c",
                "import sys; sys.path.insert(0, %r); "
                "sys.path.insert(0, %r); "
                "from bench import _autotune_producer as p; "
                "p(%d, %r, %r, %d, 11, schedule=%r)"
                % (
                    repo_root, os.path.join(repo_root, "tests"),
                    port, codec_arg, tuple(shape), total, schedule,
                ),
            ],
            env=child_env,
        )

        def watch_child():
            rc = proc.wait()
            if rc != 0:
                srv.close_all()

        try:
            threading.Thread(target=watch_child, daemon=True).start()
            if ctl_thread is not None:
                ctl_thread.start()
            c0 = WIRE.stats()
            dwell_ns = []
            seen = 0
            t0 = time.perf_counter()
            m0 = None
            seen_at_mark = 0
            for batch in batches_from_queue(
                cons, batch_size, poll_interval_s=0.002, control=control
            ):
                now_ns = time.time_ns()
                for idx in batch.event_idx[: batch.num_valid]:
                    dwell_ns.append(now_ns - int(idx))
                seen += batch.num_valid
                seen_box[0] = seen
                if m0 is None and seen >= warmup:
                    m0 = pool.stats()
                    t0 = time.perf_counter()
                    seen_at_mark = seen
                    del dwell_ns[:]  # dwell measured post-warmup only
            dt = time.perf_counter() - t0
            proc.wait(timeout=120)
            if m0 is None or seen != total:
                raise RuntimeError(f"autotune row saw {seen}/{total} frames")
            c1, m1 = WIRE.stats(), pool.stats()
            steady = max(1, seen - seen_at_mark)
            copies = (c1["copies_total"] - c0["copies_total"]) / max(1, seen)
            allocs = (m1["churn_misses"] - m0["churn_misses"]) / steady
            dwell_ms = sorted(d / 1e6 for d in dwell_ns)
            p99 = (
                dwell_ms[min(len(dwell_ms) - 1, int(0.99 * len(dwell_ms)))]
                if dwell_ms else None
            )
            acted = 0
            if autotune_on:
                snap = reg.snapshot()
                acted = sum(
                    snap[k]["actuations_total"]
                    for k in ("drain_chunk", "drain_poll_s")
                )
            return steady / dt, p99, copies, allocs, decision, acted
        finally:
            stop_ctl.set()
            if ctl_thread is not None:
                ctl_thread.join(timeout=2)
            if proc.poll() is None:
                proc.kill()
            try:
                cons.disconnect()
            except Exception:
                pass
            if proxy:
                proxy.close()
            srv.shutdown()

    def best_of(n, *args, **kw):
        """Best row over n attempts (PR 5 wall-clock convention: host
        contention only ever slows a run down). 'Best' = max fps for
        the throughput regimes, min p99 for the bursty one."""
        best = None
        for _ in range(n):
            r = run_row(*args, **kw)
            if best is None:
                best = r
            elif args[0] == "bursty":
                if r[1] is not None and (best[1] is None or r[1] < best[1]):
                    best = r
            elif r[0] > best[0]:
                best = r
        return best

    # per-regime best hand flags (the PR 9 measured choices): codec on
    # through the throttle, off where there is no bandwidth wall
    hand_flags = {"slow_link": "shuffle-rle", "loopback": None, "bursty": None}
    tries = 1 if smoke else 2
    rows = {}
    accept_all = True
    for regime in ("slow_link", "loopback", "bursty"):
        fps_h, p99_h, _, _, _, _ = best_of(tries, regime, hand_flags[regime], False)
        ipool = BufferPool()  # instrumented: the controller-live pins
        fps_c, p99_c, copies, allocs, decision, acted = best_of(
            tries, regime, "auto", True, pool=ipool
        )
        if regime == "bursty":
            ok = p99_h is not None and p99_c is not None and p99_c <= 1.05 * p99_h
        else:
            ok = fps_c >= 0.95 * fps_h
        want_codec_on = regime == "slow_link"
        codec_ok = decision is None or decision == want_codec_on
        accept_all = accept_all and ok and codec_ok
        rows[regime] = {
            "hand_fps": round(fps_h, 2),
            "hand_p99_ms": round(p99_h, 1) if p99_h is not None else None,
            "hand_flags": hand_flags[regime] or "none",
            "ctl_fps": round(fps_c, 2),
            "ctl_p99_ms": round(p99_c, 1) if p99_c is not None else None,
            "ctl_codec_decision_on": decision,
            "ctl_copies_per_frame": round(copies, 3),
            "ctl_allocs_per_frame": round(allocs, 3),
            "ctl_actuations": acted,
            "fps_ratio": round(fps_c / fps_h, 3) if fps_h else None,
            "accept": bool(ok and codec_ok),
        }
        log(
            f"autotune [{regime}]: hand {fps_h:.2f} fps"
            f"{f' / p99 {p99_h:.0f} ms' if p99_h is not None else ''} "
            f"({rows[regime]['hand_flags']}) vs controller {fps_c:.2f} fps"
            f"{f' / p99 {p99_c:.0f} ms' if p99_c is not None else ''} "
            f"(auto; codec_on={decision}, {acted} actuations, "
            f"{copies:.2f} copies/frame, {allocs:.3f} allocs/frame) — "
            f"{'OK' if rows[regime]['accept'] else 'MISS'}"
        )
    extras["autotune"] = rows
    extras["autotune_accept_all"] = accept_all
    if smoke:
        log(
            "autotune [smoke]: plumbing exercised; ratios are NOT "
            "meaningful at smoke sizes (the throttle burst covers the "
            "whole run) — acceptance comes from the full-size section"
        )
    else:
        log(
            f"autotune: controller-on with IDENTICAL flags across all "
            f"three regimes {'meets' if accept_all else 'MISSES'} the "
            f">=95% fps / <=105% p99 bar vs best hand-tuned"
        )


def _bench_data_plane(extras, smoke=False):
    """Multi-process data plane + kernel pass-through (ISSUE 17, no
    device):

    - ``data_plane_splice``: spliced vs materialized drain of a
      lazy-spill durable queue through a REAL queue_server subprocess.
      The producer fills the queue first (appends pay their log memcpy
      outside the measured window), THEN each drain is measured in
      isolation: (A) a plain connection — payload moves mmap->socket by
      ``os.sendfile``, and the SERVER's own wire counters (scraped over
      ``/healthz``) must show ~0 Python payload bytes per frame
      (zero-tolerance baseline rule); (B) a compressed connection — the
      downgrade materializes + re-encodes, the same counters show the
      full frame. Server CPU per frame comes from ``/proc/<pid>/stat``
      around each drain — the ISSUE 16 cost-model numbers, measured on
      the process that matters.
    - ``data_plane_worker_scaling``: aggregate relay fps through ONE
      port with ``--workers`` 1 vs 2: four named queues rendezvous-
      pinned 2+2, load driven by two client PROCESSES (the bench
      process's GIL must not cap the thing being measured). The
      deterministic rendezvous spread over 64 names rides along as the
      per-worker message-count balance proxy; ``cores`` is recorded so
      a 1-core box's flat speedup reads as the box, not the plane.
    - ``data_plane_kill_worker``: 2-worker durable fleet, enqueue, then
      kill -9 EVERY worker in turn (so the queue's owner dies exactly
      once, whichever worker reuseport landed it on), drain after the
      respawns: ``lost`` MUST be 0.
    """
    import json as _json
    import shutil
    import signal
    import socket as _socket
    import subprocess
    import tempfile
    import threading as _threading
    import urllib.request

    from psana_ray_tpu.records import FrameRecord
    from psana_ray_tpu.transport.tcp import TcpQueueClient
    from psana_ray_tpu.transport.workers import queue_owner

    scratch = tempfile.mkdtemp(prefix="bench_data_plane_")
    repo = os.path.dirname(os.path.abspath(__file__))
    clk = os.sysconf("SC_CLK_TCK")

    def free_port():
        s = _socket.socket()
        try:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]
        finally:
            s.close()

    def start_server(extra, tag):
        port_file = os.path.join(scratch, f"port_{tag}")
        if os.path.exists(port_file):
            os.remove(port_file)
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "psana_ray_tpu.queue_server",
                "--host", "127.0.0.1", "--port", "0",
                "--port_file", port_file, "--stall_poll_s", "0",
            ] + extra,
            cwd=repo, stderr=subprocess.DEVNULL,
        )
        deadline = time.monotonic() + 60
        while not os.path.exists(port_file):
            if proc.poll() is not None or time.monotonic() > deadline:
                raise RuntimeError(f"queue server ({tag}) failed to start")
            time.sleep(0.05)
        return proc, int(open(port_file).read())

    def stop_server(proc):
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)

    def scrape(mport):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{mport}/healthz", timeout=10
        ) as r:
            return _json.loads(r.read())

    def proc_cpu_s(pid):
        with open(f"/proc/{pid}/stat", "rb") as f:
            fields = f.read().decode("latin-1").rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / clk  # utime + stime

    # ---- spliced vs materialized drain ----------------------------------
    shape = (2, 32, 32) if smoke else (16, 352, 384)  # epix10k2M u16
    n_frames = 16 if smoke else 60
    seg_bytes = (1 << 22) if smoke else (1 << 26)
    rng = np.random.default_rng(17)
    panels = rng.integers(0, 4096, size=shape, dtype=np.uint16)
    frame_bytes = panels.nbytes
    mport = free_port()
    srv, port = start_server(
        [
            "--durable_dir", os.path.join(scratch, "splice"),
            "--ram_items", "1", "--fsync", "none",
            "--segment_bytes", str(seg_bytes), "--queue_size", "500",
            "--metrics_host", "127.0.0.1", "--metrics_port", str(mport),
        ],
        "splice",
    )
    splice_rows = {}
    try:
        for leg, codec in (("spliced", None), ("materialized", "shuffle-rle")):
            qname = f"q_{leg}"
            prod = TcpQueueClient(
                "127.0.0.1", port, namespace="dp", queue_name=qname,
                reconnect_tries=1,
            )
            for i in range(n_frames):
                if not prod.put_pipelined(
                    FrameRecord(0, i, panels, 9.5),
                    deadline=time.monotonic() + 120,
                ):
                    raise RuntimeError("producer starved out")
            if not prod.flush_puts(deadline=time.monotonic() + 120):
                raise RuntimeError("put window never drained")
            prod.disconnect()
            # everything past the 1-item RAM window now sits spilled in
            # the log; the drain below is the measured window
            snap0, cpu0 = scrape(mport), proc_cpu_s(srv.pid)
            cons = TcpQueueClient(
                "127.0.0.1", port, namespace="dp", queue_name=qname,
                reconnect_tries=1, codec=codec,
            )
            seen = 0
            t0 = time.perf_counter()
            while seen < n_frames:
                batch = cons.get_batch(16, timeout=15.0)
                if not batch:
                    break
                seen += len(batch)
            dt = time.perf_counter() - t0
            cpu1, snap1 = proc_cpu_s(srv.pid), scrape(mport)
            cons.disconnect()
            if seen != n_frames:
                raise RuntimeError(f"{leg} drain saw {seen}/{n_frames}")
            w0 = snap0.get("wire", {})
            w1 = snap1.get("wire", {})
            py_bytes = (
                w1.get("bytes_copied_total", 0) - w0.get("bytes_copied_total", 0)
            ) / n_frames
            row = {
                "drain_fps": round(seen / dt, 1),
                "py_bytes_per_frame": round(py_bytes, 1),
                "cpu_ns_per_frame": round((cpu1 - cpu0) * 1e9 / n_frames, 0),
            }
            if leg == "spliced":
                s0 = snap0.get("splice", {})
                s1 = snap1.get("splice", {})
                row["spliced_frames"] = (
                    s1.get("spliced_frames_total", 0)
                    - s0.get("spliced_frames_total", 0)
                )
                row["fallbacks"] = (
                    s1.get("fallback_total", 0) - s0.get("fallback_total", 0)
                )
            splice_rows[leg] = row
            log(
                f"data-plane [{leg} drain, u16 {shape}]: "
                f"{row['drain_fps']:.0f} fps, "
                f"{row['py_bytes_per_frame'] / 1e3:.1f} kB py-bytes/frame "
                f"(frame {frame_bytes / 1e3:.0f} kB), "
                f"{row['cpu_ns_per_frame'] / 1e3:.0f} us server-CPU/frame"
            )
        final = scrape(mport).get("splice", {})
        splice_rows["sendfile_capable"] = bool(final.get("capable", 0))
        splice_rows["frame_nbytes"] = frame_bytes
    finally:
        stop_server(srv)
    extras["data_plane_splice"] = splice_rows

    have_reuseport = hasattr(_socket, "SO_REUSEPORT")

    # ---- worker scaling (1 vs 2 workers, one port) ----------------------
    if have_reuseport:
        # queues pinned 2+2 under 2 workers (the exact rendezvous map is
        # pinned in tests/test_workers.py): q0,q1 -> w0; q3,q5 -> w1
        q_by_driver = (("q0", "q1"), ("q3", "q5"))
        n_per_q = 80 if smoke else 400
        drv_shape = "1x64x64"  # small frames: per-frame Python cost dominates
        scaling = {"cores": os.cpu_count() or 1}
        for n_workers in (1, 2):
            fsrv, fport = start_server(
                ["--workers", str(n_workers), "--queue_size", "256"],
                f"scale{n_workers}",
            )
            try:
                drivers = [
                    subprocess.Popen(
                        [
                            sys.executable, os.path.join(
                                repo, "tools", "relay_driver.py"
                            ),
                            str(fport), str(n_per_q), ",".join(qs), drv_shape,
                        ],
                        cwd=repo, stdout=subprocess.PIPE,
                        stderr=subprocess.DEVNULL,
                    )
                    for qs in q_by_driver
                ]
                total, wall = 0, 0.0
                for d in drivers:
                    out, _ = d.communicate(timeout=300)
                    if d.returncode != 0:
                        raise RuntimeError("relay driver failed")
                    frames, dt = out.split()
                    total += int(frames)
                    wall = max(wall, float(dt))
                if total != n_per_q * 4:
                    raise RuntimeError(f"scaling saw {total}/{n_per_q * 4}")
                scaling[f"workers_{n_workers}_agg_fps"] = round(total / wall, 1)
            finally:
                stop_server(fsrv)
        s1x = scaling["workers_1_agg_fps"]
        s2x = scaling["workers_2_agg_fps"]
        scaling["speedup"] = round(s2x / s1x, 3) if s1x else None
        spread = [0, 0]
        for i in range(64):
            spread[queue_owner("bench", f"stream-{i}", 2)] += 1
        scaling["balance"] = {"w0": spread[0], "w1": spread[1]}
        extras["data_plane_worker_scaling"] = scaling
        log(
            f"data-plane [worker scaling, u16 8kB frames, "
            f"{scaling['cores']} core(s)]: 1w {s1x:.0f} fps, 2w {s2x:.0f} "
            f"fps, speedup {scaling['speedup']}x, balance {scaling['balance']}"
            + (
                " (single-core box: flat speedup is the box, not the plane)"
                if (scaling["cores"] or 1) < 2 else ""
            )
        )
    else:
        log("data-plane: SO_REUSEPORT unavailable — worker rows skipped")

    # ---- kill -9 every worker: lost MUST be 0 ---------------------------
    if have_reuseport and os.path.isdir("/proc"):
        kill_frames = 16 if smoke else 48
        small = rng.integers(0, 4096, size=(2, 32, 32), dtype=np.uint16)
        fsrv, fport = start_server(
            [
                "--workers", "2",
                "--durable_dir", os.path.join(scratch, "kill"),
                "--fsync", "batch", "--fsync_batch_n", "1",
                "--segment_bytes", str(1 << 22), "--queue_size", "500",
            ],
            "kill",
        )
        row = {"produced": kill_frames, "lost": -1}
        try:
            prod = TcpQueueClient(
                "127.0.0.1", fport, namespace="dp", queue_name="q3",
            )
            for i in range(kill_frames):
                if not prod.put(FrameRecord(0, i, small, 9.5)):
                    raise RuntimeError("producer refused")
            prod.disconnect()

            def children():
                pids = []
                for d in os.listdir("/proc"):
                    if not d.isdigit():
                        continue
                    try:
                        with open(f"/proc/{d}/stat", "rb") as f:
                            st = f.read().decode("latin-1")
                        if int(st.rsplit(")", 1)[1].split()[1]) == fsrv.pid:
                            pids.append(int(d))
                    except (OSError, IndexError, ValueError):
                        continue
                return sorted(pids)

            t0 = time.monotonic()
            victims = children()
            if len(victims) != 2:
                raise RuntimeError(f"expected 2 workers, saw {victims}")
            for victim in victims:
                os.kill(victim, signal.SIGKILL)
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    cur = children()
                    if victim not in cur and len(cur) == 2:
                        break
                    time.sleep(0.05)
                else:
                    raise RuntimeError(f"worker {victim} never respawned")
            respawn_s = time.monotonic() - t0

            cons = TcpQueueClient(
                "127.0.0.1", fport, namespace="dp", queue_name="q3",
            )
            recovered = []
            while True:
                batch = cons.get_batch(64, timeout=2.0)
                if not batch:
                    break
                recovered.extend(r.event_idx for r in batch)
                if len(recovered) >= kill_frames:
                    break
            cons.disconnect()
            uniq = set(recovered)
            row = {
                "produced": kill_frames,
                "recovered": len(recovered),
                "duplicates": len(recovered) - len(uniq),
                "lost": kill_frames - len(uniq),
                "respawn_s": round(respawn_s, 3),
            }
            log(
                f"data-plane [kill -9 both workers in turn]: {row['lost']} "
                f"lost (MUST be 0), {row['duplicates']} dup(s), respawns "
                f"in {row['respawn_s']}s"
            )
        finally:
            stop_server(fsrv)
            shutil.rmtree(scratch, ignore_errors=True)
        extras["data_plane_kill_worker"] = row
    else:
        shutil.rmtree(scratch, ignore_errors=True)


def _bench_durability(extras, smoke=False):
    """Durability accounting (ISSUE 8, no device):

    - ``durability_overhead``: relay fps through one queue server with
      the segment log OFF vs ``fsync=none`` vs ``fsync=batch`` on
      detector-native u16 frames — the measured durability tax, plus
      RELAY-ADDED copies/frame per row: the log-off relay itself adds
      0.00 (pure zero-copy; the consumer batch-arena copy that makes
      the end-to-end pin 1.00 lives downstream, measured in
      host-datapath), and a log-on row pays EXACTLY +1.00 — the one
      ``encode_into`` memcpy into the mmap'd segment, no intermediate
      bytes.
    - ``durability_kill_restart``: a REAL ``kill -9`` of a durable
      queue-server subprocess mid-stream, restart on the same
      ``--durable_dir``, drain: ``lost`` MUST be 0 and consumption must
      resume at the committed offset (duplicates allowed, holes never).
      Records the recovery wall time (boot scan + re-expose included).
    """
    import shutil
    import signal
    import subprocess
    import tempfile
    import threading as _threading

    from psana_ray_tpu.records import EndOfStream, FrameRecord, is_eos
    from psana_ray_tpu.storage import DurableRingBuffer, SegmentLog
    from psana_ray_tpu.transport import RingBuffer
    from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer
    from psana_ray_tpu.utils.bufpool import WIRE

    shape = (2, 32, 32) if smoke else (16, 352, 384)  # epix10k2M u16
    n_frames = 24 if smoke else 120
    seg_bytes = (1 << 22) if smoke else (1 << 26)
    rng = np.random.default_rng(11)
    pool16 = [rng.integers(0, 4096, size=shape, dtype=np.uint16) for _ in range(4)]
    scratch = tempfile.mkdtemp(prefix="bench_durable_")

    def run_relay(mode: str):
        """One producer->server->consumer pass; fps + copies/frame."""
        if mode == "log-off":
            backing = RingBuffer(32)
        else:
            log = SegmentLog(
                os.path.join(scratch, f"overhead_{mode}"),
                segment_bytes=seg_bytes, fsync=mode, name=mode,
            )
            backing = DurableRingBuffer(log, maxsize=32, name=mode)
        srv = TcpQueueServer(backing, host="127.0.0.1").serve_background()
        prod = TcpQueueClient("127.0.0.1", srv.port)
        cons = TcpQueueClient("127.0.0.1", srv.port)
        try:
            def produce():
                for i in range(n_frames):
                    rec = FrameRecord(0, i, pool16[i % 4], 9.5)
                    if not prod.put_pipelined(rec, deadline=time.monotonic() + 120):
                        raise RuntimeError("producer starved out")
                if not prod.flush_puts(deadline=time.monotonic() + 120):
                    raise RuntimeError("put window never drained")
                if not prod.put_wait(EndOfStream(total_events=n_frames), timeout=120):
                    raise RuntimeError("EOS delivery timed out")

            c0 = WIRE.stats()
            t = _threading.Thread(target=produce, daemon=True)
            seen = 0
            t0 = time.perf_counter()
            t.start()
            while True:
                batch = cons.get_batch(16, timeout=10.0)
                if not batch:
                    break
                if any(is_eos(x) for x in batch):
                    seen += sum(0 if is_eos(x) else 1 for x in batch)
                    break
                seen += len(batch)
            dt = time.perf_counter() - t0
            t.join(timeout=10)
            c1 = WIRE.stats()
            copies = (c1["copies_total"] - c0["copies_total"]) / max(1, seen)
            if seen != n_frames:
                raise RuntimeError(f"relay saw {seen}/{n_frames} frames")
            return seen / dt, copies
        finally:
            for c in (prod, cons):
                try:
                    c.disconnect()
                except Exception:
                    pass
            srv.shutdown()
            log_ = getattr(backing, "log", None)
            if log_ is not None:
                log_.close()

    rows = []
    for mode in ("log-off", "none", "batch"):
        fps, copies = run_relay(mode)
        rows.append({
            "mode": mode, "fps": round(fps, 1),
            "copies_per_frame": round(copies, 3),
        })
        log(
            f"durability [relay, u16 {shape}, fsync={mode}]: {fps:.0f} fps, "
            f"{copies:.2f} copies/frame"
        )
    base = rows[0]["fps"]
    if base > 0:
        for row in rows[1:]:
            row["overhead_pct"] = round(100.0 * (1 - row["fps"] / base), 1)
    extras["durability_overhead"] = rows

    # -- kill -9 + restart row (lost MUST be 0) ---------------------------
    durable_dir = os.path.join(scratch, "kill")
    port_file = os.path.join(scratch, "port")
    kill_frames = 16 if smoke else 80

    def start_server():
        if os.path.exists(port_file):
            os.remove(port_file)
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "psana_ray_tpu.queue_server",
                "--port", "0", "--durable_dir", durable_dir,
                "--fsync", "batch", "--fsync_batch_n", "8",
                "--port_file", port_file, "--stall_poll_s", "0",
                "--queue_size", "500", "--segment_bytes", str(seg_bytes),
            ],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stderr=subprocess.DEVNULL,
        )
        deadline = time.monotonic() + 60
        while not os.path.exists(port_file):
            if proc.poll() is not None or time.monotonic() > deadline:
                raise RuntimeError("durable queue server failed to start")
            time.sleep(0.05)
        return proc, int(open(port_file).read())

    row = {"produced": kill_frames, "lost": -1}
    proc = None
    try:
        proc, port = start_server()
        prod = TcpQueueClient("127.0.0.1", port, reconnect_tries=1)
        for i in range(kill_frames):
            if not prod.put_pipelined(
                FrameRecord(0, i, pool16[i % 4], 9.5),
                deadline=time.monotonic() + 60,
            ):
                raise RuntimeError("producer starved out")
        if not prod.flush_puts(deadline=time.monotonic() + 60):
            raise RuntimeError("put window never drained")
        cons = TcpQueueClient("127.0.0.1", port, reconnect_tries=1)
        first = cons.get_batch(kill_frames // 3, timeout=30.0)
        cons.size()  # implicit-ack: the committed offset moves
        consumed = [r.event_idx for r in first]

        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        t0 = time.monotonic()
        proc, port = start_server()
        cons2 = TcpQueueClient("127.0.0.1", port, reconnect_tries=1)
        recovered = []
        while True:
            batch = cons2.get_batch(64, timeout=1.0)
            if not batch:
                break
            recovered.extend(r.event_idx for r in batch)
        recovery_s = time.monotonic() - t0
        all_seen = set(consumed) | set(recovered)
        row = {
            "produced": kill_frames,
            "consumed_before_kill": len(consumed),
            "recovered_after_restart": len(recovered),
            "duplicates": len(consumed) + len(recovered) - len(all_seen),
            "lost": kill_frames - len(all_seen),
            "resume_offset": min(recovered) if recovered else None,
            "recovery_s": round(recovery_s, 3),
        }
        for c in (prod, cons2):
            try:
                c.disconnect()
            except Exception:
                pass
        log(
            f"durability [kill -9 + restart]: {row['lost']} lost "
            f"(MUST be 0), resumed at offset {row['resume_offset']} after "
            f"consuming {row['consumed_before_kill']}, "
            f"{row['duplicates']} dup(s), recovery {row['recovery_s']}s"
        )
    finally:
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        shutil.rmtree(scratch, ignore_errors=True)
    extras["durability_kill_restart"] = row


def _bench_replication(extras, smoke=False):
    """Chain replication (ISSUE 11, no device):

    - ``replication_overhead``: relay fps through one durable queue
      server with replication OFF vs ON (owner + follower, the
      replicated ack floor gating every producer ack) on
      detector-native u16 frames — the measured price of surviving the
      machine, not just the process.
    - ``replication_kill_delete_disk``: the acceptance row — a
      3-server replicated cluster under windowed load; mid-run the
      COORDINATOR server is shut down AND its ``--durable_dir`` is
      deleted. ``lost`` MUST read 0 (the promoted followers serve the
      backlog), replay from=begin still serves a retained range, and
      the consumer group's generation/drained state survives the
      coordinator failover (a stale-generation commit stays fenced).
    """
    import shutil
    import tempfile
    import threading as _threading

    from psana_ray_tpu.cluster.client import ClusterClient
    from psana_ray_tpu.cluster.hashring import partition_owner
    from psana_ray_tpu.cluster.replication import ReplicationManager
    from psana_ray_tpu.records import EndOfStream, FrameRecord, is_eos
    from psana_ray_tpu.storage import DurableRingBuffer, SegmentLog
    from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer

    shape = (2, 32, 32) if smoke else (16, 352, 384)  # epix10k2M u16
    n_frames = 16 if smoke else 80
    seg_bytes = (1 << 22) if smoke else (1 << 26)
    rng = np.random.default_rng(17)
    pool16 = [rng.integers(0, 4096, size=shape, dtype=np.uint16) for _ in range(4)]
    scratch = tempfile.mkdtemp(prefix="bench_repl_")

    def free_port():
        import socket as _socket

        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def durable_factory(durable_dir):
        def factory(ns, name, maxsize):
            log_ = SegmentLog(
                os.path.join(durable_dir, f"{ns}__{name}"),
                segment_bytes=seg_bytes, fsync="none", name=f"{ns}/{name}",
            )
            return DurableRingBuffer(log_, maxsize=maxsize, name=f"{ns}__{name}")

        return factory

    def start_cluster(n, tag, group_store=False):
        dirs = [os.path.join(scratch, f"{tag}{i}") for i in range(n)]
        for d in dirs:
            os.makedirs(d, exist_ok=True)
        ports = [free_port() for _ in range(n)]
        peers = [f"127.0.0.1:{p}" for p in ports]
        servers = []
        for i in range(n):
            mgr = (
                ReplicationManager(dirs[i], peers, peers[i])
                if n > 1 else None
            )
            servers.append(
                TcpQueueServer(
                    host="127.0.0.1", port=ports[i], maxsize=256,
                    queue_factory=durable_factory(dirs[i]),
                    replication=mgr,
                    group_store_path=(
                        os.path.join(dirs[i], "groups.json")
                        if group_store else None
                    ),
                ).serve_background()
            )
        return dirs, ports, peers, servers

    # -- A/B: replication off vs on ---------------------------------------
    def run_relay(replicated: bool):
        n = 2 if replicated else 1
        dirs, ports, peers, servers = start_cluster(
            n, "ab_on" if replicated else "ab_off"
        )
        try:
            qname = "ab_q"
            for i in range(512):  # owner must be server 0 (where we dial)
                if partition_owner(peers, f"ab_q{i}", 0) == peers[0]:
                    qname = f"ab_q{i}"
                    break
            prod = TcpQueueClient(
                "127.0.0.1", ports[0], namespace="b", queue_name=qname
            )
            cons = TcpQueueClient(
                "127.0.0.1", ports[0], namespace="b", queue_name=qname
            )

            def produce():
                for i in range(n_frames):
                    rec = FrameRecord(0, i, pool16[i % 4], 9.5)
                    if not prod.put_pipelined(
                        rec, deadline=time.monotonic() + 120
                    ):
                        raise RuntimeError("producer starved out")
                if not prod.flush_puts(deadline=time.monotonic() + 120):
                    raise RuntimeError("put window never drained")
                if not prod.put_wait(
                    EndOfStream(total_events=n_frames), timeout=120
                ):
                    raise RuntimeError("EOS delivery timed out")

            t = _threading.Thread(target=produce, daemon=True)
            seen = 0
            t0 = time.perf_counter()
            t.start()
            while True:
                batch = cons.get_batch(16, timeout=10.0)
                if not batch:
                    break
                if any(is_eos(x) for x in batch):
                    seen += sum(0 if is_eos(x) else 1 for x in batch)
                    break
                seen += len(batch)
            dt = time.perf_counter() - t0
            t.join(timeout=10)
            for c in (prod, cons):
                try:
                    c.disconnect()
                except Exception:
                    pass
            if seen != n_frames:
                raise RuntimeError(f"relay saw {seen}/{n_frames} frames")
            return seen / dt
        finally:
            for s in servers:
                s.shutdown()

    rows = []
    for replicated in (False, True):
        fps = run_relay(replicated)
        rows.append({
            "replication": "on" if replicated else "off",
            "fps": round(fps, 1),
        })
        log(
            f"replication [relay A/B, u16 {shape}, "
            f"{'on: owner+follower, ack-floor gated' if replicated else 'off'}]: "
            f"{fps:.0f} fps"
        )
    if rows[0]["fps"] > 0:
        rows[1]["overhead_pct"] = round(
            100.0 * (1 - rows[1]["fps"] / rows[0]["fps"]), 1
        )
        log(
            f"replication: ack-floor overhead "
            f"{rows[1]['overhead_pct']}% on {shape} u16 frames "
            f"(every producer ack waits for the follower's log)"
        )
    extras["replication_overhead"] = rows

    # -- acceptance row: kill the coordinator AND delete its disk ---------
    P = 4
    kd_frames = 24 if smoke else 120
    dirs, ports, peers, servers = start_cluster(3, "kd", group_store=True)
    prod_c = cons_c = None
    row = {"produced": kd_frames, "lost": -1}
    try:
        prod_c = ClusterClient(
            peers, queue_name="kdq", n_partitions=P, maxsize=256,
            retain=512, reconnect_tries=1, reconnect_base_s=0.05,
        )
        cons_c = ClusterClient(
            peers, queue_name="kdq", n_partitions=P, maxsize=256,
            group="kdg", reconnect_tries=1, reconnect_base_s=0.05,
        )
        killed_t = {"t": None}
        prod_err = {"err": None}

        def produce():
            try:
                for i in range(kd_frames):
                    rec = FrameRecord(0, i, pool16[i % 4], 9.5)
                    if not prod_c.put_pipelined(
                        rec, deadline=time.monotonic() + 120
                    ):
                        raise RuntimeError(f"producer gave up at frame {i}")
                    if i == kd_frames // 3:
                        killed_t["t"] = time.monotonic()
                        servers[0].shutdown()
                        shutil.rmtree(dirs[0], ignore_errors=True)
                if not prod_c.flush_puts(time.monotonic() + 120):
                    raise RuntimeError("producer flush timed out")
                if not prod_c.put_wait(
                    EndOfStream(0, -1, 1, 1), timeout=120
                ):
                    raise RuntimeError("EOS broadcast timed out")
            except BaseException as e:  # noqa: BLE001 — reported below
                prod_err["err"] = e

        seen = []
        t = _threading.Thread(target=produce, daemon=True)
        t0 = time.perf_counter()
        t.start()
        eos = 0
        reassign_latency = None
        v0 = cons_c.partition_map.version
        deadline = t0 + 600.0
        while not eos and time.perf_counter() < deadline:
            if prod_err["err"] is not None:
                raise RuntimeError(
                    "replication kill-row producer failed; frames were "
                    "never sent, not lost"
                ) from prod_err["err"]
            for item in cons_c.get_batch_stream(32, timeout=0.5):
                if is_eos(item):
                    eos += 1
                else:
                    seen.append(item.event_idx)
            if (
                reassign_latency is None
                and killed_t["t"] is not None
                and cons_c.partition_map.version > v0
            ):
                reassign_latency = time.monotonic() - killed_t["t"]
        t.join(timeout=30.0)
        unique = set(seen)
        lost = sorted(set(range(kd_frames)) - unique)
        # the coordinator's group state survived the failover iff a
        # stale-generation commit is still FENCED on the new coordinator
        info = cons_c._rpc({"op": "info", "group": "kdg"})
        stale = cons_c._rpc({
            "op": "drained", "group": "kdg", "member": "bench-zombie",
            "generation": int(info.get("generation", 0)) - 1,
            "partition": 0,
        })
        # replay from=begin on the survivors: the retained range must
        # still serve (the promoted followers hold the logs)
        replayer = ClusterClient(
            peers[1:], queue_name="kdq", n_partitions=P, maxsize=256,
            reconnect_tries=1, reconnect_base_s=0.05,
        )
        replayed = set()
        try:
            replayer.replay_open(from_offset="begin", group="bench-audit")
            empty = 0
            while empty < 3:
                batch = replayer.get_batch(64, timeout=1.0)
                if batch:
                    replayed |= {
                        b.event_idx for b in batch if not is_eos(b)
                    }
                    empty = 0
                else:
                    empty += 1
        finally:
            replayer.disconnect()
        row = {
            "produced": kd_frames,
            "consumed": len(unique),
            "redelivered": len(seen) - len(unique),
            "lost": len(lost),
            "reassign_latency_s": (
                round(reassign_latency, 3)
                if reassign_latency is not None else None
            ),
            "group_generation": info.get("generation"),
            "group_drained": len(info.get("drained", ())),
            "stale_commit_fenced": bool(stale.get("fenced")),
            "replay_served": len(replayed),
        }
        if lost:
            raise RuntimeError(
                f"replication kill+delete-disk LOST {len(lost)} frames: "
                f"{lost[:10]}..."
            )
        log(
            f"replication [kill coordinator + delete its durable_dir]: "
            f"{row['lost']} lost (MUST be 0), "
            f"{row['redelivered']} redelivered, reassign "
            f"{row['reassign_latency_s']}s, group gen "
            f"{row['group_generation']} with {row['group_drained']}/{P} "
            f"drained survived (stale commit fenced="
            f"{row['stale_commit_fenced']}), replay served "
            f"{row['replay_served']} frame(s)"
        )
    finally:
        for c in (prod_c, cons_c):
            if c is not None:
                try:
                    c.disconnect()
                except Exception:
                    pass
        for s in servers:
            try:
                s.shutdown()
            except Exception:
                pass
        shutil.rmtree(scratch, ignore_errors=True)
    extras["replication_kill_delete_disk"] = row


def _bench_serving(extras, smoke=False):
    """SLO-aware serving gateway under overload (ISSUE 12).

    Device model: the dispatch callable SLEEPS the operating-point
    service time, with the measured B1...B8 frontier scaled 8x so
    scheduler jitter on this CPU-share-throttled box stays small
    relative to the service times (the control behavior — what gets
    admitted, shed, batched — is scale-invariant; the absolute fps are
    the scaled device's, stated as such). Sustainable capacity is
    MEASURED first (back-to-back B8 dispatches through the same sleep),
    not taken from the table.

    Rows (``serving_overload`` / ``serving_idle``):

    - ``uncontrolled`` — bursty 3-tenant open-loop load at ~2x measured
      capacity into a no-shed FIFO dispatcher: the queue grows without
      bound and p99 sojourn blows past the SLO (the failure mode the
      gateway exists for);
    - ``gateway`` — same offered load through admission control +
      deadline shedding + WDRR (weights 2:1:1): admitted-work p99 must
      stay inside the SLO, goodput >= 80% of measured capacity,
      per-tenant goodput within +-10% of the weight shares, and
      offered == completed + shed (shed is loud and counted; admitted
      frames are never lost);
    - ``serving_idle`` — single tenant far below capacity: every
      dispatch at the B1 operating point (no batching tax when there is
      no load), plus the zero-copy pins through the gateway transport
      path (serve_queue + make_batch_dispatch over a real TCP relay:
      copies/frame must be exactly 1.00, steady-state pool churn 0).
    """
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests")
    )
    import threading as _threading

    from faultproxy import OpenLoopLoad, arrival_schedule

    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.serving import (
        GatewayTelemetry,
        ServingGateway,
        SloPolicy,
        make_batch_dispatch,
    )
    from psana_ray_tpu.transport import RingBuffer
    from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer
    from psana_ray_tpu.utils.bufpool import BufferPool, WIRE

    SCALE = 8.0  # sleep-device scale over the measured device frontier
    OPS = tuple((b, ms * SCALE) for b, ms in ((1, 0.89), (2, 1.43), (4, 2.45), (8, 4.33)))
    SVC = dict(OPS)
    SLO_MS = 300.0
    WEIGHTS = {"t0": 2, "t1": 1, "t2": 1}
    rng = np.random.default_rng(12)
    frame = FrameRecord(
        0, 0, rng.integers(0, 4096, size=(2, 8, 8), dtype=np.uint16), 9.5
    )

    def device(recs, batch_size):
        time.sleep(SVC[batch_size] / 1000.0)

    # -- measure sustainable capacity on THIS box's sleep granularity -----
    t0 = time.perf_counter()
    n_cal = 4 if smoke else 16
    for _ in range(n_cal):
        device([frame] * 8, 8)
    cal_elapsed = time.perf_counter() - t0
    capacity_fps = (8 * n_cal) / cal_elapsed
    # seed the policy table with the MEASURED per-batch time (table x
    # the box's sleep-oversleep factor): measure-then-control starts
    # from measurement, not the nominal table — the online EWMA keeps
    # refining from there
    oversleep = (cal_elapsed / n_cal * 1000.0) / SVC[8]
    OPS_MEASURED = tuple((b, ms * oversleep) for b, ms in OPS)
    log(f"serving: measured B8 capacity {capacity_fps:.0f} fps "
        f"(sleep-device, {SCALE:.0f}x-scaled frontier, oversleep "
        f"x{oversleep:.3f})")

    # -- overload A/B: 3 tenants, staggered bursts at ~2x capacity --------
    duration_s = 2.0 if smoke else 6.0
    rate_per_tenant = 2.0 * capacity_fps / 3.0
    # period short enough that every tenant's admitted backlog bridges
    # the inter-burst gap (the smallest share's cap is ~1 B8 batch =
    # ~123 ms of its share-rate drain vs a ~112 ms gap), so the device
    # stays fed >= 80% while the arrivals stay violently bursty;
    # synchronized phases keep the tenants statistically identical (a
    # staggered start hands the first tenant a solo transient that
    # skews the measured shares)
    period_s = 0.15

    def tenant_schedules():
        return {
            t: arrival_schedule(
                "burst", rate_per_tenant, duration_s,
                burst_factor=4.0, period_s=period_s,
            )
            for t in WEIGHTS
        }

    def run_overload(controlled: bool):
        policy = SloPolicy(
            slo_ms=SLO_MS if controlled else 1e9,
            operating_points=OPS_MEASURED,
            shed_margin=0.85,
        )
        gw = ServingGateway(
            device, policy=policy, weights=WEIGHTS,
            telemetry=GatewayTelemetry(register=False),
        )
        stop = _threading.Event()
        loop = _threading.Thread(target=gw.run, args=(stop,), daemon=True)
        loop.start()
        t_start = time.perf_counter()
        offered = OpenLoopLoad(
            lambda tenant: gw.offer(frame, tenant=tenant), tenant_schedules()
        ).run(timeout_s=duration_s + 120.0)
        gw.drain(deadline_s=60.0 if controlled else 10.0)
        elapsed = time.perf_counter() - t_start
        stop.set()
        loop.join(timeout=5.0)
        s = gw.telemetry.stats()
        total_offered = sum(offered.values())
        shares = gw.telemetry.tenant_goodput()
        total_good = max(1, sum(shares.values()))
        row = {
            "mode": "gateway" if controlled else "uncontrolled",
            "slo_ms": SLO_MS,
            "offered": total_offered,
            "admitted": s["admitted_total"],
            "completed": s["completed_total"],
            "shed": s["shed_total"],
            "shed_by_path": gw.telemetry.shed_by_path(),
            "backlog_left": gw.backlog(),
            "goodput_fps": round(s["goodput_total"] / elapsed, 1),
            "capacity_fps": round(capacity_fps, 1),
            "p99_admitted_ms": max(
                [s[t]["p99_ms"] for t in WEIGHTS if t in s] or [0.0]
            ),
            "slo_attainment": s["slo_attainment"],
            "tenant_goodput_share": {
                t: round(shares.get(t, 0) / total_good, 3) for t in WEIGHTS
            },
            "conserved": (
                s["offered_total"]
                == s["completed_total"] + s["shed_total"] + gw.backlog()
            ),
        }
        return row

    rows = []
    for controlled in (False, True):
        row = run_overload(controlled)
        rows.append(row)
        log(
            f"serving [{row['mode']}, 3 tenants {tuple(WEIGHTS.values())}, "
            f"burst x4 @ {2.0:.1f}x capacity]: p99 {row['p99_admitted_ms']:.0f} ms "
            f"(SLO {SLO_MS:.0f}), goodput {row['goodput_fps']:.0f}/"
            f"{row['capacity_fps']:.0f} fps, shed {row['shed']}/"
            f"{row['offered']}, shares {row['tenant_goodput_share']}"
        )
    extras["serving_overload"] = rows
    base, gwy = rows
    checks = {
        "baseline_blows_slo": base["p99_admitted_ms"] > SLO_MS,
        "gateway_p99_in_slo": gwy["p99_admitted_ms"] <= SLO_MS,
        "goodput_ge_80pct_capacity": (
            gwy["goodput_fps"] >= 0.8 * capacity_fps
        ),
        "tenant_shares_within_10pct": all(
            abs(gwy["tenant_goodput_share"][t] - w / sum(WEIGHTS.values()))
            <= 0.1 * (w / sum(WEIGHTS.values()))
            for t, w in WEIGHTS.items()
        ),
        "conserved": base["conserved"] and gwy["conserved"],
    }
    extras["serving_overload_acceptance"] = checks
    log(f"serving acceptance: {checks}")

    # -- idle row: B1 latency + the zero-copy pins through the gateway ----
    n_idle = 8 if smoke else 24
    pool = BufferPool()
    q = RingBuffer(64)
    srv = TcpQueueServer(q, host="127.0.0.1", pool=pool).serve_background()
    prod = TcpQueueClient("127.0.0.1", srv.port, pool=pool)
    cons = TcpQueueClient(
        "127.0.0.1", srv.port, pool=pool, tenant="idle", tenant_weight=1
    )
    batch_sizes = []

    def consume(batch):
        batch_sizes.append(batch.batch_size)

    gw = ServingGateway(
        make_batch_dispatch(consume),
        policy=SloPolicy(slo_ms=SLO_MS, operating_points=OPS),
        telemetry=GatewayTelemetry(register=False),
    )
    try:
        idle_gap_s = SVC[8] / 1000.0 * 2  # arrivals far apart: no backlog

        def produce():
            for i in range(n_idle):
                assert prod.put_wait(
                    FrameRecord(0, i, frame.panels, 9.5), timeout=30
                )
                time.sleep(idle_gap_s)
            assert prod.put_wait(EndOfStream(total_events=n_idle), timeout=30)

        t = _threading.Thread(target=produce, daemon=True)
        c0 = WIRE.stats()
        t.start()
        gw.serve_queue(cons, max_wait_s=60.0)
        t.join(timeout=30)
        d = WIRE.stats()
        copies = (d["copies_total"] - c0["copies_total"]) / max(1, n_idle)
        s = gw.telemetry.stats()
        lat = s.get("default", {}).get("p99_ms", 0.0)
        idle_row = {
            "frames": n_idle,
            "completed": s["completed_total"],
            "b1_dispatches": sum(1 for b in batch_sizes if b == 1),
            "dispatches": len(batch_sizes),
            "p99_ms": lat,
            "copies_per_frame": round(copies, 2),
            "pool_churn_misses": pool.stats()["churn_misses"],
            "at_b1_operating_point": all(b == 1 for b in batch_sizes),
        }
        extras["serving_idle"] = idle_row
        log(
            f"serving [idle single-tenant]: {idle_row['b1_dispatches']}/"
            f"{idle_row['dispatches']} dispatches at B1, p99 "
            f"{lat:.1f} ms, copies/frame {idle_row['copies_per_frame']:.2f}, "
            f"pool churn {idle_row['pool_churn_misses']}"
        )
    finally:
        prod.disconnect()
        cons.disconnect()
        srv.shutdown()


def _bench_connection_scaling(extras, smoke=False):
    """C10K row (ISSUE 6): fps and RSS delta at 16 / 128 / 1024 streamed
    subscribers on loopback. (The thread-per-connection A/B is gone with
    the legacy mode itself — ISSUE 7; PERF_NOTES keeps the last measured
    comparison for the record.)

    Each subscriber is a raw streamed socket (subscribe 'M', cumulative
    'K' acks, final 'F') multiplexed on ONE client-side selector — a
    full TcpQueueClient per subscriber would measure client-object
    overhead, not the server. One producer pushes 16 KB u16 frames
    through one shared queue; fps is total fleet delivery rate.

    RSS methodology (ISSUE 7 satellite — the PR 6 run read a nonsense
    per-conn RSS at 16 subscribers): each RSS figure is the MEDIAN of
    repeated /proc samples around a gc.collect(), and rows whose TOTAL
    delta is under the allocator noise floor are marked
    ``rss_noise_floored`` — at 16 connections the real footprint
    (~1-4 KB/conn) is far below what one arena decision can move, so
    the per-conn division there is noise, not signal; the 128/1024 rows
    are the measurement.

    Acceptance (ISSUE 6): at 1024 subscribers the event loop sustains
    >=80% of its own 16-subscriber fps, thread count stays flat, and
    per-connection RSS growth stays <=64 KB. Recorded per row:
    ``{conns, fps, rss_kb_per_conn, rss_noise_floored, thread_delta}``.
    """
    import gc
    import selectors as _selectors
    import socket as _socket
    import statistics as _statistics
    import struct as _struct
    import threading as _threading

    from psana_ray_tpu.records import FrameRecord
    from psana_ray_tpu.transport import RingBuffer
    from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer

    # total-delta threshold below which a per-conn RSS reading is
    # allocator noise: one malloc arena / pool-trim decision moves
    # O(MB), so deltas under ~2 MB say nothing about per-conn cost
    RSS_NOISE_FLOOR_KB = 2048

    def rss_kb_median(samples=5):
        """Median of repeated RSS samples with a collect first — one
        sample reads whatever the allocator just did; the median of
        several (with GC settled) reads the footprint."""
        gc.collect()
        vals = []
        for _ in range(samples):
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        vals.append(int(line.split()[1]))
                        break
        return _statistics.median(vals) if vals else 0

    shape = (2, 64, 64)  # 16 KB u16 frames: wire work without bandwidth domination
    rng = np.random.default_rng(11)
    frames = [
        FrameRecord(0, i, rng.integers(0, 4096, size=shape, dtype=np.uint16), 1.0)
        for i in range(4)
    ]
    n_frames = 200 if smoke else 2000
    counts = (4, 16) if smoke else (16, 128, 1024)

    def run_fleet(n_subs):
        q = RingBuffer(256)
        srv = TcpQueueServer(q, host="127.0.0.1").serve_background()
        sel = _selectors.DefaultSelector()
        socks = []
        prod = None
        try:
            threads0 = _threading.active_count()
            rss0 = rss_kb_median()
            for _ in range(n_subs):
                s = _socket.create_connection(("127.0.0.1", srv.port), timeout=30.0)
                s.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
                s.sendall(b"M" + _struct.pack("<I", 8))
                s.setblocking(False)
                st = {"sock": s, "buf": bytearray(), "delivered": 0}
                sel.register(s, _selectors.EVENT_READ, st)
                socks.append(st)
            rss_delta = rss_kb_median() - rss0
            rss_per_conn = rss_delta / n_subs
            noise_floored = abs(rss_delta) < RSS_NOISE_FLOOR_KB
            thread_delta = _threading.active_count() - threads0
            prod = TcpQueueClient("127.0.0.1", srv.port)

            def produce():
                for i in range(n_frames):
                    if not prod.put_wait(frames[i % 4], timeout=120.0):
                        return

            got = 0
            t = _threading.Thread(target=produce, daemon=True)
            t0 = time.perf_counter()
            t.start()
            deadline = t0 + 600.0
            while got < n_frames and time.perf_counter() < deadline:
                for key, _m in sel.select(timeout=0.25):
                    st = key.data
                    try:
                        data = st["sock"].recv(1 << 16)
                    except (BlockingIOError, InterruptedError):
                        continue
                    if not data:
                        sel.unregister(st["sock"])
                        continue
                    buf = st["buf"]
                    buf += data
                    fresh = 0
                    while len(buf) >= 13 and buf[0:1] == b"1":
                        seq, ln = _struct.unpack_from("<QI", buf, 1)
                        if len(buf) < 13 + ln:
                            break
                        st["delivered"] = seq
                        del buf[: 13 + ln]
                        fresh += 1
                    if fresh:
                        got += fresh
                        st["sock"].sendall(
                            b"K" + _struct.pack("<Q", st["delivered"])
                        )
            dt = time.perf_counter() - t0
            t.join(timeout=10.0)
            if got < n_frames:
                raise RuntimeError(
                    f"fleet starved: {got}/{n_frames} frames at "
                    f"{n_subs} subscribers"
                )
            return {
                "mode": "evloop",
                "conns": n_subs,
                "fps": round(n_frames / dt, 1),
                "rss_kb_per_conn": round(rss_per_conn, 2),
                "rss_noise_floored": noise_floored,
                "thread_delta": thread_delta,
            }
        finally:
            for st in socks:
                try:
                    st["sock"].setblocking(True)
                    st["sock"].sendall(
                        b"K" + _struct.pack("<Q", st["delivered"]) + b"F"
                    )
                except OSError:
                    pass
                try:
                    st["sock"].close()
                except OSError:
                    pass
            sel.close()
            if prod is not None:
                try:
                    prod.disconnect()
                except Exception:
                    pass
            srv.shutdown()

    rows = []
    for n in counts:
        row = run_fleet(n)
        rows.append(row)
        rss_note = " (noise-floored)" if row["rss_noise_floored"] else ""
        log(
            f"connection-scaling [{row['conns']} subs]: "
            f"{row['fps']:.0f} fps, {row['rss_kb_per_conn']:.1f} "
            f"KB RSS/conn{rss_note}, +{row['thread_delta']} threads"
        )
    extras["connection_scaling"] = rows
    ev = {r["conns"]: r["fps"] for r in rows}
    lo, hi = min(ev), max(ev)
    if hi > lo:
        ratio = ev[hi] / ev[lo]
        extras["connection_scaling_ratio"] = {
            "conns_hi": hi, "conns_lo": lo, "fps_ratio": round(ratio, 3),
        }
        log(
            f"connection-scaling: {hi}-subscriber fps is "
            f"{100 * ratio:.0f}% of the {lo}-subscriber fps "
            f"(acceptance: >=80%, no collapse)"
        )


def _bench_cluster_scaling(extras, smoke=False):
    """Sharded queue service (ISSUE 7): aggregate streamed fps at 1 / 2 /
    4 queue servers, fixed 8-partition logical queue, one windowed-PUT
    producer and one merged-stream consumer — plus a kill-one-server row
    recording reassignment latency and frames redelivered (duplicates
    allowed, loss NEVER).

    Two row families, same PR 5 honesty convention as the streaming
    delay-line rows:

    - **raw loopback**: everything (servers, producer, consumer) shares
      this 2-core box and one interpreter, so the single server is
      nowhere near ITS ceiling and aggregate fps stays flat with server
      count — recorded at parity, exactly like PR 5's "loopback at
      parity" row (no RTT to hide, nothing to shard away).
    - **saturated-relay proxy**: each server's queues share a relay-core
      model capped at a fixed per-frame service rate (a token bucket in
      the serve path — models the Python relay core being the
      bottleneck, which is precisely the deployment regime the cluster
      exists for, per ROADMAP item 2). Capacity then grows with server
      count because each server brings its own (modeled) core; the
      >=2x-at-4-servers acceptance ratio is read HERE. The tier-1
      deterministic message-count proxy lives in tests/test_cluster.py
      (PR 5/6 flake-avoidance convention); a slow-marked test pins this
      same throttled ratio.

    Recorded: ``{family, servers, fps, fps_per_server, duplicates,
    lost}`` rows plus ``{reassign_latency_s, redelivered, lost}`` for
    the kill row (raw family — failover semantics need no model).
    """
    import threading as _threading

    from psana_ray_tpu.cluster.client import ClusterClient
    from psana_ray_tpu.cluster.hashring import PartitionMap
    from psana_ray_tpu.records import EndOfStream, FrameRecord, is_eos
    from psana_ray_tpu.transport import RingBuffer
    from psana_ray_tpu.transport.tcp import TcpQueueServer

    P = 8
    shape = (2, 64, 64)  # 16 KB u16
    rng = np.random.default_rng(13)
    payloads = [
        rng.integers(0, 4096, size=shape, dtype=np.uint16) for _ in range(4)
    ]
    # saturated-relay model: per-server relay core serves this many
    # queue OPS per second (a frame costs ~2: the PUT and the pop).
    # Low enough that 4 modeled servers stay below the 2-core client
    # ceiling (~600-800 fps measured above), so the CLIENTS never cap
    # the ratio the row exists to read.
    RELAY_OPS_PER_S = 250.0

    class _RelayCore:
        """One server's modeled saturated relay core: a token bucket
        shared by every queue on that server."""

        def __init__(self, ops_per_s):
            self._interval = 1.0 / ops_per_s
            self._next = 0.0
            self._lock = _threading.Lock()

        def tick(self, n=1):
            with self._lock:
                now = time.monotonic()
                t = max(self._next, now)
                self._next = t + n * self._interval
            delay = t - now
            if delay > 0:
                time.sleep(delay)

    class _ThrottledRing(RingBuffer):
        def __init__(self, maxsize, core, name=None):
            super().__init__(maxsize, name=name)
            self._core = core

        def put(self, item):
            self._core.tick()
            return super().put(item)

        def get_batch(self, max_items, timeout=0.0):
            items = super().get_batch(max_items, timeout)
            if items:
                self._core.tick(len(items))
            return items

    def start_servers(n, throttled):
        servers = []
        for _ in range(n):
            if throttled:
                core = _RelayCore(RELAY_OPS_PER_S)
                factory = (
                    lambda ns, name, maxsize, _c=core:
                    _ThrottledRing(maxsize, _c, name=f"{ns}__{name}")
                )
                backing = _ThrottledRing(256, core)
            else:
                factory = None
                backing = RingBuffer(256)
            servers.append(
                TcpQueueServer(
                    backing, host="127.0.0.1", maxsize=256,
                    queue_factory=factory,
                ).serve_background()
            )
        addrs = [f"127.0.0.1:{s.port}" for s in servers]
        # balanced map: no server above fair share +1 (deterministic
        # given the ports; mirrors the tier-1 proxy's precondition)
        cap = -(-P // n) + (1 if n > 1 else P)
        for i in range(512):
            qname = f"bench_cluster_{i}"
            m = PartitionMap.compute(addrs, qname, P)
            if max(len(m.partitions_on(a)) for a in addrs) <= cap:
                return servers, addrs, qname
        return servers, addrs, "bench_cluster_0"

    def run_cluster(n_servers, n_frames, kill_one=False, throttled=False):
        servers, addrs, qname = start_servers(n_servers, throttled)
        prod_c = cons_c = None
        try:
            prod_c = ClusterClient(
                addrs, queue_name=qname, n_partitions=P, maxsize=256,
                retain=512, reconnect_tries=1, reconnect_base_s=0.05,
            )
            cons_c = ClusterClient(
                addrs, queue_name=qname, n_partitions=P, maxsize=256,
                reconnect_tries=1, reconnect_base_s=0.05,
            )
            kill_at = n_frames // 3
            killed_t = {"t": None}
            prod_err = {"err": None}

            def produce():
                # any give-up is recorded so the consumer loop fails
                # FAST with the right diagnosis (a producer timeout is
                # not a durability violation — without this, the run
                # would burn the full consumer deadline and then
                # misreport the missing frames as LOST)
                try:
                    for i in range(n_frames):
                        rec = FrameRecord(0, i, payloads[i % 4], 1.0)
                        if not prod_c.put_pipelined(
                            rec, deadline=time.monotonic() + 120.0
                        ):
                            raise RuntimeError(
                                f"producer gave up at frame {i}: put "
                                f"window still full after 120 s"
                            )
                        if kill_one and i == kill_at:
                            killed_t["t"] = time.monotonic()
                            servers[-1].shutdown()
                    if not prod_c.flush_puts(time.monotonic() + 120.0):
                        raise RuntimeError("producer flush timed out")
                    if not prod_c.put_wait(
                        EndOfStream(0, -1, 1, 1), timeout=120.0
                    ):
                        raise RuntimeError("EOS broadcast timed out")
                except BaseException as e:  # noqa: BLE001 — reported below
                    prod_err["err"] = e

            seen = []
            t = _threading.Thread(target=produce, daemon=True)
            t0 = time.perf_counter()
            t.start()
            eos = 0
            reassign_latency = None
            v0 = cons_c.partition_map.version
            deadline = t0 + 600.0
            while not eos and time.perf_counter() < deadline:
                if prod_err["err"] is not None:
                    raise RuntimeError(
                        f"cluster-scaling producer failed at "
                        f"{n_servers} servers (kill={kill_one}); frames "
                        f"were never sent, not lost"
                    ) from prod_err["err"]
                for item in cons_c.get_batch_stream(32, timeout=0.5):
                    if is_eos(item):
                        eos += 1
                    else:
                        seen.append(item.event_idx)
                if (
                    kill_one
                    and reassign_latency is None
                    and killed_t["t"] is not None
                    and cons_c.partition_map.version > v0
                ):
                    # consumer adopted the recomputed map and is draining
                    # reassigned partitions: the reassignment is live
                    reassign_latency = time.monotonic() - killed_t["t"]
            dt = time.perf_counter() - t0
            t.join(timeout=30.0)
            unique = set(seen)
            lost = sorted(set(range(n_frames)) - unique)
            row = {
                "family": "relay-proxy" if throttled else "raw",
                "servers": n_servers,
                "partitions": P,
                "frames": n_frames,
                "fps": round(len(unique) / dt, 1),
                "fps_per_server": round(len(unique) / dt / n_servers, 1),
                "duplicates": len(seen) - len(unique),
                "lost": len(lost),
            }
            if kill_one:
                row["reassign_latency_s"] = (
                    round(reassign_latency, 3) if reassign_latency else None
                )
                row["redelivered"] = len(seen) - len(unique)
            if lost:
                raise RuntimeError(
                    f"cluster-scaling LOST {len(lost)} frames at "
                    f"{n_servers} servers (kill={kill_one}): {lost[:10]}..."
                )
            return row
        finally:
            if prod_c is not None:
                try:
                    prod_c.disconnect()
                except Exception:
                    pass
            if cons_c is not None:
                try:
                    cons_c.disconnect()
                except Exception:
                    pass
            for s in servers:
                try:
                    s.shutdown()
                except Exception:
                    pass

    counts = (1, 2) if smoke else (1, 2, 4)
    raw_frames = 300 if smoke else 3000
    proxy_frames = 120 if smoke else 900
    rows = []
    for n in counts:
        row = run_cluster(n, raw_frames)
        rows.append(row)
        log(
            f"cluster-scaling [raw, {n} server(s)]: {row['fps']:.0f} fps "
            f"aggregate, {row['fps_per_server']:.0f} fps/server, "
            f"{row['duplicates']} dup(s), {row['lost']} lost"
        )
    for n in counts:
        row = run_cluster(n, proxy_frames, throttled=True)
        rows.append(row)
        log(
            f"cluster-scaling [relay-proxy, {n} server(s)]: "
            f"{row['fps']:.0f} fps aggregate, "
            f"{row['fps_per_server']:.0f} fps/server"
        )
    proxy = {r["servers"]: r["fps"] for r in rows if r["family"] == "relay-proxy"}
    lo, hi = min(proxy), max(proxy)
    if hi > lo and proxy[lo] > 0:
        ratio = proxy[hi] / proxy[lo]
        extras["cluster_scaling_ratio"] = {
            "family": "relay-proxy", "servers": hi,
            "fps_ratio": round(ratio, 3),
        }
        log(
            f"cluster-scaling: {hi}-server aggregate is {ratio:.2f}x the "
            f"1-server figure under the saturated-relay model "
            f"(acceptance: >=2x at 4 servers on >=2 partitions; raw "
            f"loopback rows stay at parity on this 2-core box — there "
            f"the CLIENT pair is the bottleneck, not the server)"
        )
    kill_row = run_cluster(max(counts), raw_frames, kill_one=True)
    rows.append(dict(kill_row, kill_one_server=True))
    log(
        f"cluster-scaling [kill-one @ {max(counts)} servers]: "
        f"reassignment latency {kill_row.get('reassign_latency_s')}s, "
        f"{kill_row.get('redelivered', 0)} frame(s) redelivered, "
        f"{kill_row['lost']} lost (must be 0)"
    )
    extras["cluster_scaling"] = rows


def _bench_fanin_host(extras, smoke=False):
    """Config 5, host leg — two passes, neither touching the device:

    - ``host_fanin_volume_fps``: detector-native volume (u16 frames,
      epix10k2M + jungfrau4M, count scaled by core count) —
      MEMORY-BANDWIDTH-bound: ~3 frame-sized copies/frame split across 3
      processes timesharing this host's cores, so the ceiling scales with
      core count (``host_cpu_cores`` is recorded; PERF_NOTES.md has the
      breakdown).
    - ``host_fanin_record_rate_fps``: the same merge machinery at small
      frame size (records bound, not bandwidth) — demonstrates the
      per-record pipeline overhead itself clears kHz even on one core.
    """
    from psana_ray_tpu.transport.shm_ring import native_available

    if not native_available():
        log("fan-in host-rate demo skipped: native shm unavailable")
        return

    cores = os.cpu_count() or 1
    extras["host_cpu_cores"] = cores
    # volume auto-scales with cores (round-3 VERDICT weak #4): the pass is
    # memory-bandwidth-bound across 3 processes timesharing the host, so a
    # multi-core host both runs faster AND needs more frames for a stable
    # measuring window — scale the counts so the real number emerges
    # unprompted instead of by PERF_NOTES arithmetic
    scale = max(1, min(cores, 8))
    # each pass individually guarded: a failure in one (e.g. /dev/shm too
    # small for the 8 MB jungfrau slots) must not cost the other's number
    try:
        if smoke:
            _fanin_host_pass(
                "smoke_a", "smoke_b", 64, 32, 32, 16, extras,
                "host_fanin_volume", "smoke volume",
            )
        else:
            _fanin_host_pass(
                "epix10k2M", "jungfrau4M", 1200 * scale, 600 * scale, 32, 16, extras,
                "host_fanin_volume",
                f"shm, 2 producer procs, u16, bandwidth-bound, x{scale} cores",
            )
    except Exception as e:
        log(f"fan-in volume pass skipped: {e!r}")
    try:
        _fanin_host_pass(
            "smoke_a", "smoke_b", 2000 * scale, 1000 * scale, 64, 32, extras,
            "host_fanin_record_rate", "shm, 2 producer procs, small frames, record-bound",
        )
    except Exception as e:
        log(f"fan-in record-rate pass skipped: {e!r}")


def _bench_fanin_device(jax, jnp, pool, pedestal, gain, mask, extras, smoke=False):
    """Config 5, device leg: ``fanin_fps`` — the same merge with
    per-detector compiled calibration steps on the device, small counts
    (host wall clock with the device in the loop)."""
    from psana_ray_tpu.config import RetrievalMode
    from psana_ray_tpu.infeed import DetectorStream, FanInPipeline
    from psana_ray_tpu.ops import fused_calibrate
    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.sources import SyntheticSource
    from psana_ray_tpu.transport import RingBuffer

    epix_det = "smoke_a" if smoke else "epix10k2M"
    jf_det = "smoke_b" if smoke else "jungfrau4M"

    n_epix, n_jf = 16, 8
    jf_src = SyntheticSource(num_events=16, detector_name=jf_det, seed=1)
    jf_pool = [jf_src.event(i, RetrievalMode.RAW)[0] for i in range(8)]
    jf_ped = jnp.asarray(jf_src.pedestal())
    jf_gain = jnp.asarray(jf_src.gain_map())
    jf_mask = jnp.asarray(jf_src.create_bad_pixel_mask())

    q_epix, q_jf = RingBuffer(maxsize=24), RingBuffer(maxsize=24)

    def produce(queue, frames, n):
        for i in range(n):
            while not queue.put(FrameRecord(0, i, frames[i % len(frames)], 9.5)):
                time.sleep(0.0005)
        if not queue.put_wait(EndOfStream(total_events=n), timeout=300.0):
            raise RuntimeError("EOS delivery timed out")

    threads = [
        threading.Thread(target=produce, args=(q_epix, pool, n_epix), daemon=True),
        threading.Thread(target=produce, args=(q_jf, jf_pool, n_jf), daemon=True),
    ]
    steps = {
        epix_det: jax.jit(
            lambda f: fused_calibrate(f, pedestal, gain, mask, threshold=10.0)
        ),
        jf_det: jax.jit(
            lambda f: fused_calibrate(f, jf_ped, jf_gain, jf_mask, threshold=10.0)
        ),
    }
    fan = FanInPipeline(
        [
            DetectorStream(epix_det, q_epix, batch_size=16, poll_interval_s=0.001),
            DetectorStream(jf_det, q_jf, batch_size=8, poll_interval_s=0.001),
        ]
    )
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    counts = fan.run(
        {name: (lambda s: lambda b: s(b.frames))(s) for name, s in steps.items()},
        block_until_ready=True,
    )
    wall = time.perf_counter() - t0
    for t in threads:
        t.join()
    total = sum(counts.values())
    fps = total / wall
    extras["fanin_device_fps"] = round(fps, 1)
    log(
        f"fan-in + device calib ({epix_det}+{jf_det}): {counts} in "
        f"{wall:.2f}s -> {fps:.0f} fps aggregate wall-clock"
    )


if __name__ == "__main__":
    try:
        main()
    except SectionTimeout:
        # a soft cancel that landed outside any run_section (headline /
        # jax-init / between sections): keep whatever the artifact holds
        log("watchdog cancel escaped a section boundary — emitting as-is")
        emit_final()
    except BaseException:
        emit_final()
        raise
