#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path still starts on
the chip: train -> export -> stream -> PeakNet -> CXI, through the entry
points a user would call, at the full published width of the shipped
PeakNet-TPU (epix10k2M, features 64,128,256,512, s2d=2, CLI batch 8).

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the data-parallel path, and only it

One-chip phases, each in its own process so exactly one process holds the
chip at a time (this parent stays off JAX until the last child exited):

  probe  a child reports the device; anything but a TPU stops the run
  train  ``examples/train_peaknet.py`` takes a few steps and exports the
         serving tree
  serve  a JAX-free producer process streams RAW u16 frames over shm://
         (the native ring is rebuilt from source first) while
         ``python -m psana_ray_tpu.sfx`` calibrates on device, runs
         PeakNet, finds peaks and writes the CXI file until the typed EOS
  check  the CXI file holds every produced (shard_rank, event_idx)
         exactly once, coordinates inside the stacked-panel frame, finite
         scores; the first batch equals an in-process run of the same
         pipeline on regenerated frames
  inspect  the compiled serve step contains the Mosaic call — the
         calibration kernel did not run interpreted

``--chips 4`` runs ONE process over a ('data',) mesh of four chips: the
same calib + PeakNet + find_peaks step under shard_map at 8 frames per
chip, fed by one GlobalStreamConsumer, compared row for row with the
single-device step on the same frames and weights.

Any failed phase, any device that is not a TPU, any event lost or
duplicated: non-zero exit and no result line. The timings printed are
smoke timings (one run, compile included), not measurements. The last
line of stdout is the result: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# logs, the CXI file and the serving export; chiprun carries it back
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
CHILD_TIMEOUT_S = 600  # per phase; the whole run must fit 1200 s


@dataclasses.dataclass(frozen=True)
class Size:
    """What the smoke runs. The defaults ARE the smoke; a rehearsal off
    the chip passes a tiny one to the same functions."""

    detector: str = "epix10k2M"
    features: tuple = (64, 128, 256, 512)
    s2d: int = 2
    train_steps: int = 4
    train_batch: int = 2
    events: int = 64
    seed: int = 0


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str):
    """Stop the run: non-zero exit, no result line."""
    print(f"[smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # JAX then logs every compile and every persistent-cache hit at
    # WARNING: compile seconds come from the CLIs' own logs, no flag added
    env["JAX_LOG_COMPILES"] = "1"
    return env


def start_child(argv, log_path: str) -> subprocess.Popen:
    log = open(log_path, "w")
    try:
        return subprocess.Popen(
            argv, stdout=log, stderr=subprocess.STDOUT, env=child_env(), cwd=ROOT
        )
    finally:
        log.close()  # the child holds its own descriptor


def wait_child(name: str, proc: subprocess.Popen, log_path: str, deadline: float) -> str:
    """Wait for ``proc``; any exit code but 0 (or the deadline) fails the
    run. Returns the child's log."""
    try:
        rc = proc.wait(timeout=max(0.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        rc = None
    with open(log_path, errors="replace") as f:
        log = f.read()
    if rc != 0:
        sys.stderr.write(log[-4000:])
        fail(f"{name} child " + ("timed out" if rc is None else f"exited {rc}")
             + f" (log: {log_path})")
    return log


def run_child(name: str, argv, log_path: str, timeout_s: float = CHILD_TIMEOUT_S):
    """One child to completion -> (wall seconds, log text)."""
    t0 = time.monotonic()
    proc = start_child(argv, log_path)
    try:
        log = wait_child(name, proc, log_path, t0 + timeout_s)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return time.monotonic() - t0, log


_COMPILED = re.compile(r"Finished XLA compilation of (\S+) in ([0-9.]+) sec")
_CACHE_HIT = re.compile(r"Persistent compilation cache hit for '([^']+)'")
_DEVICE_LINE = re.compile(r"jax devices: platform=(\S+) kind=(.+?) count=(\d+)")


def compile_report(log: str) -> dict:
    """Compile seconds and persistent-cache hits, from JAX's own log lines
    in a child's output. A hit still logs its (short) load as a compile."""
    compiled = [(n, float(s)) for n, s in _COMPILED.findall(log)]
    return {
        "compile_s": round(sum(s for _, s in compiled), 1),
        "programs": len(compiled),
        "slowest": max(compiled, key=lambda c: c[1], default=("-", 0.0)),
        "hits": set(_CACHE_HIT.findall(log)),
    }


def warm_or_cold(report: dict, module: str) -> str:
    return "warm (persistent-cache hit)" if module in report["hits"] else "cold"


def child_platform(name: str, log: str) -> str:
    """The device line the CLI logged at start; a child that fell to the
    CPU fails the run here, from its own log."""
    m = _DEVICE_LINE.search(log)
    if m is None:
        fail(f"{name} child logged no device line")
    return m.group(1)


def cache_entries() -> int:
    from psana_ray_tpu.utils.jaxenv import compile_cache_dir  # touches no JAX

    try:
        return sum(1 for n in os.listdir(compile_cache_dir()) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


# ---------------------------------------------------------------------------
# the JAX-free producer process (entered as
# ``python -c "import chip_smoke; chip_smoke.producer_child(...)"``)
# ---------------------------------------------------------------------------

def producer_child(address: str, detector: str, events: int, seed: int) -> None:
    """Stream ``events`` RAW u16 frames, then the typed EOS. The producer
    CLI offers calibrated or assembled frames only, so this drives the
    library surface that CLI wraps."""
    from psana_ray_tpu.config import (
        PipelineConfig,
        RetrievalMode,
        SourceConfig,
        TransportConfig,
    )
    from psana_ray_tpu.producer import ProducerRuntime

    runtime = ProducerRuntime(
        PipelineConfig(
            source=SourceConfig(
                exp="synthetic", detector_name=detector, mode=RetrievalMode.RAW,
                dtype="uint16", num_events=events, seed=seed,
            ),
            transport=TransportConfig(address=address),
        )
    )
    runtime.run(block=True)
    if "jax" in sys.modules:
        raise SystemExit("the producer process imported jax: it could hold the chip")
    print(json.dumps({"produced": runtime.metrics.snapshot()["frames_total"]}))


def start_producer(address: str, size: Size, log_path: str) -> subprocess.Popen:
    code = (
        "import chip_smoke; chip_smoke.producer_child("
        f"{address!r}, {size.detector!r}, {size.events}, {size.seed})"
    )
    return start_child([sys.executable, "-c", code], log_path)


def produced_count(log: str) -> int:
    for line in reversed(log.splitlines()):
        if line.startswith('{"produced"'):
            return int(json.loads(line)["produced"])
    fail("producer child printed no count")


def calibration_arrays(size: Size) -> tuple:
    """(pedestal, absolute gain, mask) with numpy alone — the constants
    examples/train_peaknet.py trains against."""
    from psana_ray_tpu.sources import SyntheticSource

    src = SyntheticSource(num_events=1, detector_name=size.detector, seed=size.seed)
    return src.pedestal(), src.spec.adu_gain * src.gain_map(), src.create_bad_pixel_mask()


def frame_shape(size: Size) -> tuple:
    from psana_ray_tpu.sources.base import DETECTORS

    return DETECTORS[size.detector].frame_shape


def rebuild_native_ring() -> float:
    """Force the shm ring's library to build from native/shmring.cpp on
    this machine: a .so that rode in with a copy of the tree proves
    nothing about the toolchain here."""
    from psana_ray_tpu.transport import shm_ring

    for path in (shm_ring._LIB_PATH, shm_ring._STAMP_PATH):
        if os.path.exists(path):
            os.remove(path)
    t0 = time.monotonic()
    if not shm_ring.native_available():
        fail("native shm ring did not build (needs g++ and make)")
    return time.monotonic() - t0


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def probe_device(platform: str, out: str) -> dict:
    """Ask a child what JAX sees, before any work. The child exits and
    frees the chip; this parent still has not touched JAX."""
    code = (
        "import json; from psana_ray_tpu.utils.jaxenv import device_summary; "
        "print('DEVICE ' + json.dumps(device_summary()))"
    )
    _, log = run_child("probe", [sys.executable, "-c", code], os.path.join(out, "probe.log"), 300)
    lines = [ln for ln in log.splitlines() if ln.startswith("DEVICE ")]
    if not lines:
        fail("probe child printed no device")
    dev = json.loads(lines[-1][len("DEVICE "):])
    if dev["platform"] != platform:
        fail(f"JAX found no accelerator: {dev} (want platform {platform!r})")
    return dev


def phase_train(size: Size, out: str, platform: str) -> str:
    export = os.path.join(out, "serving")
    feats = ",".join(str(f) for f in size.features)
    wall, log = run_child(
        "train",
        [
            sys.executable, os.path.join(ROOT, "examples", "train_peaknet.py"),
            "--steps", str(size.train_steps), "--batch", str(size.train_batch),
            "--detector", size.detector,
            "--num_events", str(2 * size.train_steps * size.train_batch),
            "--features", feats, "--s2d", str(size.s2d),
            "--norm", "batch", "--export-serving", export,
        ],
        os.path.join(out, "train.log"),
    )
    if child_platform("train", log) != platform:
        fail(f"train ran on {child_platform('train', log)!r}, not {platform!r}")
    m = re.search(r"trained (\d+) steps .*loss ([0-9.eE+-]+) -> ([0-9.eE+-]+)", log)
    if m is None or int(m.group(1)) != size.train_steps:  # a NaN loss matches nothing
        fail(f"train did not take {size.train_steps} steps with a finite loss")
    if not os.path.isdir(export):
        fail("train exported no serving tree")
    rep = compile_report(log)
    say(
        f"train steps={m.group(1)} batch={size.train_batch} "
        f"features={size.features} s2d={size.s2d} detector={size.detector} "
        f"loss {m.group(2)} -> {m.group(3)} platform={platform} wall_s={wall:.1f} "
        f"compile_s={rep['compile_s']} programs={rep['programs']} "
        f"slowest={rep['slowest'][0]}:{rep['slowest'][1]:.1f}s "
        f"train_step_compile={warm_or_cold(rep, 'jit__step')} [smoke timings]"
    )
    return export


def phase_serve(size: Size, out: str, export: str, platform: str):
    from psana_ray_tpu.transport.shm_ring import ShmRingBuffer

    import numpy as np

    calib = calibration_arrays(size)

    npz = os.path.join(out, "calib.npz")
    np.savez(npz, pedestal=calib[0], gain=calib[1], mask=calib[2])
    cxi = os.path.join(out, "smoke.cxi")
    ring_name = f"chip_smoke_{os.getpid()}"
    address = f"shm://{ring_name}"
    # this parent owns the ring: created before either child attaches,
    # destroyed whatever happens to them
    ring = ShmRingBuffer.create(ring_name, maxsize=16)
    t0 = time.monotonic()
    children = []
    try:
        p_log, s_log = os.path.join(out, "producer.log"), os.path.join(out, "serve.log")
        producer = start_producer(address, size, p_log)
        children.append(producer)
        sfx = start_child(
            [
                sys.executable, "-m", "psana_ray_tpu.sfx", "--address", address,
                "--serving_params", export, "--calib_npz", npz, "--output", cxi,
            ],
            s_log,
        )
        children.append(sfx)
        deadline = t0 + CHILD_TIMEOUT_S
        producer_log = wait_child("producer", producer, p_log, deadline)
        serve_log = wait_child("serve", sfx, s_log, deadline)
    finally:
        for proc in children:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        ring.destroy()
    wall = time.monotonic() - t0
    if child_platform("serve", serve_log) != platform:
        fail(f"serve ran on {child_platform('serve', serve_log)!r}, not {platform!r}")
    produced = produced_count(producer_log)
    if produced != size.events:
        fail(f"producer sent {produced} events, not {size.events}")
    rep = compile_report(serve_log)
    say(
        f"serve events_in={produced} transport={address} (native ring rebuilt) "
        f"platform={platform} wall_s={wall:.1f} compile_s={rep['compile_s']} "
        f"programs={rep['programs']} slowest={rep['slowest'][0]}:{rep['slowest'][1]:.1f}s "
        f"serve_step_compile={warm_or_cold(rep, 'jit__device_step')} "
        f"[smoke timings]"
    )
    return cxi, npz


def phase_check(size: Size, cxi: str) -> dict:
    """Exactly-once, coordinates in frame, finite scores — read with h5py
    alone against the documented CXI layout."""
    import h5py
    import numpy as np

    panels, h, w = frame_shape(size)
    with h5py.File(cxi, "r") as f:
        g = f["entry_1/result_1"]
        n = g["nPeaks"][:]
        x, y, score = g["peakXPosRaw"][:], g["peakYPosRaw"][:], g["peakTotalIntensity"][:]
        keys = list(zip(f["LCLS/shard_rank"][:].tolist(), f["LCLS/event_idx"][:].tolist()))
    want = {(0, i) for i in range(size.events)}
    if len(keys) != len(set(keys)):
        fail(f"duplicated events in the CXI file: {len(keys) - len(set(keys))}")
    if set(keys) != want:
        fail(f"events lost or foreign: missing {sorted(want - set(keys))[:8]}, "
             f"extra {sorted(set(keys) - want)[:8]}")
    live = np.arange(x.shape[1])[None, :] < n[:, None]
    if not (np.isfinite(score[live]).all() and np.isfinite(x[live]).all() and np.isfinite(y[live]).all()):
        fail("non-finite peak scores or coordinates")
    if live.any() and not (
        (x[live] >= 0).all() and (x[live] < w).all()
        and (y[live] >= 0).all() and (y[live] < panels * h).all()
    ):
        fail(f"peak coordinates outside the stacked-panel frame [{panels * h}, {w}]")
    say(
        f"check events_out={len(keys)} distinct={len(set(keys))} duplicates=0 "
        f"peaks={int(n.sum())} coords_in_frame=[{panels * h},{w}] scores_finite=true"
    )
    return {"keys": keys, "n": n, "x": x, "y": y, "score": score}


def phase_inspect(size: Size, out: str, export: str, npz: str, served: dict, platform: str) -> dict:
    """Every child has exited: the parent may take the chip. Build the
    pipeline the CLI built, read its compiled step, run the first batch
    in-process and hold the CLI's rows to it."""
    import jax
    import numpy as np

    from psana_ray_tpu.checkpoint import load_params
    from psana_ray_tpu.config import RetrievalMode
    from psana_ray_tpu.cxi import CxiWriter
    from psana_ray_tpu.infeed.batcher import Batch
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline
    from psana_ray_tpu.sources import SyntheticSource
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache, device_summary

    where = configure_compile_cache()
    dev = device_summary()
    if dev["platform"] != platform:
        fail(f"parent sees {dev}, not platform {platform!r}")
    events = []
    jax.monitoring.register_event_listener(lambda name, **kw: events.append(name))

    with np.load(npz) as z:
        calib = (z["pedestal"], z["gain"], z["mask"])
    b = SfxConfig.batch_size
    src = SyntheticSource(
        num_events=b, detector_name=size.detector, seed=size.seed, dtype="uint16"
    )
    frames, energy = zip(*(src.event(i, RetrievalMode.RAW) for i in range(b)))
    batch = Batch(
        frames=np.stack(frames), valid=np.ones(b, np.uint8),
        shard_rank=np.zeros(b, np.int32), event_idx=np.arange(b, dtype=np.int64),
        photon_energy=np.asarray(energy, np.float32),
    )
    ref_cxi = os.path.join(out, "reference.cxi")
    with CxiWriter(ref_cxi, max_peaks=served["x"].shape[1]) as writer:
        pipe = SfxPipeline(load_params(export), writer, calib=calib)
        # the CLI's own route (a jit call): finds the entry the serve
        # child just wrote, which shows both processes share one cache
        t0 = time.monotonic()
        pipe.process_batch(batch)
        first_batch_s = time.monotonic() - t0
        hit = "/jax/compilation_cache/cache_hits" in events
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    # the compiled text needs an ahead-of-time compile; after the call
    # above it reuses that executable in-process (the served form: the
    # batch as one array a frame)
    t0 = time.monotonic()
    frame = jax.ShapeDtypeStruct(batch.frames.shape[1:], batch.frames.dtype)
    compiled = pipe._jit_step.lower(
        pipe._variables, pipe._calib, (frame,) * len(batch.frames),
    ).compile()
    aot_s = time.monotonic() - t0
    mosaic = compiled.as_text().count("tpu_custom_call")
    if platform == "tpu" and mosaic < 1:
        fail("the compiled serve step holds no tpu_custom_call: "
             "calibration would run interpreted")
    mem = compiled.memory_analysis()

    import h5py

    with h5py.File(ref_cxi, "r") as f:
        g = f["entry_1/result_1"]
        ref = {k: g[k][:] for k in ("nPeaks", "peakXPosRaw", "peakYPosRaw", "peakTotalIntensity")}
    rows = [served["keys"].index((0, i)) for i in range(b)]
    for name, got in (("nPeaks", served["n"]), ("peakXPosRaw", served["x"]),
                      ("peakYPosRaw", served["y"]), ("peakTotalIntensity", served["score"])):
        if not np.array_equal(got[rows], ref[name]):
            fail(f"the CLI's {name} for events 0..{b - 1} differ from the "
                 f"in-process pipeline on the same frames and weights")
    say(
        f"inspect serve_step tpu_custom_call={mosaic} (compiled text) "
        f"input=uint16{list(batch.frames.shape)} first_batch_s={first_batch_s:.1f} "
        f"({'persistent-cache hit on the serve child entry' if hit else 'cache miss'}) "
        f"aot_compile_s={aot_s:.1f} "
        f"args_MB={mem.argument_size_in_bytes / 1e6:.0f} temp_GB={mem.temp_size_in_bytes / 1e9:.2f} "
        f"peak_hbm_bytes={peak if peak is not None else 'not reported'} "
        f"(this process, one batch) first_batch_matches_in_process_reference=true "
        f"compile_cache={where} [smoke timings]"
    )
    return dev


def run_one_chip(size: Size, out: str, platform: str = "tpu") -> dict:
    probed = probe_device(platform, out)
    say(f"device platform={probed['platform']} kind={probed['kind']} "
        f"count={probed['count']} (probe child)")
    from psana_ray_tpu.utils.jaxenv import compile_cache_dir

    say(f"compile cache dir={compile_cache_dir()} entries_before={cache_entries()} "
        f"set_by={'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'package default'}")
    say(f"native shm ring built from native/shmring.cpp in {rebuild_native_ring():.1f} s")
    export = phase_train(size, out, platform)
    cxi, npz = phase_serve(size, out, export, platform)
    served = phase_check(size, cxi)
    if "jax" in sys.modules:
        fail("the parent imported jax while children needed the chip")
    dev = phase_inspect(size, out, export, npz, served, platform)
    shutil.rmtree(export)  # ~40 MB of weights: not worth carrying back
    say(f"compile cache entries_after={cache_entries()}")
    return dev


# ---------------------------------------------------------------------------
# four chips: one process, one mesh
# ---------------------------------------------------------------------------

def run_four_chip(size: Size, out: str, platform: str = "tpu", chips: int = 4) -> dict:
    import jax
    import numpy as np
    from flax.core import meta
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from psana_ray_tpu.infeed import GlobalStreamConsumer
    from psana_ray_tpu.models import PeakNetUNetTPU
    from psana_ray_tpu.models.init import eval_shape_init
    from psana_ray_tpu.parallel import create_mesh
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline
    from psana_ray_tpu.transport.shm_ring import ShmRingBuffer
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache, device_summary

    where = configure_compile_cache()
    dev = device_summary()
    if dev["platform"] != platform or dev["count"] != chips:
        fail(f"JAX found {dev}; --chips {chips} needs {chips} {platform} devices")
    say(f"device platform={dev['platform']} kind={dev['kind']} count={dev['count']} "
        f"compile_cache={where}")
    say(f"native shm ring built from native/shmring.cpp in {rebuild_native_ring():.1f} s")

    calib, shape = calibration_arrays(size), frame_shape(size)
    # random weights from the seed, in the serving (norm='frozen') form
    variables = meta.unbox(eval_shape_init(
        PeakNetUNetTPU(features=size.features, norm="frozen", s2d=size.s2d),
        (1, 64, 64, 1), seed=size.seed,
    ))
    pipe = SfxPipeline(variables, writer=None, calib=calib)
    per_chip = SfxConfig.batch_size
    mesh = create_mesh(("data",), (chips,))
    # shard_map, not GSPMD: a Mosaic call cannot be partitioned, so each
    # chip runs the very program the one-chip phase runs on its 8 rows
    sharded = jax.jit(
        shard_map(pipe._device_step, mesh=mesh, in_specs=(P(), P(), P("data")),
                  out_specs=P("data"), check_vma=False)
    )
    replicated = jax.device_put(
        (pipe._variables, pipe._calib), NamedSharding(mesh, P())
    )
    single = pipe._step

    def devices_of(a) -> int:
        return len({s.device for s in a.addressable_shards})

    rounds = []

    def on_result(outs, g):
        spread = [devices_of(g.frames)] + [devices_of(o) for o in outs]
        if any(n != chips for n in spread):
            fail(f"arrays not on {chips} distinct devices: input+outputs on {spread}")
        rounds.append((np.asarray(g.frames), np.asarray(g.valid),
                       [np.asarray(o) for o in outs]))

    ring_name = f"chip_smoke_{os.getpid()}"
    ring = ShmRingBuffer.create(ring_name, maxsize=16)
    p_log = os.path.join(out, "producer.log")
    t0 = time.monotonic()
    producer = start_producer(f"shm://{ring_name}", size, p_log)
    try:
        consumer = GlobalStreamConsumer(
            ring, local_batch_size=chips * per_chip, mesh=mesh,
            frame_shape=shape, frame_dtype=np.uint16,
        )
        n_seen = consumer.run(
            lambda batch: sharded(*replicated, batch.frames), on_result=on_result,
            block_until_ready=True,
        )
        produced = produced_count(
            wait_child("producer", producer, p_log, t0 + CHILD_TIMEOUT_S)
        )
    finally:
        if producer.poll() is None:
            producer.kill()
            producer.wait()
        ring.destroy()
    wall = time.monotonic() - t0
    if not (produced == n_seen == size.events):
        fail(f"events in {produced}, through the mesh {n_seen}, want {size.events}")

    # row for row against the single-device step: per chip's 8 frames,
    # peaks as a set per panel row (top-k order may differ on equal scores)
    p = shape[0]
    peaks = 0
    worst = 0.0
    for frames, valid, (yx, score, n) in rounds:
        for c in range(chips):
            fr = slice(c * per_chip, (c + 1) * per_chip)
            rows = slice(c * per_chip * p, (c + 1) * per_chip * p)
            ref_yx, ref_score, ref_n = (np.asarray(a) for a in single(frames[fr]))
            if not np.array_equal(n[rows], ref_n):
                fail(f"peak counts differ from the single-device step on chip {c}")
            for got_yx, got_s, want_yx, want_s, k in zip(
                yx[rows], score[rows], ref_yx, ref_score, ref_n
            ):
                go = np.lexsort((got_yx[:k, 1], got_yx[:k, 0]))
                wo = np.lexsort((want_yx[:k, 1], want_yx[:k, 0]))
                if not np.array_equal(got_yx[:k][go], want_yx[:k][wo]):
                    fail(f"peak coordinates differ from the single-device step on chip {c}")
                # the virtual-mesh dry run's tolerance (__graft_entry__)
                np.testing.assert_allclose(got_s[:k][go], want_s[:k][wo], rtol=1e-4, atol=1e-3)
                if k:
                    worst = max(worst, float(np.abs(got_s[:k][go] - want_s[:k][wo]).max()))
            peaks += int(n[rows][np.repeat(valid[fr], p).astype(bool)].sum())
    mosaic = sharded.lower(
        *replicated,
        jax.ShapeDtypeStruct(
            (chips * per_chip, *shape), np.uint16,
            sharding=NamedSharding(mesh, P("data")),
        ),
    ).compile().as_text().count("tpu_custom_call")
    if platform == "tpu" and mosaic < 1:
        fail("the sharded serve step holds no tpu_custom_call")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say(
        f"mesh data={chips} per_chip_batch={per_chip} global_batch={chips * per_chip} "
        f"rounds={len(rounds)} events_in={produced} events_through_mesh={n_seen} "
        f"peaks={peaks} input_and_outputs_on_{chips}_distinct_devices=true "
        f"rows_equal_single_device=true max_score_diff={worst:.2e} (rtol 1e-4, atol 1e-3) "
        f"tpu_custom_call={mosaic} peak_hbm_bytes_device0="
        f"{peak if peak is not None else 'not reported'} wall_s={wall:.1f} [smoke timings]"
    )
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the data-parallel mesh path and what it is compared "
                    "with, no other phase (default: the one-chip main path)")
    args = ap.parse_args(argv)
    for needed in ("psana_ray_tpu/sfx.py", "examples/train_peaknet.py",
                   "psana_ray_tpu/native/shmring.cpp"):
        if not os.path.exists(os.path.join(ROOT, needed)):
            fail(f"{needed} not found beside chip_smoke.py: run it from a checkout")
    sys.path.insert(0, ROOT)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    size = Size()
    dev = run_four_chip(size, OUT) if args.chips == 4 else run_one_chip(size, OUT)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
