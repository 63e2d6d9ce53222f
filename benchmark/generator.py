#!/usr/bin/env python3
"""The load generator: a JAX-free child process of ``run.py``.

One general generator for every traffic mix. It reads the mix's
parameters (``benchmark/traffic/<name>.json``), builds a pool of seeded
detector frames with numpy while the parent starts JAX, attaches to the
transport the parent created, and on the parent's ``go`` offers frames
on the mix's schedule until ``stop_at``, then sends the typed end of
stream. Each frame carries a fresh ``(shard_rank, event_idx)``; its due
time is a function of ``event_idx`` and the start instant alone
(:func:`due_offsets`), so the parent needs no field in the record to time
it. Linux's CLOCK_MONOTONIC is one clock for every process of a host:
``time.monotonic()`` here and in the parent read the same timeline.

Protocol (one JSON object per line): the child prints ``{"ready": ...}``
when the pool is built and the transport attached; the parent writes
``{"t_go", "stop_at"}`` to its stdin; the child streams, writes its
per-frame record (due, sent, blocked seconds) to ``report_path`` as an
``.npz``, prints ``{"done": ...}`` and exits 0.

Loop kinds (``traffic["loop"]``):

``closed``  put as fast as the transport accepts; a frame is due the
            moment the previous one was accepted (backpressure closes
            the loop).
``open``    frame ``i`` is due at ``t_go + offset(i)`` whatever the
            system does; a stall is charged to the frames behind it.
            ``rate_fps`` with ``burst`` frames sharing one due instant
            (``burst`` 1 = a steady stream).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def make_pool(detector: dict, pool_frames: int, seed: int) -> np.ndarray:
    """``[pool_frames, P, H, W]`` raw frames in the detector's dtype:
    pedestal level plus Gaussian read noise plus a few bright pixels per
    panel, from the seed alone. Cheap on purpose (one vectorised draw per
    frame): the program's ``SyntheticSource`` takes ~50 ms per epix10k2M
    frame, which no load generator can afford."""
    rng = np.random.default_rng([int(seed), 0xF00D])
    shape = (int(detector["panels"]), int(detector["height"]), int(detector["width"]))
    dtype = np.dtype(detector["dtype"])
    hi = float(np.iinfo(dtype).max) if dtype.kind in "ui" else np.inf
    pool = np.empty((pool_frames, *shape), dtype)
    n_hot = max(1, shape[1] * shape[2] // 4096)
    for i in range(pool_frames):
        f = rng.standard_normal(shape, dtype=np.float32)
        f *= float(detector["noise_adu"])
        f += float(detector["pedestal_adu"])
        # a sprinkle of photon hits: panel-local bright pixels
        ys = rng.integers(0, shape[1], (shape[0], n_hot))
        xs = rng.integers(0, shape[2], (shape[0], n_hot))
        f[np.arange(shape[0])[:, None], ys, xs] += float(detector["photon_adu"]) * rng.integers(
            1, 40, (shape[0], n_hot)
        )
        np.clip(f, 0.0, hi, out=f)
        pool[i] = f.astype(dtype)
    return pool


def due_offsets(traffic: dict, n: int) -> np.ndarray:
    """Seconds after ``t_go`` at which frames ``0..n-1`` of an open loop
    are due: ``burst`` frames share each instant, instants come at
    ``rate_fps / burst`` per second."""
    burst = int(traffic.get("burst", 1))
    rate = float(traffic["rate_fps"])
    return (np.arange(n) // burst) * (burst / rate)


def stream(queue, pool, traffic: dict, t_go: float, stop_at: float, traced: bool):
    """Offer frames from ``t_go`` until ``stop_at``; returns per-frame
    arrays (due, sent, blocked)."""
    from psana_ray_tpu.obs.tracing import TraceContext
    from psana_ray_tpu.records import FrameRecord

    closed = traffic["loop"] == "closed"
    poll_s = float(traffic.get("put_poll_s", 0.0002))
    rank = int(traffic.get("shard_rank", 0))
    energy = float(traffic.get("photon_energy_ev", 9500.0))
    pid = os.getpid()
    n_pool = len(pool)
    due, sent, blocked = [], [], []
    if not closed:
        horizon = int((stop_at - t_go) * float(traffic["rate_fps"])) + 2 * int(traffic.get("burst", 1))
        offsets = due_offsets(traffic, horizon)
    while time.monotonic() < t_go:
        time.sleep(0.0005)
    i = 0
    prev_sent = t_go
    while True:
        if closed:
            t_due = prev_sent
            if t_due >= stop_at:
                break
        else:
            t_due = t_go + float(offsets[i])
            if t_due >= stop_at:
                break
            while True:
                now = time.monotonic()
                if now >= t_due:
                    break
                time.sleep(min(0.001, t_due - now))
        rec = FrameRecord(
            shard_rank=rank, event_idx=i, panels=pool[i % n_pool],
            photon_energy=energy, timestamp=t_due,
            trace=TraceContext(trace_id=(pid << 28) + i + 1, sampled=True,
                               origin_host="bench", origin_pid=pid) if traced else None,
        )
        t_try = time.monotonic()
        waited = 0.0
        while not queue.put(rec):
            time.sleep(poll_s)
            waited = time.monotonic() - t_try
        prev_sent = time.monotonic()
        due.append(t_due)
        sent.append(prev_sent)
        blocked.append(waited)
        i += 1
    return np.asarray(due), np.asarray(sent), np.asarray(blocked)


def prefault(queue, frame, slots: int) -> float:
    """Touch every slot of a new shared-memory ring once, by putting a
    frame and taking it straight back: the first write to a slot faults its
    pages in (some 10 ms a slot at epix10k2M), and a stream that pays that
    in its first second starts behind its schedule. Transport warm-up,
    counted as set-up."""
    from psana_ray_tpu.records import FrameRecord

    t0 = time.monotonic()
    rec = FrameRecord(shard_rank=-1, event_idx=-1, panels=frame, photon_energy=0.0)
    for _ in range(slots):
        if queue.put(rec):
            queue.get()
    return time.monotonic() - t0


def main() -> int:
    spec = json.loads(sys.argv[1])
    sys.path.insert(0, ROOT)
    t0 = time.monotonic()
    from psana_ray_tpu.config import TransportConfig
    from psana_ray_tpu.records import EndOfStream
    from psana_ray_tpu.transport.addressing import open_queue

    traffic = spec["traffic"]
    pool = make_pool(spec["detector"], int(traffic["pool_frames"]), spec["seed"])
    pool_s = time.monotonic() - t0
    queue = open_queue(TransportConfig(address=spec["address"]), role="producer",
                       address=spec["address"])
    prefault_s = prefault(queue, pool[0], int(spec.get("slots", 0)))
    print(json.dumps({"ready": True, "pool_s": pool_s, "prefault_s": prefault_s,
                      "pool_frames": len(pool),
                      "frame_bytes": int(pool[0].nbytes)}), flush=True)
    go = json.loads(sys.stdin.readline())
    due, sent, blocked = stream(queue, pool, traffic, go["t_go"], go["stop_at"],
                                bool(spec.get("traced")))
    eos = EndOfStream(producer_rank=int(traffic.get("shard_rank", 0)), total_events=len(due))
    while not queue.put(eos):
        time.sleep(0.001)
    np.savez(spec["report_path"], due=due, sent=sent, blocked=blocked)
    if "jax" in sys.modules:
        print("the generator imported jax: it could hold the chip", file=sys.stderr)
        return 1
    print(json.dumps({"done": True, "sent": int(len(due))}), flush=True)
    if hasattr(queue, "disconnect"):
        queue.disconnect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
