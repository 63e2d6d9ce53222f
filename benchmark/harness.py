"""What the program adapters share: seeded inputs, weights made on the
device, the sinks that see every result, and the tolerance arithmetic of
``correct``. Adapters (``benchmark/programs/<name>.py``) assemble the
program under test from its public pieces; nothing here is specific to
one configuration, traffic mix or metric."""

from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np

SINK_ANNOTATION = "bench.sink"


# ---------------------------------------------------------------------------
# seeded inputs (numpy; the generator child draws its own frame pool)
# ---------------------------------------------------------------------------

def frame_shape(detector: dict) -> Tuple[int, int, int]:
    return (int(detector["panels"]), int(detector["height"]), int(detector["width"]))


def make_calibration(detector: dict, seed: int) -> tuple:
    """``(pedestal f32, absolute gain f32, mask u8)``, each ``[P,H,W]``,
    from the seed: pedestal around the detector's level, gain within a
    few percent of its ADU-per-photon figure, the stated share of bad
    pixels."""
    rng = np.random.default_rng([int(seed), 0xCA11B])
    shape = frame_shape(detector)
    pedestal = float(detector["pedestal_adu"]) + rng.standard_normal(shape, dtype=np.float32)
    gain = float(detector["photon_adu"]) * (
        1.0 + 0.05 * rng.standard_normal(shape, dtype=np.float32)
    )
    mask = (rng.random(shape, dtype=np.float32) >= float(detector["bad_pixel_fraction"]))
    return pedestal.astype(np.float32), gain.astype(np.float32), mask.astype(np.uint8)


def make_check_frames(detector: dict, n: int, seed: int) -> np.ndarray:
    """``n`` frames like the generator's, for warm-up and for ``correct``
    (drawn apart from the pool: the reference never sees served frames)."""
    from benchmark.generator import make_pool

    return make_pool(detector, n, int(seed) + 1)


def fill_batch(frames: np.ndarray, n: int) -> np.ndarray:
    """``n`` frames from the few check frames, repeated as needed."""
    reps = -(-n // len(frames))
    return np.concatenate([frames] * reps)[:n]


def make_key(seed: int):
    """A PRNG key from any whole-number seed (the driver's pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def init_on_device(model, sample_shape, seed: int):
    """The model's own initialisers, run on the device in ONE jitted call
    from the seed; the tree comes back unboxed, float32, device-resident —
    the form the serving pipelines take as an argument."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    init = jax.jit(lambda key: meta.unbox(model.init(key, jnp.zeros(sample_shape, jnp.float32))))
    return jax.block_until_ready(init(make_key(seed)))


# ---------------------------------------------------------------------------
# sinks: every result passes one, which notes who and when
# ---------------------------------------------------------------------------

class ResultLog:
    """``(shard_rank, event_idx)`` and the instant of every result that
    reached the sink, plus the seconds each batch's append took."""

    def __init__(self):
        self.rank: List[int] = []
        self.idx: List[int] = []
        self.done_t: List[float] = []
        self.append_t: List[float] = []  # one per batch: when its append ended
        self.append_s: List[float] = []  # one per batch: how long it took

    def reset(self):
        self.__init__()

    def note(self, ranks, idxs, t0: float, t1: float):
        self.rank.extend(int(r) for r in ranks)
        self.idx.extend(int(i) for i in idxs)
        self.done_t.extend([t1] * len(idxs))
        self.append_t.append(t1)
        self.append_s.append(t1 - t0)

    def arrays(self):
        return (np.asarray(self.rank, np.int64), np.asarray(self.idx, np.int64),
                np.asarray(self.done_t, np.float64))


class CxiSink:
    """The program's own ``CxiWriter`` with a log in front of it: what
    ``SfxPipeline`` takes as its writer. A result counts from the moment
    the real append returned."""

    def __init__(self, path: str, max_peaks: int):
        import jax

        from psana_ray_tpu.cxi import CxiWriter

        self._annotation = jax.profiler.TraceAnnotation
        self.writer = CxiWriter(path, max_peaks=max_peaks)
        self.max_peaks = max_peaks
        self.path = path
        self.log = ResultLog()
        self.rows = 0

    def append(self, sets):
        t0 = time.monotonic()
        with self._annotation(SINK_ANNOTATION):
            self.writer.append(sets)
        t1 = time.monotonic()
        self.rows += len(sets)
        self.log.note([s.shard_rank for s in sets], [s.event_idx for s in sets], t0, t1)

    def close_and_count(self) -> int:
        """Close the file and return the event rows it holds."""
        import h5py

        self.writer.close()
        with h5py.File(self.path, "r") as f:
            return int(f["entry_1/result_1/nPeaks"].shape[0])


class CountingSink:
    """The sink of a classifier: takes each batch's logits to the host
    and notes every real row."""

    def __init__(self):
        import jax

        self._annotation = jax.profiler.TraceAnnotation
        self.log = ResultLog()
        self.rows = 0

    def __call__(self, out, batch):
        t0 = time.monotonic()
        with self._annotation(SINK_ANNOTATION):
            np.asarray(out)  # the result reaches the host: that is the sink's job
            valid = np.asarray(batch.valid).astype(bool)
            ranks = np.asarray(batch.shard_rank)[valid]
            idxs = np.asarray(batch.event_idx)[valid]
        t1 = time.monotonic()
        self.rows += len(idxs)
        self.log.note(ranks, idxs, t0, t1)

    def close_and_count(self) -> int:
        return self.rows


# ---------------------------------------------------------------------------
# tolerance arithmetic
# ---------------------------------------------------------------------------

# How far the program's logits may be from the float32 reference: four
# times as far as the YARDSTICK is — the same plain reference with every
# convolution's operands rounded to bfloat16, which is the precision the
# configurations state. The error of random, unnormalised weights grows
# with depth and differs by a factor of ten from seed to seed (ResNet-50:
# 0.6% to 6.9% of the reference's RMS over six seeds, my chip runs, PR 23),
# so no fixed limit is both safe and tight; the yardstick moves with the
# seed. The program also rounds between convolutions (affine, SiLU, the
# residual sum in bfloat16), which the yardstick does not: measured, it
# lands at 1-2 yardsticks. An 8-bit float has 16 times bfloat16's
# round-off: it lands at 16 and fails at every seed.
PRECISION_FACTOR = 4.0


def precision_verdict(got, want_f32, want_stated) -> dict:
    err = relative_rms(got, want_f32)
    yard = relative_rms(want_stated, want_f32)
    return {
        "logits_relative_rms": err, "yardstick_relative_rms": yard,
        "limit": PRECISION_FACTOR * yard,
        "ok": bool(err <= PRECISION_FACTOR * yard and np.isfinite(np.asarray(got)).all()),
    }


def relative_rms(got, want) -> float:
    """RMS of the difference over the RMS of the reference."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / max(np.sqrt(np.mean(want ** 2)), 1e-30))
