"""Program adapter ``sfx_dp``: the SFX step data-parallel over a mesh.

One process, one ``('data',)`` mesh over every chip of the host: the
one-chip step (fused calibration + PeakNet-TPU + ``find_peaks``) under
``shard_map`` at the configuration's batch per chip, weights and
constants replicated, fed by ONE ``GlobalStreamConsumer`` from one queue —
assembled as ``chip_smoke.py --chips 4`` assembles it. Results go through
``SfxPipeline.drain`` (the shipped fold + CXI append) from the consumer's
``on_result``. ``shard_map`` and not GSPMD: a Mosaic call cannot be
partitioned, so every chip runs the very program the one-chip cell runs."""

from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.programs import sfx as one_chip


class Program:
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import os

        import jax
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        from psana_ray_tpu.parallel import create_mesh

        self.cfg = cfg
        self.chips = int(np.prod(cfg["mesh"]["shape"]))
        if self.chips != len(devices):
            raise SystemExit(f"mesh {cfg['mesh']} needs {self.chips} devices, JAX has {len(devices)}")
        self.sink = harness.CxiSink(os.path.join(work_dir, "results.cxi"),
                                    int(cfg["event_max_peaks"]))
        self.pipe, self.model, self.variables, self.calib = one_chip.build_pipeline(
            cfg, seed, self.sink
        )
        self.mesh = create_mesh(tuple(cfg["mesh"]["axes"]), tuple(cfg["mesh"]["shape"]))
        axis = cfg["mesh"]["axes"][0]
        self._sharded = jax.jit(
            shard_map(self.pipe._device_step, mesh=self.mesh,
                      in_specs=(P(), P(), P(axis)), out_specs=P(axis), check_vma=False)
        )
        self._replicated = jax.device_put(
            (self.pipe._variables, self.pipe._calib), NamedSharding(self.mesh, P())
        )
        self._batch_sharding = NamedSharding(self.mesh, P(axis))
        self.per_chip = int(cfg["batch_size"])
        self.frames_per_batch = self.chips * self.per_chip
        self.warm_rows = 0
        self._metrics = None

    @property
    def metrics(self):
        return self._metrics

    def _global(self, frames: np.ndarray):
        import jax

        full = harness.fill_batch(frames, self.frames_per_batch)
        return full, jax.device_put(full, self._batch_sharding)

    def warm(self, frames: np.ndarray):
        import jax

        full, g = self._global(frames)
        for _ in range(2):
            out = jax.block_until_ready(self._sharded(*self._replicated, g))
        # the fold + append path once, so the file and the allocator are warm
        self.pipe.drain((out, one_chip.host_batch(full, 0)))
        self.warm_rows = self.sink.rows
        self.sink.log.reset()

    def _on_result(self, outs, g):
        from psana_ray_tpu.infeed.batcher import Batch

        host = Batch(
            frames=g.frames, valid=np.asarray(g.valid), shard_rank=np.asarray(g.shard_rank),
            event_idx=np.asarray(g.event_idx), photon_energy=np.asarray(g.photon_energy),
            num_valid=g.num_valid,
        )
        self.pipe.drain((outs, host))

    def run(self, queue) -> int:
        from psana_ray_tpu.infeed import GlobalStreamConsumer

        p, h, w = harness.frame_shape(self.cfg["detector"])
        consumer = GlobalStreamConsumer(
            queue, local_batch_size=self.frames_per_batch, mesh=self.mesh,
            frame_shape=(p, h, w), frame_dtype=np.dtype(self.cfg["detector"]["dtype"]),
        )
        self._metrics = consumer.metrics
        return consumer.run(
            lambda batch: self._sharded(*self._replicated, batch.frames),
            on_result=self._on_result, block_until_ready=True,
        )

    def check(self, frames: np.ndarray) -> dict:
        """The reference check of the one-chip adapter on chip 0's rows,
        and every chip's rows against the one-device step: counts equal,
        coordinates equal as sets per panel row (top-k order may differ on
        equal scores), scores within rtol 1e-4 / atol 1e-3 (what
        ``chip_smoke.py`` holds the mesh to: the same bf16 program on
        another chip differs by rounding in the last bits at most)."""
        import jax

        full, g = self._global(frames)
        outs = [np.asarray(o) for o in self._sharded(*self._replicated, g)]
        spread = len({s.device for s in g.addressable_shards})
        p = harness.frame_shape(self.cfg["detector"])[0]
        rows_equal, worst = spread == self.chips, 0.0
        first_out = None
        for c in range(self.chips):
            fr = slice(c * self.per_chip, (c + 1) * self.per_chip)
            rows = slice(c * self.per_chip * p, (c + 1) * self.per_chip * p)
            ref = [np.asarray(a) for a in self.pipe._step(full[fr])]
            if c == 0:
                first_out = ref
            yx, score, n = (o[rows] for o in outs)
            if not np.array_equal(n, ref[2]):
                rows_equal = False
                continue
            for got_yx, got_s, want_yx, want_s, k in zip(yx, score, ref[0], ref[1], ref[2]):
                go = np.lexsort((got_yx[:k, 1], got_yx[:k, 0]))
                wo = np.lexsort((want_yx[:k, 1], want_yx[:k, 0]))
                if not np.array_equal(got_yx[:k][go], want_yx[:k][wo]):
                    rows_equal = False
                    continue
                if k:
                    d = np.abs(got_s[:k][go] - want_s[:k][wo])
                    worst = max(worst, float(d.max()))
                    if not np.allclose(got_s[:k][go], want_s[:k][wo], rtol=1e-4, atol=1e-3):
                        rows_equal = False
        res = one_chip.check_against_reference(
            self.cfg, self.model, self.variables, self.calib, full[: self.per_chip], first_out,
        )
        res["rows_equal_one_device_step"] = bool(rows_equal)
        res["mesh_score_max_abs_diff"] = worst
        res["inputs_on_devices"] = spread
        res["ok"] = bool(res["ok"] and rows_equal)
        return res
