"""Program adapter ``classify``: the hit finder through ``InfeedPipeline``.

queue -> ``batches_from_queue`` -> ``DevicePrefetcher`` (``device_put`` on
its own thread, two batches ahead) -> one compiled step (fused calibration
+ the fused-Pallas ResNet, panels as channels) -> a counting sink that
takes the logits to the host. The step is the one ``bench.py`` runs
(``_make_resnet_infer``), with weights and calibration constants as
arguments so that the compiled program does not depend on the seed."""

from __future__ import annotations

import numpy as np

from benchmark import harness

STEP_NAME = "hit_step"  # the compiled program is jit_hit_step in a trace


class Program:
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax
        import jax.numpy as jnp

        from psana_ray_tpu.models import ResNetClassifier, panels_to_nhwc
        from psana_ray_tpu.models.pallas_resnet import resnet_fused_infer
        from psana_ray_tpu.ops import fused_calibrate

        self.cfg = cfg
        m = cfg["model"]
        self.stage_sizes = tuple(m["stage_sizes"])
        model = ResNetClassifier(
            stage_sizes=self.stage_sizes, num_classes=int(m["num_classes"]),
            width=int(m["width"]), norm=m["norm"],
        )
        p, _, _ = harness.frame_shape(cfg["detector"])
        self.variables = harness.init_on_device(model, (1, 64, 64, p), seed)
        self.calib = harness.make_calibration(cfg["detector"], seed)
        self.calib_d = jax.device_put(tuple(self.calib))
        thr = float(cfg["calib_threshold"])
        stage_sizes = self.stage_sizes

        def hit_step(variables, calib, frames):
            x = fused_calibrate(frames, *calib, threshold=thr, out_dtype=jnp.bfloat16)
            return resnet_fused_infer(variables, panels_to_nhwc(x), stage_sizes=stage_sizes)

        hit_step.__name__ = STEP_NAME
        self._step = jax.jit(hit_step)
        self.sink = harness.CountingSink()
        self.frames_per_batch = int(cfg["batch_size"])
        self.warm_rows = 0
        self._metrics = None

    @property
    def metrics(self):
        return self._metrics

    def warm(self, frames: np.ndarray):
        import jax

        batch = harness.fill_batch(frames, self.frames_per_batch)
        for _ in range(2):
            jax.block_until_ready(self._step(self.variables, self.calib_d, jax.device_put(batch)))

    def run(self, queue) -> int:
        from psana_ray_tpu.infeed import InfeedPipeline

        # only what the configuration states is passed: every other
        # parameter keeps the program's default, whatever a later PR makes it
        options = {k: int(self.cfg[k]) for k in ("prefetch_depth", "batcher_buffers")
                   if k in self.cfg}
        pipe = InfeedPipeline(queue, batch_size=self.frames_per_batch, **options)
        self._metrics = pipe.metrics
        return pipe.run(
            lambda batch: self._step(self.variables, self.calib_d, batch.frames),
            on_result=self.sink, block_until_ready=True,
        )

    def check(self, frames: np.ndarray) -> dict:
        import jax
        import jax.numpy as jnp

        from benchmark.reference import calib as ref_calib
        from benchmark.reference import resnet50 as ref_model

        n_ref = int(self.cfg["reference"]["frames"])
        batch = harness.fill_batch(frames, self.frames_per_batch)
        got = np.asarray(self._step(self.variables, self.calib_d, jax.device_put(batch)))[:n_ref]
        thr = float(self.cfg["calib_threshold"])
        stage_sizes = self.stage_sizes

        @jax.jit
        def reference_logits(v, c, f):
            x = jnp.transpose(ref_calib.calibrate(f, *c, threshold=thr), (0, 2, 3, 1))
            return (ref_model.forward(v["params"], x, stage_sizes),
                    ref_model.forward(v["params"], x, stage_sizes, compute=jnp.bfloat16))

        with jax.default_matmul_precision("highest"):
            want, stated = (np.asarray(a) for a in reference_logits(
                self.variables, self.calib_d, jnp.asarray(batch[:n_ref])))
        return {**harness.precision_verdict(got, want, stated), "reference_frames": n_ref}
