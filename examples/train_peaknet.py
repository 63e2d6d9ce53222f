"""Streaming PeakNet training, end to end: source -> transport -> batcher
-> sharded train step -> checkpoint.

The reference streams frames to opaque per-GPU torch loops
(``project.toml:4`` "Stream psana data ... for distributed, real-time
analysis and inference"); this is the training side of that capability,
TPU-first: a ``ProducerRuntime`` feeds a bounded queue, the infeed
batcher pads tails to fixed shapes, and a donated/jit'd train step runs
``PeakNetUNetTPU`` over a ('data',) mesh — on one chip, a CPU mesh, or a
pod slice with the same code.

Labels here are self-derived on device (peaks := calibrated pixels above
an SNR threshold) so the example runs anywhere without a labeled corpus;
swap ``labels_of`` for real CXI/psocake masks in production. Loss is
focal BCE (Bragg peaks are ~1e-4 of pixels; plain BCE collapses to the
background class).

Run (small, CPU-friendly):
    python examples/train_peaknet.py --steps 4

Convergence scale: on the synthetic oracle this recipe saturates peak
recall/precision around ~300 steps at batch 2 — the tiny defaults here demonstrate the plumbing,
not a finished detector.
"""

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8, help="train steps to run")
    ap.add_argument("--batch", type=int, default=2, help="frames per batch")
    ap.add_argument("--detector", default="epix100")
    ap.add_argument("--num_events", type=int, default=32)
    ap.add_argument("--checkpoint_dir", default=None, help="orbax save target")
    ap.add_argument(
        "--norm", default="group", choices=["group", "batch"],
        help="normalization for training: 'group' (row-independent, the "
        "robust default) or 'batch' (running statistics — REQUIRED for "
        "--export-serving, which folds them into the fused-inference "
        "FrozenAffine form)",
    )
    ap.add_argument(
        "--export-serving", default=None, metavar="DIR", dest="export_serving",
        help="after training, fold BatchNorm stats into FrozenAffine "
        "constants (models/fold.py) and save serving params here — the "
        "parameter form psana-ray-tpu-sfx serves. Implies --norm batch.",
    )
    ap.add_argument(
        "--features", default="16,32",
        help="comma-separated encoder widths (default keeps the example "
        "CPU-fast; 64,128,256,512 is the real PeakNet-TPU capacity "
        "psana-ray-tpu-sfx serves). The exported checkpoint "
        "carries the widths — sfx infers them back, no flag to keep in "
        "sync.",
    )
    ap.add_argument(
        "--s2d", type=int, default=2, choices=[2, 4],
        help="space-to-depth factor: 2 = quality mode, 4 = throughput "
        "mode (the operating point is baked into the trained tree; "
        "psana-ray-tpu-sfx reads it from the checkpoint)",
    )
    ap.add_argument(
        "--focal_alpha", type=float, default=0.95,
        help="focal-loss positive-class weight. At this domain's ~1e-4 "
        "peak-pixel fraction the textbook 0.25 collapses training to "
        "all-background within a few steps (measured on epix10k2M: "
        "recall 0.04 after 320 steps at 0.25 vs 1.00 at 0.95)",
    )
    ap.add_argument(
        "--lr", type=float, default=3e-3,
        help="learning rate (default: the quality probe's recipe; "
        "precision is the slow-saturating metric — at 1e-3 a 320-step "
        "epix10k2M run stops around precision 0.4 where 3e-3 saturates)",
    )
    args = ap.parse_args()
    try:
        args.features = tuple(int(f) for f in args.features.split(","))
    except ValueError:
        ap.error(f"--features {args.features!r} is not a comma-separated "
                 f"integer list")
    if args.export_serving:
        args.norm = "batch"

    from psana_ray_tpu.utils.hostmem import enable_large_alloc_reuse

    enable_large_alloc_reuse()

    import jax
    import jax.numpy as jnp
    import optax

    from psana_ray_tpu.config import PipelineConfig, SourceConfig
    from psana_ray_tpu.infeed import InfeedPipeline, StopStream
    from psana_ray_tpu.models import PeakNetUNetTPU, panels_to_nhwc
    from psana_ray_tpu.models.losses import masked_sigmoid_focal
    from psana_ray_tpu.ops import calibrate
    from psana_ray_tpu.parallel import create_mesh
    from psana_ray_tpu.parallel.steps import create_train_state, make_train_step
    from psana_ray_tpu.producer import ProducerRuntime
    from psana_ray_tpu.sources import SyntheticSource
    from psana_ray_tpu.transport.addressing import open_queue
    from psana_ray_tpu.utils.jaxenv import startup_line

    print(startup_line())  # once, at start: a fall to the CPU is visible

    # DP over every device; 'model' axis present (width 1) because the
    # models' logical-axis annotations name it — widen it on pod slices
    # for tensor parallelism
    mesh = create_mesh(("data", "model"), (jax.device_count(), 1))
    src = SyntheticSource(num_events=1, detector_name=args.detector, seed=0)
    pedestal = jnp.asarray(src.pedestal())
    # absolute gain (ADUs/photon): calibrate() divides by this, so the
    # net trains on PHOTON-scale inputs — the same scale the calib-mode
    # stream (and therefore psana-ray-tpu-sfx without --calib_npz)
    # serves. The relative map alone would leave outputs 35x hot and
    # the >50 label policy marking Poisson background as peaks.
    gain = jnp.asarray(src.spec.adu_gain * src.gain_map())
    mask = jnp.asarray(src.create_bad_pixel_mask())
    n_panels, h, w = src.spec.frame_shape

    # default widths keep the example training in seconds on CPU;
    # --features 64,128,256,512 is the real PeakNet-TPU capacity
    model = PeakNetUNetTPU(features=args.features, norm=args.norm, s2d=args.s2d)

    def labels_of(frames_nhwc):
        # stand-in ground truth: calibrated intensity over threshold.
        # Real runs: replace with CXI/psocake peak masks joined on
        # (shard_rank, event_idx).
        return (frames_nhwc > 50.0).astype(jnp.float32)

    def loss_fn(logits, batch_aux):
        targets, valid = batch_aux
        return masked_sigmoid_focal(logits, targets, valid, alpha=args.focal_alpha)

    opt = optax.adamw(args.lr)
    sample = jnp.zeros((args.batch * n_panels, h, w, 1))
    state = create_train_state(model, opt, jax.random.key(0), sample, mesh)
    step = make_train_step(model, opt, loss_fn)

    # the calibration constants are ARGUMENTS, not closed over: a jit bakes
    # closed-over arrays into the program as literals (19 MB here at
    # epix10k2M — and as much again in every compile-cache entry)
    @jax.jit
    def prepare(frames, valid, pedestal, gain, mask):
        c = calibrate(frames, pedestal, gain, mask, cm_algorithm="mean")
        x = panels_to_nhwc(c, mode="batch")  # [B*P, H, W, 1]
        targets = labels_of(x)
        row_valid = jnp.repeat(valid.astype(jnp.uint8), n_panels)
        return x, targets, row_valid

    # stream: producer -> bounded queue (in-process by default; set
    # cfg.transport.address to shm:///tcp://host:port for real clusters)
    # -> padded fixed-shape batches. The stream carries RAW ADUs because
    # prepare() calibrates on-device: the default calib-mode stream would
    # be calibrated TWICE here (pedestal subtracted from already-clean
    # photons), training the net on a distribution serving never sees —
    # measured on epix10k2M: the doubly-calibrated recipe tops out at
    # recall 0.73 / precision 0.45 where raw-in training saturates.
    cfg = PipelineConfig(
        source=SourceConfig(
            exp="synthetic", num_events=args.num_events,
            detector_name=args.detector, mode="raw",
        )
    )
    ProducerRuntime(cfg).run(block=False)
    queue = open_queue(cfg.transport)

    pipe = InfeedPipeline(
        queue, batch_size=args.batch, place_on_device=False,
        poll_interval_s=0.001,
    )
    losses = []
    t0 = time.perf_counter()

    def train_on(batch):
        if args.norm == "batch" and not all(batch.valid):
            # batch statistics see every row — a padded tail would poison
            # the running stats the serving export folds, so skip partial
            # batches (GroupNorm training has no such constraint)
            return None
        x, targets, row_valid = prepare(
            jnp.asarray(batch.frames), jnp.asarray(batch.valid),
            pedestal, gain, mask,
        )
        train_on.state, loss = step(train_on.state, x, (targets, row_valid))
        losses.append(float(loss))
        print(f"step {len(losses)}: loss {losses[-1]:.5f}")
        if len(losses) >= args.steps:
            raise StopStream  # quota reached: stop draining the stream
        return None

    train_on.state = state
    n = pipe.run(train_on)
    state = train_on.state
    dt = time.perf_counter() - t0
    trend = f"; loss {losses[0]:.5f} -> {losses[-1]:.5f}" if losses else ""
    print(
        f"trained {len(losses)} steps on {n} frames in {dt:.1f}s "
        f"(mesh={dict(mesh.shape)}){trend}"
    )

    if args.checkpoint_dir:
        from psana_ray_tpu.checkpoint import save_train_state

        save_train_state(args.checkpoint_dir, state)
        print(f"checkpointed to {args.checkpoint_dir}")

    if args.export_serving:
        from psana_ray_tpu.models import export_serving_params

        export_serving_params(state.variables, args.export_serving)
        print(
            f"serving params (norm='frozen' form) exported to "
            f"{args.export_serving} — consumable by "
            f"PeakNetUNetTPU(norm='frozen').apply (what psana-ray-tpu-sfx serves)"
        )


if __name__ == "__main__":
    main()
