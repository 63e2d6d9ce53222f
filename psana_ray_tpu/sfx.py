"""SFX serving pipeline: stream -> calibrate -> PeakNet -> peaks -> CXI.

This is the assembled capability the reference's own packaging names as
its mission — "Save PeakNet inference results to CXI" (reference
``setup.py:11``; SFX keyword ``setup.py:15``) — which its code never
ships (the consumers are opaque per-GPU torch loops; nothing writes CXI).
Every piece exists in this repo already; this module is the wiring plus
the operator CLI:

    transport queue -> fixed-shape batcher -> [fused calibration ->]
    PeakNet-TPU segmentation -> find_peaks -> CxiWriter (+ StreamCursor)

TPU structure: calibration + U-Net + peak extraction compile into ONE
jitted device program per batch shape (fixed shapes from the batcher; the
peak list is top-K padded, so streaming never recompiles); only the
final ``(yx, score, n)`` tuples come back to the host, where panel-local
coordinates fold into the CrystFEL-style unassembled layout and append to
the CXI file. The serving loop is one thread with at most two batches
dispatched and undrained: batch N runs on device while batch N-1's host
fold + HDF5 append proceed (JAX's async dispatch — blocking only happens
at the ``device_get`` drain), so host write time hides under device
compute instead of serializing with it. A batch is drained right after
the next one is launched, or — when its step ends before the next batch
has filled — between two turns of the batcher, as soon as its result is
seen ready (:meth:`SfxPipeline.run`): a result never waits on the device
for the stream.

A frame's bytes cross to the device in the turn of the batcher in which
they landed, not when the batch fills: frames that arrive without
filling the arena are put on the device between two turns, one
``[P, H, W]`` array a frame, so the launch carries only the frames of
the turn that completed the batch (at a paced stream, one of sixteen)
and the step starts that much sooner. An arena that one turn fills
crosses whole at the launch, as it always did. Both ways the step is
ONE compiled program over B per-frame arrays.

The loop's thread is always inside one phase (``utils.trace.phase``; the
vocabulary is ``obs.stages.PHASES``): the batcher's ``queue_wait`` /
``decode`` / ``copy``, then ``put_ahead`` (the ``device_put`` call for
frames that landed without filling the batch) or ``launch`` (the put of
the frames not yet on the device + the jit call), and for the batch
before it ``device_wait`` (the ``device_get`` drain), ``fold`` (panel
rows -> per-event peak sets) and ``append`` (``writer.append`` +
cursor); an early drain's three lie between two turns of the batcher,
ahead of that turn's ``put_ahead``. Each is a
``stage.<name>`` region on the profiler's timeline, one observation per
batch in ``metrics.stages``, and one span in the trace spool.

Coordinate convention (``peakYPosRaw``/``peakXPosRaw``): the cheetah-style
vertically stacked panel layout — ``y_raw = panel * H + y_panel``,
``x_raw = x_panel`` — the unassembled frame CrystFEL pairs with a
geometry file. Downstream indexing consumes these directly.

Resume: at-least-once via :class:`~psana_ray_tpu.checkpoint.StreamCursor`
(``--cursor_path``). After a crash-restart the producer re-sends anything
past the durable watermark, so a resumed run may re-append events the
previous run already wrote — dedupe on the ``(shard_rank, event_idx)``
columns the writer records per event, or write each run to its own file
and merge.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from psana_ray_tpu.obs.jitwatch import WATCH
from psana_ray_tpu.obs.stages import (
    PHASE_APPEND,
    PHASE_DEVICE_WAIT,
    PHASE_FOLD,
    PHASE_LAUNCH,
    PHASE_PUT_AHEAD,
    observe_batch_done,
    observe_frame_stages,
)
from psana_ray_tpu.utils.trace import phase


@dataclasses.dataclass
class SfxConfig:
    """Knobs of the assembled pipeline (CLI flags parse into this)."""

    # frames per device dispatch. 8 is the measured throughput knee on
    # v5e for the s2d=2 step (B=2/4/8 -> 119/111/145 fps/chip: the
    # 128-panel-row batch tiles the U-Net convs better); per-dispatch
    # latency is ~55 ms at B=8 — latency-sensitive consumers should pass
    # a smaller --batch, throughput (CXI production) wants this default
    batch_size: int = 8
    peak_threshold: float = 0.5  # sigmoid prob floor for find_peaks
    # per-PANEL candidate cap inside find_peaks (fixed device shapes); the
    # per-EVENT cap in the CXI file is writer.max_peaks — an event keeps
    # its writer.max_peaks brightest candidates across all panels
    max_peaks: int = 128
    # local-max window radius: 2 px suppresses the adjacent-duplicate
    # detections inside one peak blob (measured: precision 0.42 -> 0.99
    # at equal threshold on the synthetic oracle)
    min_distance: int = 2
    calib_threshold: float = 10.0  # ADU zero-floor inside fused_calibrate


# Per-mode default find_peaks thresholds, keyed by s2d — calibrated on
# the synthetic oracle's precision/recall sweep (a 320-step training
# probe). With an adequately trained checkpoint BOTH modes saturate the
# oracle across a wide threshold range (s2d=4 at 320 steps:
# recall/precision 1.0/1.0 at thr 0.3-0.5, degrading only gently above —
# 0.6 still scores 0.98/1.0), so 0.5 is the shared default for both
# modes: inside the saturated range, matching s2d=2's calibrated knee,
# with mild degradation rather than a cliff on either side. Earlier
# rounds shipped s2d=4 at 0.8 with a "triage-only" warning; a step sweep
# showed that quarter-res precision ceiling was an UNDERTRAINING
# artifact of the then-16-step probe (16 steps -> prec ~0.2-0.5 and an
# unstable knee; 192 -> 0.97; 320 -> 1.00), not a resolution limit.
# Operating guidance: with a converged checkpoint s2d=4 is a
# full-quality operating point that runs the trunk at a quarter of the
# resolution (no ledger cell measures it yet: ROADMAP R1); an
# UNDERTRAINED s2d=4 checkpoint degrades toward over-prediction, so
# raise --peak_threshold if CXI output from an early checkpoint floods
# downstream indexing.
DEFAULT_THRESHOLDS = {2: 0.5, 4: 0.5}


def infer_s2d(params, num_classes: int = 1) -> int:
    """Read the space-to-depth factor out of a serving checkpoint: the
    logits head emits ``num_classes * s2d**2`` channels
    (models/unet_tpu.py depth-to-space head), so the factor — and hence
    the quality (s2d=2) vs throughput (s2d=4) operating mode — is a
    property of the TRAINED tree, not something the operator must
    remember to pass consistently."""
    try:
        kern = params["logits"]["kernel"]
        kern = getattr(kern, "value", kern)  # unbox LogicallyPartitioned
        out_ch = int(np.shape(kern)[-1])
    except (KeyError, TypeError) as e:
        raise ValueError(
            "params tree has no logits/kernel leaf — is this a PeakNetUNetTPU "
            "serving checkpoint (export_serving_params output)?"
        ) from e
    s2d = math.isqrt(out_ch // num_classes)
    if s2d * s2d * num_classes != out_ch:
        raise ValueError(
            f"logits head emits {out_ch} channels, not num_classes*s2d^2 "
            f"for any integer s2d"
        )
    return s2d


def infer_features(params) -> Tuple[int, ...]:
    """Read the encoder widths out of a serving checkpoint: ``ConvBlock_i``'s
    first conv emits ``features[i]`` channels (encoder blocks ``0..n-2``
    plus the bottleneck ``n-1`` — models/unet_tpu.py builds them in that
    order, so flax's auto-numbering IS the features index). Like the s2d
    factor, the widths are a property of the TRAINED tree — the CLI's
    ``--features`` is a cross-check, not something the operator must keep
    in sync by hand."""
    widths = []
    while True:
        blk = params.get(f"ConvBlock_{len(widths)}") if hasattr(params, "get") else None
        if blk is None:
            break
        try:
            kern = blk["Conv_0"]["kernel"]
        except (KeyError, TypeError) as e:
            raise ValueError(
                f"ConvBlock_{len(widths)} has no Conv_0/kernel leaf — is this "
                f"a PeakNetUNetTPU serving checkpoint?"
            ) from e
        kern = getattr(kern, "value", kern)  # unbox LogicallyPartitioned
        widths.append(int(np.shape(kern)[-1]))
    if not widths:
        raise ValueError(
            "params tree has no ConvBlock_0 — is this a PeakNetUNetTPU "
            "serving checkpoint (export_serving_params output)?"
        )
    return tuple(widths)


class SfxPipeline:
    """The assembled stream->CXI serving loop.

    ``variables`` is the ``norm='frozen'`` serving tree
    (:func:`~psana_ray_tpu.models.fold.export_serving_params` output,
    loaded back with :func:`~psana_ray_tpu.checkpoint.load_params`);
    the s2d operating mode AND encoder widths are inferred from it.
    ``calib`` is an optional ``(pedestal, gain, mask)`` triple of
    ``[P, H, W]`` arrays — give it when the stream carries RAW ADUs; omit
    it for producer-calibrated (``--calib``) streams.

    ``features=None`` (default) infers the widths from the checkpoint;
    an explicit tuple is cross-checked against the tree and refused on
    mismatch (an early clear error instead of a shape failure deep in
    the first apply).
    """

    def __init__(
        self,
        variables,
        writer,
        features: Optional[Tuple[int, ...]] = None,
        calib: Optional[tuple] = None,
        config: Optional[SfxConfig] = None,
    ):
        import jax

        from psana_ray_tpu.models import PeakNetUNetTPU

        self.cfg = config or SfxConfig()
        self.writer = writer
        params = variables.get("params", variables)
        self.s2d = infer_s2d(params)
        self.features = infer_features(params)
        if features is not None and tuple(features) != self.features:
            raise ValueError(
                f"features={tuple(features)} does not match the checkpoint "
                f"(trained with {self.features}); the widths are a property "
                f"of the tree — drop the explicit features/--features"
            )
        self._model = PeakNetUNetTPU(
            features=self.features, norm="frozen", s2d=self.s2d
        )
        # Weights and calibration constants are device-resident ARGUMENTS
        # of the compiled step, placed once here. Values a jit closes
        # over are baked into the program as literals: the compiled step
        # (and its persistent-cache entry) would carry ~56 MB of them and
        # be keyed by the checkpoint's values, a cold compile per
        # checkpoint.
        self._variables = jax.device_put({"params": params})
        self._calib = None if calib is None else jax.device_put(tuple(calib))
        self._jit_step = jax.jit(self._device_step)
        self.n_events = 0
        self.n_peaks = 0
        # events/s, bytes/s, per-batch device-wait latency; a registry
        # source for the --metrics_port endpoint (obs.MetricsRegistry)
        from psana_ray_tpu.utils.metrics import PipelineMetrics

        self.metrics = PipelineMetrics()

    # -- the one compiled program ----------------------------------------
    def _device_step(self, variables, calib, frames):
        """Raw-or-calibrated frames -> panel-row peak tuples ``(yx [B*P,
        K, 2], score [B*P, K], n [B*P])``. ``frames`` is what it is given:
        a sequence of B per-frame ``[P, H, W]`` arrays (the form the loop
        serves: each frame went to the device on its own, and the stack
        is the step's first operation — on the TPU one in-place
        ``dynamic-update-slice`` fusion a frame, 0.09 ms of a 74 ms step
        at batch 16, which the convert ahead of the calibration kernel
        wins back by reading the batch from where they wrote it), or one
        ``[B, P, H, W]`` array, taken as it is. Pure in its arguments (``calib`` is the
        ``(pedestal, gain, mask)`` triple or None), so it also runs per
        shard under ``shard_map``. Calibration stays ONE call over the
        whole batch either way.

        The three parts run under named scopes (``calib``, ``peaknet``,
        ``find_peaks``), by which a device trace finds their ops again
        whatever the fusions are numbered. Each part is a NESTED jit,
        which XLA inlines — the compiled program is the same — because
        of what its exporter keeps: the name stack of a call site reaches
        every op inlined from the call, but the stack on the enclosing
        function's own ops is lost unless locations carry full tracebacks,
        which ``configure_compile_cache`` turns off so that Pallas kernels
        key alike from every entry point (looked at on the v5e, PR 24:
        the profile's own HLO said ``conv_general_dilated``, no more)."""
        import jax
        import jax.numpy as jnp

        from psana_ray_tpu.models import panels_to_nhwc
        from psana_ray_tpu.models.peaks import find_peaks

        cfg = self.cfg
        x = jnp.stack(frames) if isinstance(frames, (tuple, list)) else frames
        if calib is not None:
            from psana_ray_tpu.ops import fused_calibrate  # itself a jit

            ped, gain, mask = calib
            with jax.named_scope("calib"):
                x = fused_calibrate(
                    x, ped, gain, mask,
                    threshold=cfg.calib_threshold, out_dtype=jnp.bfloat16,
                )
        # the head's logits stay packed as the model computes them, and
        # ``find_peaks`` reads them so (``s2d`` is the checkpoint's own):
        # no full-resolution map between the two
        with jax.named_scope("peaknet"):
            logits = jax.jit(functools.partial(self._model.apply, packed=True))(
                variables, panels_to_nhwc(x, mode="batch")
            )
        with jax.named_scope("find_peaks"):
            return jax.jit(
                lambda lg: find_peaks(
                    lg,
                    max_peaks=cfg.max_peaks,
                    threshold=cfg.peak_threshold,
                    min_distance=cfg.min_distance,
                    s2d=self.s2d,
                )
            )(logits)

    def _step(self, frames):
        """The compiled step on this pipeline's own weights and constants
        (``frames``: either form of :meth:`_device_step`; a tuple and an
        array are two programs, and the loop serves the tuple alone)."""
        return self._jit_step(self._variables, self._calib, frames)

    # -- host side: panel rows -> per-event raw-coordinate peak sets ------
    def dispatch(self, batch, staged: Sequence = ()):
        """Enqueue one batch's device step WITHOUT waiting for the result.

        ``staged`` holds the device arrays of the batch's first
        ``len(staged)`` frames, put there as they landed (:meth:`run`);
        the rows not yet on the device — all of them for a batch nobody
        staged, the padding of a tail — follow here in ONE
        ``jax.device_put`` of their views, and the step is called with
        the B per-frame arrays: always that form, so every caller
        (:meth:`run`, :meth:`process_batch`, a warm-up through either)
        shares one compiled program. Both calls return once the transfers
        and the computation are enqueued; a staged frame's transfer began
        turns ago. Pairing this with :meth:`drain` one batch later
        overlaps the device program for batch N with the host-side peak
        fold and HDF5 append for batch N-1 (the serial loop leaves the
        chip idle for the whole host phase). :meth:`run` drains a handle
        after the next launch at the latest, and sooner once its outputs
        answer ``is_ready()``; results are bit-identical to the serial
        path and to a batch that crossed whole.

        The ``launch`` phase; ``metrics.frames_staged_ahead`` counts the
        frames that were on their way before it began. A timed batch's
        per-frame stamps are folded right after it, while the device
        works (``obs.stages.observe_frame_stages``)."""
        import jax

        if staged:
            self.metrics.frames_staged_ahead.add(len(staged))
        with phase(PHASE_LAUNCH, self.metrics, batch.batch_id, batch.num_valid):
            rest = jax.device_put(list(batch.frames[len(staged):]))  # a view a frame
            out = self._step((*staged, *rest))
        observe_frame_stages(self.metrics.stages, batch)
        return out, batch

    def drain(self, pending, cursor=None,
              on_appended: Optional[Callable[[int], None]] = None) -> int:
        """Block on a :meth:`dispatch` handle and append its REAL events
        to the CXI file; returns the number of events appended. Padding
        rows never reach the file; the cursor (if given) advances only
        after an event is written. ``on_appended(n)`` (optional) runs
        last inside the ``append`` phase: :meth:`run` saves the cursor
        there.

        Three phases: ``device_wait``, ``fold``, ``append``. The batch's
        ``e2e`` ends here, at append-done (``obs.stages.
        observe_batch_done``)."""
        import jax

        out, batch = pending
        _, p, h, _ = batch.frames.shape
        metrics = self.metrics
        mark = (metrics, batch.batch_id, batch.num_valid)
        with phase(PHASE_DEVICE_WAIT, *mark) as ph:
            # ONE readback of the three results (``device_get`` starts
            # every copy, then waits): each blocking round trip to the
            # device pays the runtime's wake-up latency, which differs by
            # half a millisecond with the host's state, and three in a row
            # made a paced frame's latency follow that state three times
            yx, score, n = jax.device_get(out)
        # device-wait latency: the step time NOT hidden behind the host
        # fold/append of the previous batch, or behind the stream (an
        # early drain's is the readback alone: the step had ended)
        metrics.observe_batch(
            int(np.sum(batch.valid)), ph.t1 - ph.t0,
            nbytes=int(getattr(batch.frames, "nbytes", 0)),
        )
        with phase(PHASE_FOLD, *mark):
            sets = self._fold(batch, yx, score, n, p, h)
        with phase(PHASE_APPEND, *mark) as ph:
            self.writer.append(sets)
            if cursor is not None:
                for s in sets:  # after the append: watermark never runs ahead
                    cursor.advance(s.shard_rank, s.event_idx)
            self.n_events += len(sets)
            if on_appended is not None:
                on_appended(len(sets))
        observe_batch_done(metrics.stages, batch, ph.t1)
        return len(sets)

    def _fold(self, batch, yx, score, n, p: int, h: int) -> list:
        """Panel rows -> one raw-coordinate :class:`PeakSet` per REAL event."""
        from psana_ray_tpu.cxi import PeakSet

        sets = []
        for i in range(len(batch.frames)):
            if not batch.valid[i]:
                continue
            ys, xs, ss = [], [], []
            for panel in range(p):
                row = i * p + panel
                k = int(n[row])
                ys.append(yx[row, :k, 0].astype(np.float32) + panel * h)
                xs.append(yx[row, :k, 1].astype(np.float32))
                ss.append(score[row, :k].astype(np.float32))
            ys, xs, ss = (np.concatenate(a) for a in (ys, xs, ss))
            if len(ss) > self.writer.max_peaks:  # keep the brightest
                keep = np.argsort(-ss)[: self.writer.max_peaks]
                ys, xs, ss = ys[keep], xs[keep], ss[keep]
            sets.append(
                PeakSet(
                    event_idx=int(batch.event_idx[i]),
                    shard_rank=int(batch.shard_rank[i]),
                    y=ys, x=xs, intensity=ss,
                    photon_energy=float(batch.photon_energy[i]),
                )
            )
            self.n_peaks += len(ss)
        return sets

    def process_batch(self, batch, cursor=None) -> int:
        """Serial convenience: :meth:`dispatch` + :meth:`drain` in one
        call (no overlap; :meth:`run` pipelines them instead)."""
        return self.drain(self.dispatch(batch), cursor=cursor)

    def run(
        self,
        queue,
        poll_interval_s: float = 0.01,
        cursor=None,
        cursor_path: Optional[str] = None,
        cursor_save_every: int = 32,
        stop=None,
        max_events: Optional[int] = None,
    ) -> int:
        """Drain ``queue`` to EOS (or ``stop``/``max_events``) through the
        pipeline; returns events written this run.

        One thread, at most two batches dispatched and undrained. Batch
        N's device step executes while batch N-1's peaks fold into raw
        coordinates and append to the HDF5 file on the host (see
        :meth:`dispatch`) — the serial loop pays host-write time as chip
        idle time.

        WHEN a frame's bytes go to the device follows what the loop
        sees: frames that land without filling the arena are put there
        at the end of that turn of the batcher (its ``rows_landed``
        hook, after any early drain: a finished result goes to the file
        first), one ``[P, H, W]`` array each in one asynchronous
        ``jax.device_put`` (the ``put_ahead`` phase: the call returns,
        nothing waits), and wait on the device for their batch; the
        launch then carries the frames of the filling turn alone. Frames
        that fill an arena in one turn cross at the launch. Only real
        rows are staged (a tail's padding is written at the flush, and
        crosses at the launch), and only for the batcher's CURRENT
        arena, whose array the emitted batch carries: what was staged
        belongs to that batch by identity, and a ``stop`` or a raise
        drops it with the arena. The arenas are fresh ones (no
        ``n_buffers``): an asynchronous put may read a row after the
        batcher has moved on.

        WHEN a batch is drained follows what the loop sees too:

        - right after the next batch is launched, blocking on the result
          (the one-deep schedule) — where frames outrun the device, the
          batcher emits a batch every turn and this is the only drain;
        - or earlier, between two turns of the batcher that emitted
          nothing, as soon as every output of its step answers
          ``is_ready()`` — where the device outruns the frames, a result
          reaches the file when its step ends (seen at the next frame's
          arrival, or the next empty poll), not when the next batch
          fills. That drain never waits for the device, so the thread is
          back at the transport after one readback, ``fold`` and
          ``append``; ``metrics.drained_ahead`` counts such batches.
          While a dispatched batch is undrained the pop's wait ends
          every millisecond (the live ``poll_s`` dial), so the step's
          end is seen within one: at frame arrivals alone it is seen up
          to a frame period late, and how late follows where the step
          time happens to fall between two frames.

        The in-flight batch is always drained before returning (it was
        dispatched, and the producer will not re-send it), so ``stop``
        and ``max_events`` may overshoot the serial loop's stopping point
        by one extra batch: up to ``2*batch_size - 1`` events past the
        bound, vs the serial loop's ``batch_size - 1``. A drain that
        raises, early or not, surfaces from here: its handle is not
        drained again, and the cursor is saved over what was written.

        When the process's first result is out the loop logs ONE line at
        INFO (``obs.jitwatch``): seconds since the process started, what
        JAX traced, lowered, loaded from the compile cache and compiled
        until then, and which functions; a load or compile after it is a
        flight-recorder event ``recompile``."""
        from psana_ray_tpu.infeed.batcher import DrainControl, batches_from_queue

        start = self.n_events
        dials = DrainControl()  # the pop's live dial
        ask_every_s = min(poll_interval_s, 0.001)  # after a running step

        def _watch_the_step(running: bool) -> None:
            dials.poll_s = ask_every_s if running else None

        def _save_cursor(wrote: int) -> None:
            if (self.n_events // cursor_save_every) != (
                (self.n_events - wrote) // cursor_save_every
            ):
                cursor.save(cursor_path)

        saving = cursor is not None and cursor_path and cursor_save_every > 0
        on_appended = _save_cursor if saving else None

        def _drain_one(pending) -> bool:
            """Drain + cursor bookkeeping; True = hit the max_events bound."""
            self.drain(pending, cursor=cursor, on_appended=on_appended)
            if not WATCH.serving:  # the first result is out: the start's account, one line
                WATCH.first_result("SfxPipeline.run")
            return max_events is not None and self.n_events - start >= max_events

        pending = None
        # the current arena's first rows, already on the device
        ahead_of: Optional[np.ndarray] = None
        ahead: list = []
        in_put_ahead = phase(PHASE_PUT_AHEAD, self.metrics)  # built once: it can run every turn

        def _put_ahead(arena: np.ndarray, lo: int, hi: int) -> None:
            """The batcher's ``rows_landed``: start rows ``lo:hi`` of the
            arena on their way to the device, a ``[P, H, W]`` array each."""
            nonlocal ahead_of, ahead
            import jax

            if ahead_of is not arena:  # the first rows of a new arena
                ahead_of, ahead = arena, []
            rows = arena[lo:hi]
            in_put_ahead.frames, in_put_ahead.nbytes = hi - lo, rows.nbytes
            with in_put_ahead:
                ahead += jax.device_put(list(rows))

        def _staged_for(batch) -> Sequence:
            """What was put ahead of ``batch``, if it is that arena's."""
            nonlocal ahead_of, ahead
            staged = ahead if ahead_of is batch.frames else ()
            ahead_of, ahead = None, []
            return staged

        def _drain_if_ready() -> Optional[bool]:
            """The batcher's ``between_turns``: drain the pending batch if
            its step has ended (asked, never waited for). None = nothing
            was drained; True = hit the max_events bound."""
            nonlocal pending
            if pending is None or not all(a.is_ready() for a in pending[0]):
                return None
            prev, pending = pending, None  # as below: never drained twice
            _watch_the_step(False)
            hit = _drain_one(prev)
            self.metrics.drained_ahead.add(1)
            return hit

        try:
            for batch in batches_from_queue(
                queue, self.cfg.batch_size, poll_interval_s=poll_interval_s,
                stop=stop, control=dials, metrics=self.metrics,
                between_turns=_drain_if_ready, rows_landed=_put_ahead,
            ):
                nxt = self.dispatch(batch, _staged_for(batch))
                _watch_the_step(True)
                # clear ``pending`` BEFORE draining it: if drain raises
                # after its writer.append, the finally below must not
                # drain the same handle again (duplicate CXI rows)
                prev, pending = pending, None
                if prev is not None and _drain_one(prev):
                    pending = nxt
                    break
                pending = nxt
        finally:
            try:
                if pending is not None:
                    prev, pending = pending, None
                    _drain_one(prev)
            finally:
                # the durable watermark is saved even when a drain raised
                # (everything it covers WAS written)
                if cursor is not None and cursor_path:
                    cursor.save(cursor_path)
        return self.n_events - start


def main(argv=None):
    """``psana-ray-tpu-sfx`` — the operator CLI for the stream->CXI loop.

    Minimal bring-up (producer already streaming calibrated frames):

        psana-ray-tpu-sfx --address shm://sfx --serving_params /data/pn \\
            --output run42.cxi --cursor_path run42.cursor --cursor_stride 4
    """
    import argparse
    import logging
    import signal

    from psana_ray_tpu.utils.hostmem import enable_large_alloc_reuse

    enable_large_alloc_reuse()
    ap = argparse.ArgumentParser(prog="psana-ray-tpu-sfx")
    ap.add_argument("--ray_address", "--address", dest="address", default="auto")
    ap.add_argument("--ray_namespace", "--namespace", dest="namespace", default="default")
    ap.add_argument("--queue_name", default="shared_queue")
    ap.add_argument("--output", required=True, help="CXI (HDF5) output path")
    ap.add_argument(
        "--serving_params", required=True,
        help="serving checkpoint dir (export_serving_params output; the "
        "quality/throughput mode is inferred from its s2d factor)",
    )
    ap.add_argument(
        "--mode", choices=["auto", "quality", "throughput"], default="auto",
        help="cross-check the checkpoint's operating point: 'quality' "
        "asserts s2d=2, 'throughput' asserts s2d=4, 'auto' trusts the "
        "checkpoint",
    )
    ap.add_argument(
        "--features", default="auto",
        help="comma-separated encoder widths as a cross-check against the "
        "checkpoint (default: inferred from it, like the s2d mode)",
    )
    ap.add_argument(
        "--calib_npz", default=None,
        help="npz with pedestal/gain/mask [P,H,W] arrays — give it when "
        "the stream carries RAW ADUs; omit for producer-calibrated streams",
    )
    ap.add_argument(
        "--batch", type=int, default=SfxConfig.batch_size,
        help="frames per device dispatch (default: the measured "
        "throughput knee; lower it for latency-sensitive serving)",
    )
    ap.add_argument(
        "--peak_threshold", type=float, default=None,
        help="sigmoid probability floor for a peak pixel (default: the "
        "mode's entry in sfx.DEFAULT_THRESHOLDS)",
    )
    ap.add_argument(
        "--max_peaks", type=int, default=128,
        help="per-EVENT cap: the CXI row width (brightest kept)",
    )
    ap.add_argument(
        "--panel_max_peaks", type=int, default=128,
        help="per-PANEL device-side candidate cap (fixed top-K shape in "
        "the compiled step) — distinct from the per-event --max_peaks",
    )
    ap.add_argument("--min_distance", type=int, default=2)
    ap.add_argument("--max_events", type=int, default=None)
    ap.add_argument("--cursor_path", default=None)
    ap.add_argument(
        "--cursor_stride", type=int, default=1,
        help="total producer shards (must match the producer topology)",
    )
    ap.add_argument("--cursor_save_every", type=int, default=32)
    ap.add_argument(
        "--overwrite", action="store_true",
        help="allow truncating an existing --output on a FRESH run "
        "(resumed runs — cursor already has positions — always append)",
    )
    from psana_ray_tpu.obs import (
        add_history_args,
        add_metrics_args,
        add_profile_args,
        add_trace_args,
    )
    from psana_ray_tpu.transport.addressing import add_cluster_args

    add_cluster_args(ap, consumer=True)

    add_metrics_args(ap)
    add_trace_args(ap)
    add_history_args(ap)
    add_profile_args(ap)
    ap.add_argument("--log_level", default="INFO")
    a = ap.parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, a.log_level.upper(), logging.INFO),
        format="%(asctime)s - %(levelname)s - %(message)s",
    )
    log = logging.getLogger("sfx")

    import os

    from psana_ray_tpu.utils.jaxenv import startup_line

    log.info(startup_line())  # once, before any work

    import dataclasses as dc

    from psana_ray_tpu.checkpoint import StreamCursor, load_params
    from psana_ray_tpu.config import TransportConfig
    from psana_ray_tpu.cxi import CxiWriter
    from psana_ray_tpu.transport.addressing import open_queue

    variables = load_params(a.serving_params)
    s2d = infer_s2d(variables.get("params", variables))
    want = {"quality": 2, "throughput": 4}.get(a.mode)
    if want is not None and s2d != want:
        log.error(
            "--mode %s expects s2d=%d but checkpoint %s was trained with "
            "s2d=%d; refusing (the mode is a property of the trained tree)",
            a.mode, want, a.serving_params, s2d,
        )
        return 1
    if a.peak_threshold is None:
        a.peak_threshold = DEFAULT_THRESHOLDS.get(s2d, 0.5)
    if a.features != "auto":
        try:
            features = tuple(int(f) for f in a.features.split(","))
        except ValueError:
            log.error(
                "--features %r is not a comma-separated integer list "
                "(or the default 'auto')", a.features,
            )
            return 1
        trained = infer_features(variables.get("params", variables))
        if features != trained:
            # same fail-fast shape as the --mode check: refuse before any
            # transport wait, not after the queue rendezvous
            log.error(
                "--features %s does not match checkpoint %s (trained with "
                "%s); the widths are a property of the tree — drop --features",
                a.features, a.serving_params, ",".join(map(str, trained)),
            )
            return 1

    calib = None
    if a.calib_npz:
        with np.load(a.calib_npz) as z:
            calib = (z["pedestal"], z["gain"], z["mask"])

    cursor = None
    if a.cursor_path:
        cursor = StreamCursor.load(a.cursor_path)
        if not cursor.positions:
            cursor.stride = a.cursor_stride
        elif cursor.stride != a.cursor_stride:
            log.error(
                "cursor %s has stride=%d but --cursor_stride=%d; refusing",
                a.cursor_path, cursor.stride, a.cursor_stride,
            )
            return 1

    import threading

    stop_ev = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop_ev.set())

    from psana_ray_tpu.transport.addressing import apply_cluster_args

    cfg = apply_cluster_args(
        dc.replace(
            TransportConfig(), address=a.address, queue_name=a.queue_name,
            namespace=a.namespace,
        ),
        a,
    )
    a.address = cfg.address  # --cluster rewrote it (monitor shares it)
    try:
        queue = open_queue(cfg, role="consumer", address=a.address)
    except Exception as e:
        log.error("could not open queue %s: %s", a.queue_name, e)
        return 1

    sfx_cfg = SfxConfig(
        batch_size=a.batch, peak_threshold=a.peak_threshold,
        max_peaks=a.panel_max_peaks, min_distance=a.min_distance,
    )
    log.info(
        "sfx pipeline up: s2d=%d (%s mode), threshold=%.3f, calib=%s",
        s2d, {2: "quality", 4: "throughput"}.get(s2d, f"s2d={s2d}"),
        a.peak_threshold, "on-device" if calib else "upstream",
    )
    # Output-file policy: a RESUMED run (the loaded cursor already has
    # positions) must append — truncating would permanently lose every
    # event the cursor has durably marked done (the producer won't re-send
    # them). A fresh run refuses to clobber an existing file unless told.
    resuming = cursor is not None and bool(cursor.positions)
    if resuming:
        writer_mode = "a"
    else:
        writer_mode = "w"
        if os.path.exists(a.output) and not a.overwrite:
            log.error(
                "%s exists and this is not a resume (cursor empty/absent); "
                "pass --overwrite to truncate it or point --output elsewhere",
                a.output,
            )
            return 1
    from psana_ray_tpu.obs import MetricsRegistry, start_metrics_server

    metrics_server = start_metrics_server(a.metrics_port, host=a.metrics_host)
    # history ring (ISSUE 13): flight-dump tails + /federate consumers
    from psana_ray_tpu.obs import configure_history_from_args, configure_profiling_from_args

    history = configure_history_from_args(a)
    # continuous profiler (ISSUE 16): --profile_hz 0 = off
    profiler = configure_profiling_from_args(a, "sfx")
    # queue depth for scrapes over a DEDICATED handle, never the data
    # connection: over TCP any opcode on the data connection implicitly
    # ACKs its in-flight GET deliveries (transport.tcp serve loop), so a
    # stats() probe from the metrics HTTP thread would confirm frames this
    # process is still folding and forfeit crash-redelivery
    monitor = None
    if metrics_server is not None:
        from psana_ray_tpu.consumer import DataReader

        try:
            monitor = DataReader(
                address=a.address, queue_name=a.queue_name,
                namespace=a.namespace, config=cfg,
            ).open_monitor()
        except Exception as e:  # noqa: BLE001 — depth is optional
            log.debug("queue monitor unavailable: %s", e)
    # sampled distributed tracing + flight recorder (shared flags): the
    # monitor handle doubles as the clock-anchor exchange channel — an
    # anchor RPC on the data connection would ACK in-flight deliveries
    from psana_ray_tpu.obs import configure_tracing_from_args

    configure_tracing_from_args(a, "sfx", queue=monitor)
    try:
        with CxiWriter(a.output, max_peaks=a.max_peaks, mode=writer_mode) as writer:
            # features already cross-checked above (one source of truth:
            # the constructor's check is for library callers)
            pipe = SfxPipeline(
                variables, writer, calib=calib, config=sfx_cfg
            )
            MetricsRegistry.default().register("sfx", pipe.metrics)
            if monitor is not None:
                pipe.metrics.attach_queue(monitor)
            import time

            t0 = time.monotonic()
            n = pipe.run(
                queue,
                cursor=cursor,
                cursor_path=a.cursor_path,
                cursor_save_every=a.cursor_save_every,
                stop=stop_ev,  # SIGINT -> clean stop between batches
                max_events=a.max_events,
            )
            dt = time.monotonic() - t0
            log.info(
                "end of stream: %d events, %d peaks -> %s (%.1f s wall, "
                "%.1f events/s incl. first-batch compile; %s)",
                n, pipe.n_peaks, a.output, dt, n / dt if dt > 0 else 0.0,
                pipe.metrics.status_line(),
            )
    except ValueError as e:
        # writer/params misconfiguration (foreign HDF5 layout, max_peaks
        # mismatch, bad checkpoint tree) — explain and exit, no traceback
        log.error("%s", e)
        return 1
    finally:
        if history is not None:
            history.stop()
        if metrics_server is not None:
            metrics_server.close()
        if monitor is not None and hasattr(monitor, "disconnect"):
            try:
                monitor.disconnect()
            except Exception:  # noqa: BLE001 — already closing
                pass
        if hasattr(queue, "disconnect"):
            queue.disconnect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
