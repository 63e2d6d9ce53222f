"""Program adapter ``prefill_batched``: a decoder as a frame reader over
BATCHES of frames through ``InfeedPipeline``.

``prefill``'s program (queue -> ``batches_from_queue`` ->
``DevicePrefetcher`` -> one compiled ``decoder.frame_step`` -> a counting
sink; weights, calibration constants and the prompt arguments of the
step), with ``batch_size`` frames a step, each a sequence of its own, and
a reference the configuration names (``reference.module``): LFM2-8B-A1B's
trunk (``reference/lfm2_decoder.py``). No loop and no option of its own.

``check`` decides as ``prefill.check`` does, on what the timed step
computes at the timed sizes. The reference reads ONE sequence; the
sequences of the batch it is run on are ``reference.sequences`` (the first
and the last: a sequence that read its neighbour's tokens, through the
convolution or the attention, is any but the first). Per such sequence
``i``, by :func:`rows_verdict`:

- ``patch_rows.i`` and ``prompt_rows.i``: the trunk's output at ``ROWS``
  positions of each part (``prefill.check_parts``), from a second program
  over the same package functions (:meth:`Program.hidden`), against the
  reference's;
- ``isolated.i``: the SAME program on the batch with its frames moved
  one place on (sequence ``i`` then follows another neighbour, or none):
  its output at the sequence's first ``FIRST_ROWS`` positions must be what
  it was. A convolution that runs over the batch's rows as one sequence
  hands the previous sequence's last two tokens to this one's first two,
  nine such layers carry that 18 tokens on, and attention spreads it over
  what follows, thinning as 1/t: by position 134, the spread rows' second,
  it is under the rounding, so ``patch_rows`` cannot see it. Nothing is
  tossed here: the same program on the same rows decides alike, and reads
  0 exactly; the limit is what rounding the operands to bfloat16 moves
  those rows of the reference (their yardstick's lower quartile).
- ``first_rows.i``, for the record: those rows against the reference, by
  :func:`rows_verdict`. It decides nothing: a sequence's first rows (a
  token with few others to attend to; a blank patch's state is a mean of
  few) read 0 to 63% of their rows over the limit from seed to seed, and
  no limit between that and a leak's 100% leaves room for the sixty
  readings of a check.

and once: ``head`` (the second program's logits of the last checked
sequence against the reference's final norm and tied head on that
program's own hidden row), and ``served`` (the logits THE SERVED STEP
returned for ALL the batch's sequences against the second program's, by
no more than 4 times what rounding the operands to bfloat16 moves the
reference's logits)."""

from __future__ import annotations

import importlib

import numpy as np

from benchmark import harness
from benchmark.programs import prefill
from benchmark.programs.prefill import _row_errors, check_parts

STEP_NAME = "lfm2_step"  # the compiled program is jit_lfm2_step in a trace
FIRST_ROWS = 32  # the positions a leak across a sequence's start reaches, directly or at 1/t >= 1/2
RECORD_ONLY = ("first_rows",)  # parts printed with the verdict that decide nothing
# the two limits of rows_verdict; their two readings (the program's largest
# over its seeds, the controls' least) are in PERF.md section 4
LEVEL_QUANTILE = 0.25
TOSSED_ROWS_SHARE = 0.7


def first_and_spread(cfg: dict) -> dict:
    """The positions compared, by part: a sequence's first ``FIRST_ROWS``,
    then ``prefill.check_parts``' (the last of all is the last token's)."""
    first = np.arange(min(FIRST_ROWS, int(cfg["sequence_tokens"])))
    return {"first_rows": first, **check_parts(cfg)}


def rows_verdict(got, want_f32, want_stated) -> dict:
    """``prefill.rows_verdict``'s two questions, asked so that they hold
    where MOST rows may be tossed. Ten expert layers choose 4 of 32 by
    sigmoid affinities that lie close together: a choice inside the
    rounding noise goes either way in the program and in the yardstick
    independently, and on the chip 25-50% of a part's rows carry such a
    toss (the yardstick's own rows, bf16 operands against float32, lie
    16-28% over 4 of their medians). A median over rows of which half are
    tossed stands on the toss. So: (1) the rounding LEVEL is the rows'
    lower quartile, not their median: at most ``PRECISION_FACTOR`` times
    the yardstick's lower quartile. A fault in the mathematics moves
    every row, the lower quartile with them. (2) At most
    ``TOSSED_ROWS_SHARE`` of the rows lie over ``PRECISION_FACTOR``
    yardstick MEDIANS: how many rows a fault may break."""
    err, yard = _row_errors(got, want_f32), _row_errors(want_stated, want_f32)
    rows_level, yard_level = (float(np.quantile(e, LEVEL_QUANTILE)) for e in (err, yard))
    row_limit = harness.PRECISION_FACTOR * float(np.median(yard))
    over = float(np.mean(err > row_limit))
    return {
        "rows_relative_rms_level": rows_level, "yardstick_relative_rms_level": yard_level,
        "yardsticks": rows_level / max(yard_level, 1e-30),
        "limit": harness.PRECISION_FACTOR * yard_level,
        "rows_relative_rms_median": float(np.median(err)), "row_limit": row_limit,
        "rows_over_limit": over, "yardstick_rows_over_limit": float(np.mean(yard > row_limit)),
        "rows": int(len(err)),
        "ok": bool(rows_level <= harness.PRECISION_FACTOR * yard_level
                   and over <= TOSSED_ROWS_SHARE and np.isfinite(np.asarray(got)).all()),
    }


class Program(prefill.Program):
    """``prefill.Program`` (weights, prompt and calibration from the seed;
    ``warm``; ``run`` through ``InfeedPipeline``) with the step under this
    adapter's name, the configuration's own reference, and a check that
    knows of a batch."""

    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        super().__init__(cfg, seed, work_dir, devices)
        self.ref = importlib.import_module(f"benchmark.reference.{cfg['reference']['module']}")
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def lfm2_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        lfm2_step.__name__ = STEP_NAME
        self._step = jax.jit(lfm2_step)  # in place of the parent's, which never ran

    def hidden(self, batch: np.ndarray):
        """``(x [B*S, d], logits [B, V])`` for the raw frames ``batch [B,
        P, H, W]``: the trunk's output at every token, frame after frame,
        and the head on each frame's last, as ``frame_step`` computes
        them, in a program of its own."""
        import jax

        from psana_ray_tpu.models import decoder

        dcfg, threshold = self.dcfg, float(self.cfg["calib_threshold"])

        def hidden(params, calib, frames, prompt_ids):
            x, _ = decoder.frame_hidden(params, calib, frames, prompt_ids, cfg=dcfg,
                                        threshold=threshold)
            s = x.shape[0] // frames.shape[0]
            return x, decoder.logits_of(params, x[s - 1::s], dcfg)

        return jax.jit(hidden)(self.params, self.calib_d, jax.device_put(batch), self.prompt_ids)

    def reference_hidden(self, frame: np.ndarray, compute, **fault):
        """The reference trunk's output at every token of ONE raw frame
        ``[1, P, H, W]``: ``[S, d]`` float32, with the operands of every
        product rounded to ``compute``; ``fault`` as ``ref.sizes`` takes it."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import calib as ref_calib

        ref, m = self.ref, self.ref.sizes(self.cfg, **fault)
        patch, block = int(self.cfg["patch"]), int(self.cfg["reference"]["query_block"])
        threshold = float(self.cfg["calib_threshold"])
        one_layer = jax.jit(lambda p, x, kind: ref.layer(p, x, kind, m, compute, block),
                            static_argnums=2)  # one program a kind of layer
        with jax.default_matmul_precision("highest"):
            x = jax.jit(lambda p, c, f: ref.embed(
                p, ref.patches_of(ref_calib.calibrate(f, *c, threshold=threshold)[0], patch),
                self.prompt_ids, compute))(
                {k: self.params[k] for k in ("patch", "embed")}, self.calib_d, jnp.asarray(frame))
            for p, kind in zip(self.params["layers"], ref.kinds(m)):
                x = one_layer(p, x, kind)
        return x

    def reference_logits(self, rows, compute) -> np.ndarray:
        """The reference's final norm and tied head on hidden ``rows [N, d]``."""
        import jax
        import jax.numpy as jnp

        m = self.ref.sizes(self.cfg)
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda p, x: self.ref.logits_of(p, x, m, compute))(
                {k: self.params[k] for k in ("norm", "embed")}, jnp.asarray(rows, jnp.float32)))

    def check(self, frames: np.ndarray) -> dict:
        import time

        import jax
        import jax.numpy as jnp

        batch = harness.fill_batch(frames, self.frames_per_batch)
        logits = np.asarray(self._serve(jax.device_put(batch))[0])
        t0 = time.monotonic()
        parts = first_and_spread(self.cfg)
        at = np.concatenate(list(parts.values()))  # its last is the sequence's last token
        s = int(self.cfg["sequence_tokens"])
        x, own_logits = self.hidden(batch)
        moved, _ = self.hidden(np.roll(batch, 1, axis=0))  # sequence i now sits at i + 1
        own_logits = np.asarray(own_logits)
        verdict, decided = {}, []
        for i in (int(i) % len(batch) for i in self.cfg["reference"]["sequences"]):
            got = np.asarray(x[i * s + at], np.float32)
            want, stated = (np.asarray(self.reference_hidden(batch[i:i + 1], c)[at])
                            for c in (jnp.float32, jnp.bfloat16))
            lo = 0
            for name, positions in parts.items():
                rows = slice(lo, lo + len(positions))
                verdict[f"{name}.{i}"] = rows_verdict(got[rows], want[rows], stated[rows])
                if name not in RECORD_ONLY:
                    decided.append(f"{name}.{i}")
                lo += len(positions)
            first = parts["first_rows"]
            apart = harness.relative_rms(
                np.asarray(moved[(i + 1) % len(batch) * s + first], np.float32), got[:len(first)])
            limit = verdict[f"first_rows.{i}"]["yardstick_relative_rms_level"]
            verdict[f"isolated.{i}"] = {"relative_rms_to_itself_moved": apart, "limit": limit,
                                        "ok": bool(apart <= limit)}
            decided.append(f"isolated.{i}")
        # the head and the yardstick of `served`, on the last checked sequence
        verdict["head"] = harness.precision_verdict(
            own_logits[i:i + 1],
            *(self.reference_logits(got[-1:], c) for c in (jnp.float32, jnp.bfloat16)))
        want_logits = self.reference_logits(want[-1:], jnp.float32)
        yard = harness.relative_rms(self.reference_logits(stated[-1:], jnp.bfloat16), want_logits)
        apart = harness.relative_rms(logits, own_logits)
        verdict["served"] = {"relative_rms_to_own_program": apart, "sequences": int(len(logits)),
                             "limit": harness.PRECISION_FACTOR * yard,
                             "ok": bool(apart <= harness.PRECISION_FACTOR * yard)}
        # through every decision of the expert layers, for the record: decides nothing
        verdict["last_token_logits_yardsticks"] = (
            harness.relative_rms(logits[i:i + 1], want_logits) / max(yard, 1e-30))
        verdict["ok"] = bool(all(verdict[k]["ok"] for k in (*decided, "head", "served"))
                             and np.isfinite(logits).all())
        verdict["reference_seconds"] = time.monotonic() - t0
        return verdict
