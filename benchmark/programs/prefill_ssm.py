"""Program adapter ``prefill_ssm``: a decoder whose layers are STATE-SPACE
layers (Mamba-2's selective scan: one scalar decay a head and token, one
``B`` and ``C`` for all heads, a float32 state a head carried along the
sequence) with position-free grouped-query attention among them and a dense
MLP in every layer, as a frame reader through ``InfeedPipeline``, WHOLE on
one chip.

``prefill_batched``'s program and check, to the letter (queue ->
``batches_from_queue`` -> ``DevicePrefetcher`` -> one compiled
``decoder.frame_step`` -> a counting sink; ``patch_rows``, ``prompt_rows``
and ``isolated`` for the sequences the configuration names, ``head`` on the
tied table, ``served``, each by that module's limits and for its reasons),
with five differences, none of them a loop or an option:

- the step runs under this adapter's name (``jit_granite_step`` in a trace);
- the reference's embedding takes the reference's own reading of the
  configuration (its ``embedding_multiplier``), and its tied head divides by
  its ``logits_scaling``: :meth:`Program.reference_hidden` and
  :meth:`Program.reference_logits` hand it ``ref.sizes(cfg)``;
- the share of a part's rows that may lie over the rows' limit is this
  cell's own, ``TOSSED_ROWS_SHARE``, laid over ``prefill_batched``'s 0.7:
  this model has no router and no selection, so NO row is tossed and the
  share that a fault may break is small. Its two readings (the program's
  largest over its seeds, the controls' least) are in PERF.md section 4;
- ``first_rows.i`` DECIDES here, by the same two limits. ``prefill_batched`` keeps it for the record because a model with
  experts reads 0 to 63% of a sequence's first rows over the limit from seed
  to seed; this one has no router, and reads none over on any seed. It is
  the part that sees the attention layers' POSITIONS: a rotary planted in
  them moves a sequence's first rows (few keys: 5.4 yardsticks, 78-81% of
  the rows over) and not the spread rows (1.1-1.2: over thousands of keys
  with random weights a turned score is another draw of the same average);
- a package whose ``DecoderConfig`` knows no state-space layer and no
  multipliers cannot run this configuration (its ``from_mapping`` refuses
  the file's ``layer_types``). The adapter looks for the fields first and
  ends the run at once, with a non-zero exit code, where one is missing.

At ``batch_size`` 1 (the cell's) ``isolated.0`` is vacuous: the frame moved
one place on is the same frame. The configuration run at ``batch_size`` 2
(``benchmark/tests/granite_controls.py --batch 2``) is where the state and
the convolution are seen to stop at a sequence's edge on the chip."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.programs import prefill_batched

STEP_NAME = "granite_step"  # the compiled program is jit_granite_step in a trace
TOSSED_ROWS_SHARE = 0.1  # of a decided part's rows, over the rows' limit; 0.7 in prefill_batched
# DecoderConfig fields this configuration needs
MECHANISM = ("ssm_state", "residual_multiplier", "embedding_multiplier", "attention_multiplier",
             "logits_scaling", "rotary", "conv_bias")


class Program(prefill_batched.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        missing = [name for name in MECHANISM if name not in have]
        if missing:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no state-space layer or no multipliers "
                f"(DecoderConfig lacks {', '.join(missing)}): it cannot run "
                + str(cfg.get("name")))
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def granite_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        granite_step.__name__ = STEP_NAME
        self._step = jax.jit(granite_step)  # in place of the parents', which never ran

    def reference_hidden(self, frame: np.ndarray, compute, **fault):
        """``prefill_batched``'s, the reference's embedding given its own
        reading of the configuration (the multiplier on the embedded rows).
        A layer's bf16 weights are widened inside its own program: all 40
        in float32 would be 12.8 GB beside the program's 6.4."""
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
                self.prompt_ids, compute, m))(
                {k: self.params[k] for k in ("patch", "embed")}, self.calib_d, jnp.asarray(frame))
            for p, kind in zip(self.params["layers"], ref.kinds(m)):
                x = one_layer(p, x, kind)
        return x

    def reference_logits(self, rows, compute, **fault) -> np.ndarray:
        """The reference's final norm and tied head on hidden ``rows [N, d]``,
        over its own ``logits_scaling``."""
        import jax
        import jax.numpy as jnp

        m = self.ref.sizes(self.cfg, **fault)
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda p, x: self.ref.logits_of(p, x, m, compute))(
                {k: self.params[k] for k in ("norm", "embed")}, jnp.asarray(rows, jnp.float32)))

    def check(self, frames: np.ndarray) -> dict:
        verdict = super().check(frames)
        decided = [name for name, v in verdict.items()  # every part of rows, first_rows too
                   if isinstance(v, dict) and "rows_over_limit" in v]
        for name in decided:
            v = verdict[name]
            v["rows_over_share_limit"] = TOSSED_ROWS_SHARE
            v["ok"] = bool(v["ok"] and v["rows_over_limit"] <= TOSSED_ROWS_SHARE)
        verdict["ok"] = bool(verdict["ok"] and all(verdict[name]["ok"] for name in decided))
        return verdict
