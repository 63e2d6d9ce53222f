"""Program adapter ``prefill_reordered``: a decoder whose linear layers are
GATED DELTANET layers (the delta rule with ONE decay a head over a
rectangular float32 state carried along the sequence) with position-free
multi-head attention among them, a dense MLP in every layer and OLMo 2's
REORDERED norm around every branch (``x + rms(Op(x))``: nothing norms a
branch's input), as a frame reader through ``InfeedPipeline``: one of two
pipeline stages on one chip.

``prefill_batched``'s program and check, to the letter (queue ->
``batches_from_queue`` -> ``DevicePrefetcher`` -> one compiled
``decoder.frame_step`` -> a counting sink; ``patch_rows``, ``prompt_rows``
and ``isolated`` for the sequences the configuration names, ``head``,
``served``, each by that module's limits and for its reasons), with five
differences, none of them a loop or an option:

- the step runs under this adapter's name (``jit_olmo_hybrid_step`` in a
  trace);
- the output head is its own matrix (``tie_word_embeddings`` false), over
  every id, so the reference's logits read ``params["head"]`` (as
  ``prefill_latent`` does);
- the share of a part's rows that may lie over the rows' limit is this
  cell's own, ``TOSSED_ROWS_SHARE``, laid over ``prefill_batched``'s 0.7:
  this model has no router and no selection, so NO row is tossed and the
  share that a fault may break is small (granite's and ouro's 0.1). Its two
  readings (the program's largest over its seeds, the controls' least) are
  in PERF.md section 4;
- ``first_rows.i`` DECIDES here, by the same two limits (granite's reason:
  without a router a sequence's first rows read none over on any seed, and
  they are the part that sees the full layers' POSITIONS: a rotary planted in
  them moves a sequence's first rows, few keys each, and not the spread rows,
  where over thousands of keys a turned score is another draw of the same
  average);
- a package whose ``DecoderConfig`` knows no decay a head, no value width of
  its own, no reordered norm and no norm over a whole projection cannot run
  this configuration (its ``from_mapping`` fails on the file's
  ``rope_parameters``, or would build pre-normed Kimi Delta Attention under
  this model's name). The adapter looks for the fields (``MECHANISM``) FIRST
  and ends the run at once, with a non-zero exit code, where one is missing.

At ``batch_size`` 1 (the cell's) ``isolated.0`` is vacuous: the frame moved
one place on is the same frame. The configuration run at ``batch_size`` 2
(``benchmark/tests/olmo_hybrid_controls.py --batch 2``) is where the state
and the convolution are seen to stop at a sequence's edge on the chip."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.programs import prefill_batched, prefill_latent

STEP_NAME = "olmo_hybrid_step"  # the compiled program is jit_olmo_hybrid_step in a trace
TOSSED_ROWS_SHARE = 0.1  # of a decided part's rows, over the rows' limit; 0.7 in prefill_batched
# DecoderConfig fields this configuration needs
MECHANISM = ("linear_decay", "linear_value_dim", "linear_beta_scale", "pre_norm", "qk_norm_span")


class Program(prefill_batched.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        missing = [name for name in MECHANISM if name not in have]
        if missing:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no delta rule with a decay a head, no "
                "reordered norm or no norm over a whole projection (DecoderConfig lacks "
                f"{', '.join(missing)}): it cannot run " + str(cfg.get("name")))
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def olmo_hybrid_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        olmo_hybrid_step.__name__ = STEP_NAME
        self._step = jax.jit(olmo_hybrid_step)  # in place of the parents', which never ran

    # the reference's final norm and (untied) head on hidden rows, as kimi's adapter reads them
    reference_logits = prefill_latent.Program.reference_logits

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
