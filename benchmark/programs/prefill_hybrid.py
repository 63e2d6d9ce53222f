"""Program adapter ``prefill_hybrid``: a decoder whose layers are LINEAR
attention (the gated delta rule with a decay per channel, a float32 state
a head carried along the sequence) with latent attention among them, as a
frame reader over batches of frames through ``InfeedPipeline``, on a
holder of a SHARE of each layer.

``prefill_batched``'s program and check, to the letter (queue ->
``batches_from_queue`` -> ``DevicePrefetcher`` -> one compiled
``decoder.frame_step`` -> a counting sink; ``patch_rows``, ``prompt_rows``
and ``isolated`` for the first and the last sequence of the batch,
``head``, ``served``, each by that module's limits and for its reasons),
with four differences, none of them a loop or an option:

- the step runs under this adapter's name (``jit_ling3_step`` in a trace);
- the output head is its own matrix (``tie_word_embeddings`` false), over
  the vocabulary slice this holder has, so the reference's logits read
  ``params["head"]`` (as ``prefill_latent`` does);
- the share of a part's rows that may lie over the rows' limit is this
  cell's own, ``TOSSED_ROWS_SHARE``, laid over ``prefill_batched``'s: a
  holder of 128 of 512 experts computes a tossed choice's row whenever the
  toss touches one of its quarter of the experts. Its two readings (the
  program's largest over its seeds, the controls' least) are in PERF.md
  section 4;
- a package whose ``DecoderConfig`` knows no linear attention, no
  full-rank latent query or no output gate cannot run this configuration:
  its ``from_mapping`` refuses the file's ``layer_types`` or the missing
  ``q_lora_rank``. The adapter looks for the fields (``linear_head_dim``,
  ``attn_gate``) first and ends the run at once, with a non-zero exit
  code, where one is missing; and, as ``prefill_latent``, where the file's
  ``num_experts`` (what the roofline functions and the reference count) is
  not the count of ``experts_held`` (what the program holds).

The holder's share is the reference's too (``reference/ling3_decoder.py``
is given the 128 held experts' weights, the shared expert and the
vocabulary slice the program has)."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.programs import prefill_batched, prefill_latent

STEP_NAME = "ling3_step"  # the compiled program is jit_ling3_step in a trace
TOSSED_ROWS_SHARE = 0.3  # of a decided part's rows, over the rows' limit; 0.7 in prefill_batched
MECHANISM = ("linear_head_dim", "attn_gate")  # DecoderConfig fields this configuration needs


class Program(prefill_batched.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        missing = [name for name in MECHANISM if name not in have]
        if missing:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no linear attention or no gated latent "
                f"attention (DecoderConfig lacks {', '.join(missing)}): it cannot run "
                + str(cfg.get("name")))
        if int(cfg["num_experts"]) != int(cfg["experts_held"][1]):
            # one fact under two keys: the program holds `experts_held`, the roofline functions
            # and the reference count `num_experts`
            raise SystemExit(
                f"[bench] ERROR: {cfg.get('name')}: num_experts {cfg['num_experts']} is not the "
                f"count of experts_held {cfg['experts_held']}")
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def ling3_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        ling3_step.__name__ = STEP_NAME
        self._step = jax.jit(ling3_step)  # in place of the parents', which never ran

    def check(self, frames: np.ndarray) -> dict:
        verdict = super().check(frames)
        decided = [name for name, v in verdict.items()
                   if isinstance(v, dict) and "rows_over_limit" in v
                   and name.split(".")[0] not in prefill_batched.RECORD_ONLY]
        for name in decided:
            v = verdict[name]
            v["rows_over_share_limit"] = TOSSED_ROWS_SHARE
            v["ok"] = bool(v["ok"] and v["rows_over_limit"] <= TOSSED_ROWS_SHARE)
        verdict["ok"] = bool(verdict["ok"] and all(verdict[name]["ok"] for name in decided))
        return verdict

    # the reference's final norm and (untied) head on hidden rows, as kimi's adapter reads them
    reference_logits = prefill_latent.Program.reference_logits
