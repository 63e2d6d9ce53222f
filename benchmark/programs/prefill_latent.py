"""Program adapter ``prefill_latent``: a decoder with LATENT attention as
a frame reader over batches of frames through ``InfeedPipeline``, on a
holder of a SHARE of each layer.

``prefill_batched``'s program and check, to the letter (queue ->
``batches_from_queue`` -> ``DevicePrefetcher`` -> one compiled
``decoder.frame_step`` -> a counting sink; ``patch_rows``, ``prompt_rows``
and ``isolated`` for the first and the last sequence of the batch,
``head``, ``served``, each by that module's limits and for its reasons),
with four differences, none of them a loop or an option:

- the step runs under this adapter's name (``jit_kimi_k2_step`` in a
  trace);
- the output head is its own matrix (``tie_word_embeddings`` false), over
  the vocabulary slice this holder has, so the reference's logits read
  ``params["head"]``;
- the share of a part's rows that may lie over the rows' limit is this
  cell's own, ``TOSSED_ROWS_SHARE``, laid over the parent's: a holder of
  12 of 384 experts computes a tossed choice's row only when the toss
  touches a held expert, so few rows are tossed (the program's largest
  reading over 15 runs 9.4%, where lfm2's was 48%) and a routing fault
  moves fewer rows too (the selection bias left out: 59-66%, under lfm2's
  70%). Both readings are in PERF.md section 4;
- a package whose ``DecoderConfig`` has no latent attention cannot run this
  configuration at all: its ``from_mapping`` ignores the keys it does not
  know and would build a dense grouped-query model of head width 112 under
  Kimi-K2's name. The adapter looks for the field and ends the run at once,
  with a non-zero exit code, where it is missing. It ends it too where the
  file's ``n_routed_experts`` (what the roofline functions and the reference
  count) is not the count of ``experts_held`` (what the program holds).

The holder's share is the reference's too (``reference/kimi_k2_decoder.py``
is given the 12 held experts' weights, the shared expert and the
vocabulary slice the program has)."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.programs import prefill_batched

STEP_NAME = "kimi_k2_step"  # the compiled program is jit_kimi_k2_step in a trace
TOSSED_ROWS_SHARE = 0.3  # of a decided part's rows, over the rows' limit; 0.7 in prefill_batched


class Program(prefill_batched.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        if "kv_lora_rank" not in have:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no latent attention (DecoderConfig lacks "
                "kv_lora_rank): it cannot run " + str(cfg.get("name")))
        if int(cfg["n_routed_experts"]) != int(cfg["experts_held"][1]):
            # one fact under two keys: the program holds `experts_held`, the roofline functions
            # and the reference count `n_routed_experts`
            raise SystemExit(
                f"[bench] ERROR: {cfg.get('name')}: n_routed_experts {cfg['n_routed_experts']} "
                f"is not the count of experts_held {cfg['experts_held']}")
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def kimi_k2_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        kimi_k2_step.__name__ = STEP_NAME
        self._step = jax.jit(kimi_k2_step)  # in place of the parents', which never ran

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

    def reference_logits(self, rows, compute) -> np.ndarray:
        """The reference's final norm and (untied) head on hidden ``rows [N, d]``."""
        import jax
        import jax.numpy as jnp

        m = self.ref.sizes(self.cfg)
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda p, x: self.ref.logits_of(p, x, m, compute))(
                {k: self.params[k] for k in ("norm", "head")}, jnp.asarray(rows, jnp.float32)))
