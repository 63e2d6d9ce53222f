"""Program adapter ``prefill_latent_selected``: a decoder with a LEARNED KEY
SELECTION OVER LATENT attention as a frame reader through
``InfeedPipeline``, on a holder of a SHARE of each layer.

``prefill_latent``'s program and check, to the letter (queue ->
``batches_from_queue`` -> ``DevicePrefetcher`` -> one compiled
``decoder.frame_step`` -> a counting sink; ``patch_rows``, ``prompt_rows``
and ``isolated`` for the checked sequence, ``head`` over the untied head's
slice, ``served``), with three differences, none of them a loop or an
option:

- the step runs under this adapter's name (``jit_dsv32_step`` in a trace);
- the share of a part's rows that may lie over the rows' limit is this
  cell's own, ``TOSSED_ROWS_SHARE``, laid over ``prefill_batched``'s: beside
  a routing choice inside the rounding noise (which moves a row only when
  it touches one of the 8 held of 256 experts) a SELECTED SET differs
  between bf16 index scores and the reference's near the 2,048th score,
  in every layer and for every query. Its two readings (the program's
  largest over its seeds, the controls' least) are in PERF.md section 4;
- a package whose ``DecoderConfig`` knows no selection over latent
  attention or no group-limited router cannot run this configuration: its
  ``from_mapping`` ignores the keys it does not know and would build
  DeepSeek-V3's block, every causal key attended and the router without its
  group limit, under DeepSeek-V3.2's name. The adapter looks for the fields
  (``indexer_rope_dim``, ``router_groups``) and ends the run at once, with a
  non-zero exit code, where one is missing; and, as ``prefill_latent``,
  where the file's ``n_routed_experts`` is not the count of ``experts_held``.

The holder's share is the reference's too
(``reference/deepseek_v32_decoder.py`` is given the 8 held experts'
weights, the shared expert and the vocabulary slice the program has)."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.programs import prefill_batched, prefill_latent

STEP_NAME = "dsv32_step"  # the compiled program is jit_dsv32_step in a trace
TOSSED_ROWS_SHARE = 0.3  # of a decided part's rows, over the rows' limit; 0.7 in prefill_batched
MECHANISM = ("indexer_rope_dim", "router_groups")  # DecoderConfig fields this configuration needs


class Program(prefill_latent.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        missing = [name for name in MECHANISM if name not in have]
        if missing:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no key selection over latent attention "
                f"or no group-limited router (DecoderConfig lacks {', '.join(missing)}): it "
                "cannot run " + str(cfg.get("name")))
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def dsv32_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        dsv32_step.__name__ = STEP_NAME
        self._step = jax.jit(dsv32_step)  # in place of the parents', which never ran

    def check(self, frames: np.ndarray) -> dict:
        verdict = prefill_batched.Program.check(self, frames)  # not prefill_latent's share on top
        decided = [name for name, v in verdict.items()
                   if isinstance(v, dict) and "rows_over_limit" in v
                   and name.split(".")[0] not in prefill_batched.RECORD_ONLY]
        for name in decided:
            v = verdict[name]
            v["rows_over_share_limit"] = TOSSED_ROWS_SHARE
            v["ok"] = bool(v["ok"] and v["rows_over_limit"] <= TOSSED_ROWS_SHARE)
        verdict["ok"] = bool(verdict["ok"] and all(verdict[name]["ok"] for name in decided))
        return verdict
