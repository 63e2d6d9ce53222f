"""Program adapter ``prefill_windowed``: a decoder whose layers are
grouped-query attention under a WINDOW (a band of keys) with full
attention among them, each with its own count of query heads and its layer
type's rotary, a sigmoid gate a head on the output, as a frame reader over
batches of frames through ``InfeedPipeline``, on a holder of a SHARE of
each layer.

``prefill_batched``'s program and check, to the letter (queue ->
``batches_from_queue`` -> ``DevicePrefetcher`` -> one compiled
``decoder.frame_step`` -> a counting sink; ``patch_rows``, ``prompt_rows``
and ``isolated`` for the first and the last sequence of the batch,
``head``, ``served``, each by that module's limits and for its reasons),
with three differences, none of them a loop or an option:

- the step runs under this adapter's name (``jit_laguna_step`` in a trace);
- the output head is its own matrix (``tie_word_embeddings`` false), over
  the vocabulary slice this holder has, so the reference's logits read
  ``params["head"]`` (as ``prefill_latent`` does);
- (NOT a difference: the share of a part's rows that may lie over the
  rows' limit is ``prefill_batched``'s own 0.7, with nothing laid over it
  as kimi's, dsv32's and ling3's adapters lay 0.3. Eight expert layers
  choose TEN of 256 by sigmoid affinities that lie close together, and the
  yardstick's own rows lie 3-27% over the limit: the program's parts read
  3-39% over and every control 91-100%, so 0.3 would sit inside the
  program's readings. Both readings are in PERF.md section 4;)
- a package whose ``DecoderConfig`` knows no windowed layer, no head count
  of a layer's own or no rotary by layer type cannot run this
  configuration: its ``from_mapping`` refuses the file's ``layer_types``
  (or, older, finds no ``rope_theta``). The adapter looks for the fields
  (``sliding_window``, ``heads_per_layer``) first and ends the run at
  once, with a non-zero exit code, where one is missing; and, as
  ``prefill_latent``, where the file's ``num_experts`` (what the roofline
  functions and the reference count) is not the count of ``experts_held``
  (what the program holds).

The holder's share is the reference's too (``reference/laguna_decoder.py``
is given the 64 held experts' weights, the shared expert and the
vocabulary slice the program has)."""

from __future__ import annotations

import dataclasses

from benchmark.programs import prefill_batched, prefill_latent

STEP_NAME = "laguna_step"  # the compiled program is jit_laguna_step in a trace
MECHANISM = ("sliding_window", "heads_per_layer")  # DecoderConfig fields this configuration needs


class Program(prefill_batched.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        missing = [name for name in MECHANISM if name not in have]
        if missing:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no windowed attention or no head count "
                f"of a layer's own (DecoderConfig lacks {', '.join(missing)}): it cannot run "
                + str(cfg.get("name")))
        if int(cfg["num_experts"]) != int(cfg["experts_held"][1]):
            # one fact under two keys: the program holds `experts_held`, the roofline functions
            # and the reference count `num_experts`
            raise SystemExit(
                f"[bench] ERROR: {cfg.get('name')}: num_experts {cfg['num_experts']} is not the "
                f"count of experts_held {cfg['experts_held']}")
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def laguna_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        laguna_step.__name__ = STEP_NAME
        self._step = jax.jit(laguna_step)  # in place of the parents', which never ran

    # the reference's final norm and (untied) head on hidden rows, as kimi's adapter reads them
    reference_logits = prefill_latent.Program.reference_logits
