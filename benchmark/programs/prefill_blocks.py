"""Program adapter ``prefill_blocks``: a decoder whose every layer is ONE
block, ``x + Mixer(rms(x))`` — a Mamba-2 mixer with several groups of ``B``
and ``C``, position-free grouped-query attention, or UNGATED ``relu^2``
experts beside a shared one, each ALONE in its layer — as a frame reader over
batches of frames through ``InfeedPipeline``, on a holder of a SHARE of each
expert layer and of the vocabulary.

``prefill_batched``'s program and check, to the letter (queue ->
``batches_from_queue`` -> ``DevicePrefetcher`` -> one compiled
``decoder.frame_step`` -> a counting sink; ``patch_rows``, ``prompt_rows``
and ``isolated`` for the first and the last sequence of the batch,
``first_rows`` for the record, ``head``, ``served``, each by that module's
limits and for its reasons), with three differences, none of them a loop or
an option:

- the step runs under this adapter's name (``jit_nemotron3_step`` in a
  trace);
- the output head is its own matrix (``tie_word_embeddings`` false), over
  the vocabulary slice this holder has, so the reference's logits read
  ``params["head"]`` (as ``prefill_latent`` does);
- (NOT a difference: the share of a part's rows that may lie over the
  rows' limit, ``TOSSED_ROWS_SHARE``, is ``prefill_batched``'s own 0.7,
  with nothing laid over it as kimi's, dsv32's and ling3's adapters lay
  0.3. Six expert layers choose 6 of 128 by sigmoid affinities that lie
  close together and this holder has HALF of the experts, so a tossed
  choice touches a held expert more often than on a holder of a quarter
  or a thirty-second: the yardstick's own rows lie 0-30% over the limit,
  the program's parts read 0-45% over (0.3 sat INSIDE its readings on the
  first chip run) and the least fault that the share alone would have to
  catch 91%; every fault of the controls but the planted rotary is caught
  by the rows' level already. A sequence's ``first_rows`` stay for the
  record: they read 1.1-2.8 yardsticks and 0-53% over from seed to seed.
  Both readings are in PERF.md section 4;)
- a package whose ``DecoderConfig`` knows no layer of one block, no groups
  of ``B`` and ``C`` or no ungated MLP cannot run this configuration (its
  ``from_mapping`` would read the file's ``layer_types``, find none and
  build fourteen attention layers with a rotary and gated experts under
  this model's name). The adapter looks for the fields (``MECHANISM``)
  FIRST and ends the run at once, with a non-zero exit code, where one is
  missing; and, as ``prefill_latent``, where the file's ``n_routed_experts``
  (what the roofline functions and the reference count) is not the count of
  ``experts_held`` (what the program holds).

The holder's share is the reference's too (``reference/nemotron3_decoder.py``
is given the 64 held experts' weights, the shared expert and the vocabulary
slice the program has)."""

from __future__ import annotations

import dataclasses

from benchmark.programs import prefill_batched, prefill_latent

STEP_NAME = "nemotron3_step"  # the compiled program is jit_nemotron3_step in a trace
# of a decided part's rows, over the rows' limit: prefill_batched's own, nothing laid over it
TOSSED_ROWS_SHARE = prefill_batched.TOSSED_ROWS_SHARE
# DecoderConfig fields this configuration needs
MECHANISM = ("single_block", "ssm_groups", "mlp_act")


class Program(prefill_batched.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        missing = [name for name in MECHANISM if name not in have]
        if missing:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no layer of one block, no groups of B and C "
                f"or no ungated MLP (DecoderConfig lacks {', '.join(missing)}): it cannot run "
                + str(cfg.get("name")))
        if int(cfg["n_routed_experts"]) != int(cfg["experts_held"][1]):
            # one fact under two keys: the program holds `experts_held`, the roofline functions
            # and the reference count `n_routed_experts`
            raise SystemExit(
                f"[bench] ERROR: {cfg.get('name')}: n_routed_experts {cfg['n_routed_experts']} "
                f"is not the count of experts_held {cfg['experts_held']}")
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def nemotron3_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        nemotron3_step.__name__ = STEP_NAME
        self._step = jax.jit(nemotron3_step)  # in place of the parents', which never ran

    # the reference's final norm and (untied) head on hidden rows, as kimi's adapter reads them
    reference_logits = prefill_latent.Program.reference_logits
