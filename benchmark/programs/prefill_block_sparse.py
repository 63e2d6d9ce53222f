"""Program adapter ``prefill_block_sparse``: a decoder whose attention layers
select BLOCKS of keys a key head by the attention's own queries against
mean-pooled keys (no indexer) and whose other layers are LINEAR attention
with a fixed decay a head (a float32 state a head carried along the sequence,
a rotary inside), a dense MLP in every layer, every branch and both ends
under a multiplier, as a frame reader through ``InfeedPipeline``: the first of
eight pipeline stages on one chip.

``prefill_batched``'s program and check, to the letter (queue ->
``batches_from_queue`` -> ``DevicePrefetcher`` -> one compiled
``decoder.frame_step`` -> a counting sink; ``patch_rows``, ``prompt_rows``
and ``isolated`` for the sequences the configuration names, ``head``,
``served``, each by that module's limits and for its reasons), with five
differences, none of them a loop or an option:

- the step runs under this adapter's name (``jit_minicpm_sala_step`` in a
  trace);
- the reference's embedding and head take the reference's own reading of the
  configuration (``scale_emb``; ``hidden_size / dim_model_base`` under the
  logits), and the head is its own matrix (``tie_word_embeddings`` false):
  :meth:`Program.reference_hidden` and :meth:`Program.reference_logits` hand
  it ``ref.sizes(cfg)`` (``prefill_ssm``'s way) and ``params["head"]``
  (``prefill_latent``'s);
- the share of a part's rows that may lie over the rows' limit is this
  cell's own, ``TOSSED_ROWS_SHARE``, laid over ``prefill_batched``'s 0.7: ONE
  layer selects, among block scores that lie close together under random
  weights, so a choice inside the rounding goes either way in the program
  and in the yardstick, as a router's does, and moves that row. Its two
  readings (the program's largest over its seeds, the controls' least) are in
  PERF.md section 4;
- ``first_rows.i`` DECIDES here, by the same two limits (granite's reason: a
  sequence's first 32 rows lie inside the first block, which every query
  keeps, so no row of them is tossed, and they are the part that sees the
  sparse layer's POSITIONS: a rotary planted there moves a sequence's first
  rows, few keys each);
- a package whose ``DecoderConfig`` knows no selection of blocks and no
  fixed-decay linear layer cannot run this configuration. The adapter looks
  for the fields (``MECHANISM``) FIRST and ends the run at once, with a
  non-zero exit code, where one is missing.

At ``batch_size`` 1 (the cell's: a selection is one sequence's) ``isolated.0``
is vacuous: the frame moved one place on is the same frame."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.programs import prefill_batched

STEP_NAME = "minicpm_sala_step"  # the compiled program is jit_minicpm_sala_step in a trace
TOSSED_ROWS_SHARE = 0.3  # of a decided part's rows, over the rows' limit; 0.7 in prefill_batched
# DecoderConfig fields this configuration needs
MECHANISM = ("block_select", "linear_rotary", "residual_multiplier", "embedding_multiplier",
             "logits_scaling")


class Program(prefill_batched.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        missing = [name for name in MECHANISM if name not in have]
        if missing:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no selection of blocks a key head or no "
                f"linear attention with a fixed decay (DecoderConfig lacks {', '.join(missing)}): "
                "it cannot run " + str(cfg.get("name")))
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def minicpm_sala_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        minicpm_sala_step.__name__ = STEP_NAME
        self._step = jax.jit(minicpm_sala_step)  # in place of the parents', which never ran

    def reference_hidden(self, frame: np.ndarray, compute, **fault):
        """``prefill_batched``'s, the reference's embedding given its own
        reading of the configuration (the multiplier on the embedded rows)."""
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
        """The reference's final norm and (untied) head on hidden ``rows [N,
        d]``, over its own ``hidden_size / dim_model_base``."""
        import jax
        import jax.numpy as jnp

        m = self.ref.sizes(self.cfg, **fault)
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda p, x: self.ref.logits_of(p, x, m, compute))(
                {k: self.params[k] for k in ("norm", "head")}, jnp.asarray(rows, jnp.float32)))

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
