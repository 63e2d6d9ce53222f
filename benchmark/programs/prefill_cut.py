"""Program adapter ``prefill_cut``: a decoder-hybrid-decoder (Mamba-1
selective scans 1:1 with differential attention under a window, ONE full
layer whose keys and values the later cross-attention layers read, gated
memory units that read the last scan's output; a dense MLP in every layer) as
a frame reader through ``InfeedPipeline``, WHOLE on one chip, whose
cross-decoder runs on the SERVED rows alone.

``prefill_batched``'s program and check, to the letter (queue ->
``batches_from_queue`` -> ``DevicePrefetcher`` -> one compiled
``decoder.frame_step`` -> a counting sink; ``patch_rows``, ``prompt_rows``
and ``isolated`` for the sequences the configuration names, ``head`` on the
tied table, ``served``, each by that module's limits and for its reasons),
with these differences, none of them a loop or an option:

- the step runs under this adapter's name (``jit_phi4flash_step`` in a trace);
- THE TIMED STEP AND THE CHECK'S SECOND PROGRAM ARE TWO ROW COUNTS OF THE SAME
  LAYERS: ``frame_step`` runs the layers after the kept keys and values on
  each frame's last row alone (``decoder.trunk``'s ``rows``), :meth:`hidden`
  (``frame_hidden``) runs every layer on every row, as the reference does.
  ``served`` (the timed step's logits against the second program's, for ALL
  the batch's sequences) is therefore what holds the cut: keys and values cut
  to the served rows too, the other frame's row, the memory at another row
  each read there (``tests/phi4flash_controls.py``), and ``patch_rows``,
  ``prompt_rows`` and ``first_rows`` hold the all-rows program to the
  reference;
- the reference hands the kept keys and values and the kept scan output from
  layer to layer as VALUES (``ref.layer`` returns what a layer made,
  ``ref.reads`` says whose a layer reads): :meth:`Program.reference_hidden`;
  its final norm is a LayerNorm with a bias: :meth:`Program.reference_logits`;
- the share of a part's rows that may lie over the rows' limit is this cell's
  own, ``TOSSED_ROWS_SHARE``, laid over ``prefill_batched``'s 0.7: this model
  has no router and no selection, so NO row is tossed. Its two readings (the
  program's largest over its seeds, the controls' least) are in PERF.md
  section 4;
- ``first_rows.i`` DECIDES here, by the same two limits (``prefill_ssm``'s
  reason: no router, so a sequence's first rows read none over on any seed);
- a package whose ``DecoderConfig`` knows no such layers cannot run this
  configuration. The adapter looks for the fields first and ends the run at
  once, with a non-zero exit code, where one is missing."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.programs import prefill_batched

STEP_NAME = "phi4flash_step"  # the compiled program is jit_phi4flash_step in a trace
TOSSED_ROWS_SHARE = 0.1  # of a decided part's rows, over the rows' limit; 0.7 in prefill_batched
# DecoderConfig fields this configuration needs
MECHANISM = ("scan_channels", "scan_dt_rank", "diff_attention", "norm", "attn_bias")


class Program(prefill_batched.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        missing = [name for name in MECHANISM if name not in have]
        if missing:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no Mamba-1 scan, no differential attention "
                f"or no LayerNorm block (DecoderConfig lacks {', '.join(missing)}): it cannot run "
                + str(cfg.get("name")))
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def phi4flash_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        phi4flash_step.__name__ = STEP_NAME
        self._step = jax.jit(phi4flash_step)  # in place of the parents', which never ran

    def reference_hidden(self, frame: np.ndarray, compute, **fault):
        """``prefill_batched``'s, a layer's products handed on to the layers
        that read them. A layer's bf16 weights are widened inside its own
        program: all 32 in float32 would be 15.4 GB beside the program's 7.7."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import calib as ref_calib

        ref, m = self.ref, self.ref.sizes(self.cfg, **fault)
        patch, block = int(self.cfg["patch"]), int(self.cfg["reference"]["query_block"])
        threshold = float(self.cfg["calib_threshold"])
        one_layer = jax.jit(lambda p, x, kind, index, read: ref.layer(
            p, x, kind, m, compute, block, index, read), static_argnums=2)  # one program a kind
        with jax.default_matmul_precision("highest"):
            x = jax.jit(lambda p, c, f: ref.embed(
                p, ref.patches_of(ref_calib.calibrate(f, *c, threshold=threshold)[0], patch),
                self.prompt_ids, compute))(
                {k: self.params[k] for k in ("patch", "embed")}, self.calib_d, jnp.asarray(frame))
            kinds = ref.kinds(m)
            wanted = {ref.reads(m, i) for i in range(len(kinds))}  # the layers a later one reads
            made = {}
            for i, (p, kind) in enumerate(zip(self.params["layers"], kinds)):
                x, out = one_layer(p, x, kind, jnp.float32(i), made.get(ref.reads(m, i)))
                if i in wanted:
                    made[i] = out
        return x

    def reference_logits(self, rows, compute, **fault) -> np.ndarray:
        """The reference's final LayerNorm and tied head on hidden ``rows [N, d]``."""
        import jax
        import jax.numpy as jnp

        m = self.ref.sizes(self.cfg, **fault)
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda p, x: self.ref.logits_of(p, x, m, compute))(
                {k: self.params[k] for k in ("norm", "norm_b", "embed")},
                jnp.asarray(rows, jnp.float32)))

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
