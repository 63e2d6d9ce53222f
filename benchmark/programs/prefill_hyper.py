"""Program adapter ``prefill_hyper``: a latent-attention decoder whose
residual path is SEVERAL STREAMS a token (hyper-connections) as a frame
reader over batches of frames through ``InfeedPipeline``.

``prefill_latent``'s program and check (``prefill_batched``'s, an untied
head, a limit of its own on tossed rows), with four differences, none of them
a loop or an option:

- the step runs under this adapter's name (``jit_xing4_step`` in a trace);
- the reference's stream is ``hc_mult`` rows a token: its embedded rows enter
  as that many equal streams and its layers hand ``[S, n * d]`` on, so
  :meth:`Program.reference_hidden` sums the streams after the last layer
  (``reference/xing4_decoder.narrow``), as the program's trunk does ahead of
  the final norm: what is compared is the ``[S, d]`` row the head reads;
- the share of a part's rows that may lie over the rows' limit is this
  cell's own, ``TOSSED_ROWS_SHARE``, READ on the chip: all 64 experts are
  held, so every tossed choice moves its row (lfm2's regime, not kimi_k2's
  12 of 384): the program's largest reading over nine seeds 37.5%, the
  planted faults' least 98.4% (one Sinkhorn step for twenty; DeepSeek-V3's
  own faults alike). Both readings are in PERF.md section 4;
- a package whose ``DecoderConfig`` has no hyper-connections cannot run this
  configuration at all: its ``from_mapping`` ignores the keys it does not know
  and would build DeepSeek-V3's plain block under Xing4.0's name. The adapter
  looks for the field and ends the run at once, with a non-zero exit code,
  where it is missing."""

from __future__ import annotations

import dataclasses

from benchmark.programs import prefill_batched, prefill_latent

STEP_NAME = "xing4_step"  # the compiled program is jit_xing4_step in a trace
TOSSED_ROWS_SHARE = 0.65  # of a decided part's rows, over the rows' limit; 0.7 in prefill_batched


class Program(prefill_latent.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        if "hc_mult" not in have:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu has no hyper-connections (DecoderConfig lacks "
                "hc_mult): it cannot run " + str(cfg.get("name")))
        super().__init__(cfg, seed, work_dir, devices)
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def xing4_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        xing4_step.__name__ = STEP_NAME
        self._step = jax.jit(xing4_step)  # in place of the parents', which never ran

    def check(self, frames) -> dict:
        verdict = prefill_batched.Program.check(self, frames)  # (past the parent's share, to lay this one)
        decided = [name for name, v in verdict.items()
                   if isinstance(v, dict) and "rows_over_limit" in v
                   and name.split(".")[0] not in prefill_batched.RECORD_ONLY]
        for name in decided:
            v = verdict[name]
            v["rows_over_share_limit"] = TOSSED_ROWS_SHARE
            v["ok"] = bool(v["ok"] and v["rows_over_limit"] <= TOSSED_ROWS_SHARE)
        verdict["ok"] = bool(verdict["ok"] and all(verdict[name]["ok"] for name in decided))
        return verdict

    def reference_hidden(self, frame, compute, **fault):
        """The reference trunk's output at every token of ONE raw frame: the
        streams after the last layer SUMMED, ``[S, d]`` float32."""
        import jax

        m = self.ref.sizes(self.cfg, **fault)
        streams = super().reference_hidden(frame, compute, **fault)
        return jax.jit(lambda x: self.ref.narrow(x, m))(streams)
