"""Program adapter ``prefill_looped``: a LOOPED decoder (one stack of layers
run several times over with the SAME weights, the final norm and an exit
gate after every pass: Ouro's) as a frame reader through ``InfeedPipeline``,
WHOLE on one chip.

``prefill_batched``'s program and check (queue -> ``batches_from_queue`` ->
``DevicePrefetcher`` -> one compiled ``decoder.frame_step`` -> a counting
sink; ``patch_rows``, ``prompt_rows`` and ``isolated`` for the sequences the
configuration names, ``head``, ``served``, each by that module's limits and
for its reasons), on the rows after the LAST pass, with these differences,
none of them a loop or an option:

- a package whose ``DecoderConfig`` knows no passes, no sandwich and no exit
  gate cannot run this configuration (it would run the stack once, norm
  each branch once and call that the model). The adapter looks for the
  fields FIRST and ends the run at once, with a non-zero exit code, where
  one is missing;
- the step runs under this adapter's name (``jit_ouro_step`` in a trace);
- the weights are drawn a layer at a time: ONE jitted function, called 48
  times, each layer under its own key (``fold_in(key, i)``), and the parents'
  one program draws the rest of the tree;
- the reference goes through its own passes (``ref.passes``: the loops
  written out), a layer's program at a time, and its head is untied and
  norms nothing (the last pass's rows come normed);
- ``head``: the untied head reads rows that are normed and rounded already, so
  rounding its operands to bf16 moves nothing and the parents' yardstick is 0:
  the limit is stated (``HEAD_LIMIT``), for its reason;
- ``exits.i`` decides too: the exit distribution ``p_1..p_R`` at the checked
  rows of sequence ``i``, from the second program over the same package
  functions (``frame_hidden(exits=True)``), against the reference's, by at
  most ``EXITS_FACTOR`` yardsticks (:func:`exits_verdict`). ``p_r`` for ``r < R`` is made of EARLIER passes' normed rows,
  which the last pass's rows alone do not show: a gate that read a pass's
  rows before their norm leaves the hidden rows as they are and moves ``p``;
- this model has no router and no selection, so NO row is tossed: the share
  of a part's rows that may lie over the rows' limit is this cell's own,
  ``TOSSED_ROWS_SHARE``, laid over ``prefill_batched``'s 0.7, and
  ``first_rows.i`` DECIDES (as in ``prefill_ssm``, for its reason). The two
  readings (the program's largest over its seeds, the controls' least) are
  in PERF.md section 4.

The program's rows and the reference's are each computed ONCE a batch (a
sequence and a precision) and kept: the rows' parts and the exits read the
same run. ``isolated`` is live here: the cell serves two frames a step."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark import harness
from benchmark.programs import prefill_batched

STEP_NAME = "ouro_step"  # the compiled program is jit_ouro_step in a trace
TOSSED_ROWS_SHARE = 0.1  # of a decided part's rows, over the rows' limit; 0.7 in prefill_batched
# the head's limit. Its operands are the last pass's rows and the head as the program holds them,
# bf16 both, so rounding them to bf16 moves nothing and prefill_batched's yardstick reads 0: what
# is left is the float32 accumulation of 2,048 exact products against the reference's (1e-7 to
# 1e-6 of the logits' RMS); an accumulation in bf16 reads 1e-3 and more
HEAD_LIMIT = 1e-5
# the exits' limit, in yardsticks (the reference's p with bf16 operands against float32, some 0.4-1.3%
# of p's RMS). p_r multiplies up to four sigmoids of rows that each carry the trunk's own error (the
# rows lie at 1.5-2.3 yardsticks of THEIRS: the bf16 stream), so the program's reading has a tail the
# rows' has not: 1.06-3.59 over 27 readings of 16 seeds; the controls' least is 19.3 (a pass with
# weights of its own)
EXITS_FACTOR = 8.0
DECIDES = ("first_rows", "patch_rows", "prompt_rows", "isolated", "exits", "head", "served")
MECHANISM = ("passes", "sandwich", "exit_gate")  # DecoderConfig fields this configuration needs


def exits_verdict(got, want_f32, want_stated) -> dict:
    """The exit distribution ``[R, rows]`` against the reference's: its relative
    RMS error at most ``EXITS_FACTOR`` yardsticks."""
    err, yard = (harness.relative_rms(p, want_f32) for p in (got, want_stated))
    return {"relative_rms": err, "yardstick_relative_rms": yard, "yardsticks": err / max(yard, 1e-30),
            "limit": EXITS_FACTOR * yard,
            "ok": bool(err <= EXITS_FACTOR * yard and np.isfinite(np.asarray(got)).all())}


class Program(prefill_batched.Program):
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax

        from psana_ray_tpu.models import decoder

        have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
        missing = [name for name in MECHANISM if name not in have]
        if missing:
            raise SystemExit(
                "[bench] ERROR: this psana_ray_tpu runs a stack of layers once (DecoderConfig "
                f"lacks {', '.join(missing)}): it cannot run " + str(cfg.get("name")))
        # The parents draw the whole tree in ONE program, which for 48 layers compiles for 43 s
        # (granite's 40 for 94: PERF.md section 7 (al)). They are handed the model WITHOUT its
        # layers (the tables, the final gain, the head, the gate), and a layer is drawn by one
        # jitted function, called once a layer under that layer's own key
        super().__init__({**cfg, "num_hidden_layers": 0, "layer_types": []}, seed, work_dir, devices)
        self.cfg, self.dcfg = cfg, decoder.DecoderConfig.from_mapping(cfg)
        one = dataclasses.replace(self.dcfg, num_layers=1, layer_types=self.dcfg.layer_types[:1])
        draw = jax.jit(lambda k: decoder.init_params(one, k)["layers"][0])
        key = harness.make_key(seed)
        self.params["layers"] = jax.block_until_ready(
            [draw(jax.random.fold_in(key, i)) for i in range(self.dcfg.num_layers)])
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def ouro_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        ouro_step.__name__ = STEP_NAME
        self._step = jax.jit(ouro_step)  # in place of the parents', which never ran
        self._kept = {}  # (which, the frames' bytes, ...) -> what that run computed

    def _program(self, batch: np.ndarray):
        """``(x [B*S, d], logits [B, V], p [R, B*S])`` for the raw frames
        ``batch``: the last pass's normed rows at every token, the head on each
        frame's last, and the exit distribution at every token, as
        ``frame_step`` computes them, in a program of its own; once a batch."""
        import jax

        from psana_ray_tpu.models import decoder

        key = ("program", hash(batch.tobytes()))
        if key not in self._kept:
            dcfg, threshold = self.dcfg, float(self.cfg["calib_threshold"])

            def hidden(params, calib, frames, prompt_ids):
                x, _, p = decoder.frame_hidden(params, calib, frames, prompt_ids, cfg=dcfg,
                                               threshold=threshold, exits=True)
                s = x.shape[0] // frames.shape[0]
                return x, decoder.logits_of(params, x[s - 1::s], dcfg), p

            self._kept[key] = jax.jit(hidden)(self.params, self.calib_d, jax.device_put(batch),
                                              self.prompt_ids)
        return self._kept[key]

    def hidden(self, batch: np.ndarray):
        return self._program(batch)[:2]

    def _reference(self, frame: np.ndarray, compute, **fault):
        """The reference's ``(h_R [S, d], p [R, S])`` float32 for ONE raw
        frame ``[1, P, H, W]``, the operands of every product rounded to
        ``compute``; ``fault`` as ``ref.sizes`` takes it. A layer's bf16
        weights are widened inside its own program; once a frame, precision
        and fault."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import calib as ref_calib

        key = ("reference", hash(frame.tobytes()), jnp.dtype(compute).name, tuple(sorted(fault.items())))
        if key not in self._kept:
            ref, m = self.ref, self.ref.sizes(self.cfg, **fault)
            patch, block = int(self.cfg["patch"]), int(self.cfg["reference"]["query_block"])
            threshold = float(self.cfg["calib_threshold"])
            one_layer = jax.jit(lambda p, x: ref.layer(p, x, m, compute, block))
            end = jax.jit(lambda q, x, last: ref.pass_end(q, x, m, compute, last), static_argnums=2)
            with jax.default_matmul_precision("highest"):
                x = jax.jit(lambda p, c, f: ref.embed(
                    p, ref.patches_of(ref_calib.calibrate(f, *c, threshold=threshold)[0], patch),
                    self.prompt_ids, compute))(
                    {k: self.params[k] for k in ("patch", "embed")}, self.calib_d, jnp.asarray(frame))
                self._kept[key] = ref.passes(self.params, x, m, compute, block, one_layer, end)
        return self._kept[key]

    def reference_hidden(self, frame: np.ndarray, compute, **fault):
        return self._reference(frame, compute, **fault)[0]

    def reference_logits(self, rows, compute) -> np.ndarray:
        """The reference's untied head on hidden ``rows [N, d]``, which are normed already."""
        import jax
        import jax.numpy as jnp

        m = self.ref.sizes(self.cfg)
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda p, x: self.ref.logits_of(p, x, m, compute))(
                {"head": self.params["head"]}, jnp.asarray(rows, jnp.float32)))

    def check(self, frames: np.ndarray) -> dict:
        import jax.numpy as jnp

        verdict = super().check(frames)
        head = verdict["head"]  # no norm before this head: the yardstick reads 0, the limit is stated
        head["limit"] = HEAD_LIMIT
        head["ok"] = bool(head["logits_relative_rms"] <= HEAD_LIMIT)
        decided = [name for name, v in verdict.items()  # every part of rows, first_rows too
                   if isinstance(v, dict) and "rows_over_limit" in v]
        for name in decided:
            v = verdict[name]
            v["rows_over_share_limit"] = TOSSED_ROWS_SHARE
            v["ok"] = bool(v["ok"] and v["rows_over_limit"] <= TOSSED_ROWS_SHARE)
        batch = harness.fill_batch(frames, self.frames_per_batch)
        s = int(self.cfg["sequence_tokens"])
        at = np.concatenate(list(prefill_batched.first_and_spread(self.cfg).values()))
        p = np.asarray(self._program(batch)[2])
        for i in (int(i) % len(batch) for i in self.cfg["reference"]["sequences"]):
            want, stated = (np.asarray(self._reference(batch[i:i + 1], c)[1])[:, at]
                            for c in (jnp.float32, jnp.bfloat16))
            v = exits_verdict(p[:, i * s + at], want, stated)
            v["exit_pass_mean"] = float(np.mean(np.arange(1, len(want) + 1) @ want))
            verdict[f"exits.{i}"] = v
        verdict["ok"] = bool(all(v["ok"] for name, v in verdict.items() if isinstance(v, dict)
                                 and name.split(".")[0] in DECIDES))
        self._kept.clear()
        return verdict
