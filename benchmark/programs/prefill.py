"""Program adapter ``prefill``: a decoder as a frame reader through
``InfeedPipeline``.

queue -> ``batches_from_queue`` (batch ONE: a frame is a sequence) ->
``DevicePrefetcher`` -> one compiled step
(``psana_ray_tpu.models.decoder.frame_step``: fused calibration, patches,
a linear patch embedding, the prompt, the decoder's layers, the logits of
the next token) -> a counting sink that takes the logits to the host. As
``classify`` drives the ResNet: no loop and no option of its own. Weights
(bf16, made on the device from the seed), calibration constants and the
prompt are arguments of the step, so the compiled program does not depend
on the seed. The step's statistics vector goes to the pipeline's counters
through the package's own ``fold_step_stats``.

``check`` holds the check frame's result to the plain reference
(``reference/keye_decoder.py``), which goes a layer at a time (one layer's
float32 weights are 2.5 GB) and in blocks of queries, and reads the
configuration, cuts the patches and places the tokens by its own code.
What the served step keeps to itself, the trunk's output at every token,
comes from a second program over the same package functions
(:meth:`Program.hidden`: ``frame_hidden``, then ``logits_of`` as
``frame_step`` applies it). Four comparisons decide, each by
``harness.precision_verdict``'s rule (at most ``PRECISION_FACTOR``
yardsticks, the yardstick being the reference with bfloat16 operands):

- ``patch_rows`` and ``prompt_rows``: the trunk's output at ``ROWS``
  positions spread over the patches, and over the prompt (its last token
  among them), by :func:`rows_verdict`.
- ``head``: that program's logits against the reference's final norm and
  head applied to that program's own last hidden row. No decision lies
  between the two: this reads the precision of the norm and of the
  151,936-wide head alone.
- ``served``: the logits THE SERVED STEP returned against that program's.
  The same code compiled twice may differ by the stated precision's
  rounding, so by no more than 4 times what rounding the operands to
  bfloat16 moves the reference's own logits.

The served logits against the reference's own, one token through every
decision of four layers, are printed beside them
(``last_token_logits_yardsticks``) and decide nothing: see
:func:`rows_verdict`."""

from __future__ import annotations

import numpy as np

from benchmark import harness

STEP_NAME = "keye_step"  # the compiled program is jit_keye_step in a trace
ROWS = 64  # positions compared in each part of the sequence
# the share of a part's rows that may lie over the limit; its two readings
# (the program's largest over its seeds, the controls') are in PERF.md §4
TOSSED_ROWS_SHARE = 0.25


def rows_verdict(got, want_f32, want_stated) -> dict:
    """``harness.precision_verdict``'s rule (the reason for the 4 is
    written there) on rows that are positions of the sequence: the MEDIAN
    of the rows' relative RMS errors is at most 4 yardsticks, the
    yardstick being the same median for the reference with bfloat16
    operands, and at most ``TOSSED_ROWS_SHARE`` of the rows lie over that
    limit.

    Why not one token's error: top-8 of 128 and top-2048 are decisions.
    Where a token's eighth and ninth expert lie closer than the rounding
    noise, the decision goes either way, in the program and in the
    yardstick independently, and that token's row moves by many times the
    rounding error. One token's reading is then a toss of a coin at any
    precision (tests/test_decoder.py shows it at its size: float8 operands
    PASS where the yardstick was tossed). Over many rows the tossed ones
    are a minority: the median moves with the precision alone, and the
    share over the limit bounds how many a fault may break."""
    err, yard = _row_errors(got, want_f32), _row_errors(want_stated, want_f32)
    limit = harness.PRECISION_FACTOR * float(np.median(yard))
    median, over = float(np.median(err)), float(np.mean(err > limit))
    return {
        "rows_relative_rms_median": median, "yardstick_relative_rms_median": float(np.median(yard)),
        "yardsticks": median / max(float(np.median(yard)), 1e-30), "limit": limit,
        "rows_over_limit": over, "yardstick_rows_over_limit": float(np.mean(yard > limit)),
        "rows": int(len(err)),
        "ok": bool(median <= limit and over <= TOSSED_ROWS_SHARE
                   and np.isfinite(np.asarray(got)).all()),
    }


def _row_errors(got, want) -> np.ndarray:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2, axis=1)) / np.maximum(
        np.sqrt(np.mean(want ** 2, axis=1)), 1e-30)


def check_parts(cfg: dict) -> dict:
    """The positions the check compares, by part of the sequence: ``ROWS``
    of the patches and ``ROWS`` of the prompt, spread evenly, each part's
    last position among them (so the last of all is the served token's)."""
    def spread(first, count):
        return first + np.unique(
            np.linspace(0, count - 1, min(ROWS, count)).round().astype(np.int64))

    n_prompt = int(cfg["prompt_tokens"])
    n_patches = int(cfg["sequence_tokens"]) - n_prompt
    return {"patch_rows": spread(0, n_patches), "prompt_rows": spread(n_patches, n_prompt)}


class Program:
    def __init__(self, cfg: dict, seed: int, work_dir: str, devices):
        import jax
        import jax.numpy as jnp

        from psana_ray_tpu.models import decoder

        self.cfg = cfg
        self.dcfg = decoder.DecoderConfig.from_mapping(cfg)
        key = harness.make_key(seed)
        self.params = jax.block_until_ready(
            jax.jit(lambda k: decoder.init_params(self.dcfg, k, jnp.bfloat16))(key))
        ids = np.random.default_rng([int(seed), 0x1D5]).integers(
            0, self.dcfg.vocab_size, int(cfg["prompt_tokens"]))
        self.prompt_ids = jax.device_put(ids.astype(np.int32))
        self.calib = harness.make_calibration(cfg["detector"], seed)
        self.calib_d = jax.device_put(tuple(self.calib))
        dcfg, threshold = self.dcfg, float(cfg["calib_threshold"])

        def keye_step(params, calib, frames, prompt_ids):
            return decoder.frame_step(params, calib, frames, prompt_ids, cfg=dcfg,
                                      threshold=threshold)

        keye_step.__name__ = STEP_NAME
        self._step = jax.jit(keye_step)
        self._fold = decoder.fold_step_stats
        self.sink = harness.CountingSink()
        self.frames_per_batch = int(cfg["batch_size"])
        self.warm_rows = 0
        self._metrics = None

    @property
    def metrics(self):
        return self._metrics

    def _serve(self, frames):
        return self._step(self.params, self.calib_d, frames, self.prompt_ids)

    def warm(self, frames: np.ndarray):
        import jax

        batch = harness.fill_batch(frames, self.frames_per_batch)
        for _ in range(2):
            jax.block_until_ready(self._serve(jax.device_put(batch)))

    def run(self, queue) -> int:
        from psana_ray_tpu.infeed import InfeedPipeline

        # only what the configuration states is passed: every other
        # parameter keeps the program's default, whatever a later PR makes it
        options = {k: int(self.cfg[k]) for k in ("prefetch_depth", "batcher_buffers")
                   if k in self.cfg}
        pipe = InfeedPipeline(queue, batch_size=self.frames_per_batch, **options)
        self._metrics = pipe.metrics

        def on_result(out, batch):
            logits, stats = out
            self.sink(logits, batch)
            self._fold(pipe.metrics, stats)

        return pipe.run(lambda batch: self._serve(batch.frames), on_result=on_result,
                        block_until_ready=True)

    def hidden(self, batch: np.ndarray, dcfg=None):
        """``(x [S, d], logits [1, V])`` for the raw frame ``batch [1, P,
        H, W]``: the trunk's output at every token, and the head on its
        last, as ``frame_step`` computes them, in a program of its own."""
        import jax

        from psana_ray_tpu.models import decoder

        dcfg, threshold = dcfg or self.dcfg, float(self.cfg["calib_threshold"])

        def hidden(params, calib, frames, prompt_ids):
            x, _ = decoder.frame_hidden(params, calib, frames, prompt_ids, cfg=dcfg,
                                        threshold=threshold)
            return x, decoder.logits_of(params, x[-1:], dcfg)

        return jax.jit(hidden)(self.params, self.calib_d, jax.device_put(batch), self.prompt_ids)

    def reference_hidden(self, batch: np.ndarray, compute):
        """The reference trunk's output at every token ``[S, d]`` float32,
        with the operands of every product rounded to ``compute``."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import calib as ref_calib
        from benchmark.reference import keye_decoder as ref

        m = ref.sizes(self.cfg)
        patch = int(self.cfg["patch"])
        _, panels, height, width = batch.shape
        pos = ref.positions(panels, height // patch, width // patch, len(self.prompt_ids))
        block = int(self.cfg["reference"]["query_block"])

        @jax.jit
        def patches_of(calib, f):
            x = ref_calib.calibrate(f, *calib, threshold=float(self.cfg["calib_threshold"]))
            x = x[0].reshape(panels, height // patch, patch, width // patch, patch)
            return jnp.transpose(x, (0, 1, 3, 2, 4)).reshape(-1, patch * patch)

        one_layer = jax.jit(lambda p, x: ref.layer(p, x, pos, m, compute, block, False)[0])
        with jax.default_matmul_precision("highest"):
            x = jax.jit(lambda p, f: ref.embed(p, f, self.prompt_ids, compute))(
                {k: self.params[k] for k in ("patch", "embed")},
                patches_of(self.calib_d, jnp.asarray(batch)))
            for p in self.params["layers"]:
                x = one_layer(p, x)
        return x

    def reference_logits(self, rows, compute) -> np.ndarray:
        """The reference's final norm and head on hidden ``rows [N, d]``."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import keye_decoder as ref

        m = ref.sizes(self.cfg)
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda p, x: ref.logits_of(p, x, m, compute))(
                {k: self.params[k] for k in ("norm", "head")}, jnp.asarray(rows, jnp.float32)))

    def check(self, frames: np.ndarray) -> dict:
        import time

        import jax
        import jax.numpy as jnp

        batch = harness.fill_batch(frames, self.frames_per_batch)
        logits = np.asarray(self._serve(jax.device_put(batch))[0])
        t0 = time.monotonic()
        parts = check_parts(self.cfg)
        at = np.concatenate(list(parts.values()))  # its last is the last token
        x, own_logits = self.hidden(batch)
        got, own_logits = np.asarray(x[at], np.float32), np.asarray(own_logits)
        want, stated = (np.asarray(self.reference_hidden(batch, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        verdict, lo = {}, 0
        for name, positions in parts.items():
            rows = slice(lo, lo + len(positions))
            verdict[name] = rows_verdict(got[rows], want[rows], stated[rows])
            lo += len(positions)
        verdict["head"] = harness.precision_verdict(
            own_logits, *(self.reference_logits(got[-1:], c) for c in (jnp.float32, jnp.bfloat16)))
        want_logits = self.reference_logits(want[-1:], jnp.float32)
        yard = harness.relative_rms(self.reference_logits(stated[-1:], jnp.bfloat16), want_logits)
        apart = harness.relative_rms(logits, own_logits)
        verdict["served"] = {"relative_rms_to_own_program": apart,
                             "limit": harness.PRECISION_FACTOR * yard,
                             "ok": bool(apart <= harness.PRECISION_FACTOR * yard)}
        # through every decision of four layers, for the record: decides nothing
        verdict["last_token_logits_yardsticks"] = (
            harness.relative_rms(logits, want_logits) / max(yard, 1e-30))
        verdict["ok"] = bool(all(verdict[k]["ok"] for k in (*parts, "head", "served"))
                             and np.isfinite(logits).all())
        verdict["reference_seconds"] = time.monotonic() - t0
        return verdict
