#!/usr/bin/env python3
"""Can ``lfm2_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/lfm2_controls.py --seeds 5,3000000006

For each seed, at the cell's own size and on the batch's LAST sequence,
the check's comparisons (``programs/prefill_batched.py``) with something
else in the program's place:

- ``float8``: the reference with float8-rounded operands (the nearest
  precision below the stated one) as the rows, and its head as the logits;
- ``latest_tap_only``: the reference's convolution with its two earlier
  taps dropped (``c[t] = w[:, 2] * u[t]``: no convolution at all);
- ``softmax_router``: the reference with a softmax router (no bias, no
  epsilon) in place of the sigmoid affinities under a selection bias;
- ``no_selection_bias``: the reference choosing its experts by the
  affinity alone;
- ``leaky_conv``: the PROGRAM with its convolution run over the batch's
  rows as one sequence, so that a sequence's first two tokens read the
  previous sequence's last two (read by the check's ``isolated``: the
  same program with the batch's frames moved one place on).

Each has to come out as not correct by one of the rows' limits; the
program's own reading is printed beside them. Lines go to
``chiprun_out/lfm2_controls.jsonl``. A tool for a builder, not a proof:
nothing reads its output."""

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.programs import prefill_batched
    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    out_path = os.path.join(ROOT, "chiprun_out", "lfm2_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    s, n = int(cfg["sequence_tokens"]), int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    last = (n - 1) * s + at
    faults = {"latest_tap_only": {"taps_used": (int(cfg["conv_L_cache"]) - 1,)},
              "softmax_router": {"scoring": "softmax"},
              "no_selection_bias": {"select_bias": False}}

    conv = decoder.gated_short_conv

    def leaky(p, x, batch, dcfg):  # the batch's rows as ONE sequence
        return conv(p, x, 1, dcfg)

    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_batched.Program(cfg, seed, "", None)
        batch = harness.fill_batch(harness.make_check_frames(cfg["detector"], n, seed), n)
        frame = batch[n - 1:]
        want, stated = (np.asarray(program.reference_hidden(frame, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        moved_to = parts["first_rows"]  # the last sequence, moved to the front
        rows = {"program": np.asarray(program.hidden(batch)[0][last], np.float32),
                "float8": np.asarray(program.reference_hidden(frame, jnp.float8_e4m3fn)[at])}
        for name, fault in faults.items():
            rows[name] = np.asarray(program.reference_hidden(frame, jnp.float32, **fault)[at])
        moved = {"program": np.asarray(program.hidden(np.roll(batch, 1, axis=0))[0][moved_to],
                                       np.float32)}
        decoder.gated_short_conv = leaky
        try:
            rows["leaky_conv"] = np.asarray(program.hidden(batch)[0][last], np.float32)
            moved["leaky_conv"] = np.asarray(
                program.hidden(np.roll(batch, 1, axis=0))[0][moved_to], np.float32)
        finally:
            decoder.gated_short_conv = conv
        line = {"seed": seed}
        for name, got in rows.items():
            lo, line[name] = 0, {}
            for part, positions in parts.items():
                span = slice(lo, lo + len(positions))
                v = prefill_batched.rows_verdict(got[span], want[span], stated[span])
                line[name][part] = {k: v[k] for k in ("yardsticks", "rows_over_limit", "ok")}
                line[name][part]["median_yardsticks"] = v["rows_relative_rms_median"] * 4 / v["row_limit"]
                lo += len(positions)
            if name in moved:  # the check's `isolated`: the same program, the sequence moved
                first = slice(0, len(moved_to))
                apart = harness.relative_rms(moved[name], got[first])
                limit = prefill_batched.rows_verdict(
                    got[first], want[first], stated[first])["yardstick_relative_rms_level"]
                line[name]["isolated"] = {"relative_rms_to_itself_moved": apart, "limit": limit,
                                          "ok": bool(apart <= limit)}
            line[name]["ok"] = all(v["ok"] for part, v in line[name].items()
                                   if part not in prefill_batched.RECORD_ONLY)
        head = [program.reference_logits(rows["program"][-1:], c)
                for c in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn)]
        v = harness.precision_verdict(head[2], head[0], head[1])
        line["float8"]["head"] = {
            "yardsticks": v["logits_relative_rms"] / max(v["yardstick_relative_rms"], 1e-30),
            "ok": v["ok"]}
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
