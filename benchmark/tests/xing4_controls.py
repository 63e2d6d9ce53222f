#!/usr/bin/env python3
"""Can ``xing4_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/xing4_controls.py --seeds 5,3000000006
    python3 benchmark/tests/xing4_controls.py --seeds 7 --only one_iteration,alpha_0

For each seed, at the cell's own size (batch 2) and on the batch's LAST
sequence, the check's comparisons (``programs/prefill_batched.py``, as
``programs/prefill_hyper.py`` runs them) with the reference in the program's
place, a fault put into it (``reference/xing4_decoder.sizes``):

- ``float8``: float8-rounded operands (the nearest precision below the stated
  one: every product's, ``x~ phi`` among them) as the rows, and the
  reference's head with them as the logits (``float8_head``);
- the hyper-connections' own: ``plain_residual_a_stream`` (``H_res = I``),
  ``exp_alone`` (no Sinkhorn), ``one_iteration`` (for twenty),
  ``columns_never_normed``, ``post_without_its_2``, ``pre_without_its_sigmoid``,
  ``no_wide_norm``, ``alpha_0`` (every H its bias), ``branch_fed_stream_0`` (for
  the mix), ``exit_takes_stream_0`` (for the sum),
  ``attention_s_numbers_for_the_feed_forward``; and two that NO chip limit can
  catch under these weights, RECORDED as such and held by
  ``tests/test_decoder_xing4.py`` under loud ones: ``nineteen_iterations``
  (``-k twentieth``) and ``no_clamp`` (``-k clamp``: no logit of ``h_res``
  comes near 30);
- DeepSeek-V3's block's, as kimi_k2's controls have them: ``unturned_key``,
  ``no_mscale``, ``softmax_router``, ``no_selection_bias``;
- ``stream_float32`` (``--only`` names it: not a fault): the PROGRAM with the
  stream between the layers in float32, for the reading that chose bf16.

Each has to come out as not correct by one of the rows' limits (the level at 4
yardsticks; rows over the limit at ``prefill_hyper.TOSSED_ROWS_SHARE``) or, for
the float8 head, by the head's; a fault that no limit can catch is RECORDED
(``caught`` false), not dropped. The program's own reading is printed beside
them, with the step's ``hyper_sum_defect_max``. Lines go to
``chiprun_out/xing4_controls.jsonl``. A tool for a builder, not a proof:
nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {"plain_residual_a_stream": {"res": "identity"}, "exp_alone": {"iters": 0},
          "one_iteration": {"iters": 1}, "nineteen_iterations": {"iters": 19},
          "columns_never_normed": {"sinkhorn": "rows"}, "post_without_its_2": {"post_two": False},
          "pre_without_its_sigmoid": {"pre_sigmoid": False}, "no_wide_norm": {"wide_norm": False},
          "alpha_0": {"alpha_scale": 0.0}, "branch_fed_stream_0": {"reads": "stream0"},
          "exit_takes_stream_0": {"exit": "stream0"},
          "attention_s_numbers_for_the_feed_forward": {"ff_mix": "attention"}, "no_clamp": {"clamp": False},
          "unturned_key": {"turn_key": False}, "no_mscale": {"mscale": False},
          "softmax_router": {"scoring": "softmax"}, "no_selection_bias": {"select_bias": False}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", default="", help="comma-separated faults (default: all but stream_float32)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.programs import prefill_batched, prefill_hyper
    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()  # every fault's layer compiles once a checkout, not once a seed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs", "xing4_29b_a4b_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    only = args.only.split(",") if args.only else None
    faults = {k: v for k, v in FAULTS.items() if only is None or k in only}
    out_path = os.path.join(ROOT, "chiprun_out", "xing4_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    s, n = int(cfg["sequence_tokens"]), int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    last = (n - 1) * s + at
    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_hyper.Program(cfg, seed, "", None)
        batch = harness.fill_batch(harness.make_check_frames(cfg["detector"], n, seed), n)
        frame = batch[n - 1:]
        want, stated = (np.asarray(program.reference_hidden(frame, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        rows = {"program": np.asarray(program.hidden(batch)[0][last], np.float32)}
        line = {"seed": seed, "hyper_sum_defect_max": float(
            program._serve(jax.device_put(batch))[1][len(decoder.STEP_STATS) + 15])}
        if only is None or "float8" in only:
            rows["float8"] = np.asarray(program.reference_hidden(frame, jnp.float8_e4m3fn)[at])
        for name, fault in faults.items():
            rows[name] = np.asarray(program.reference_hidden(frame, jnp.float32, **fault)[at])
            print(f"[controls] seed {seed}: {name} read", file=sys.stderr, flush=True)
        if only and "stream_float32" in only:  # the program with a float32 stream, for the record
            kept = decoder.DecoderConfig.stream_dtype
            decoder.DecoderConfig.stream_dtype = property(lambda self: jnp.float32)
            jax.clear_caches()
            try:
                rows["stream_float32"] = np.asarray(program.hidden(batch)[0][last], np.float32)
            except Exception as e:  # noqa: BLE001 — (a stream twice as large may not fit the chip)
                line["stream_float32"] = {"error": repr(e)[:300]}
            finally:
                decoder.DecoderConfig.stream_dtype = kept
                jax.clear_caches()
        for name, got in rows.items():
            lo, line[name] = 0, {}
            for part, positions in parts.items():
                span = slice(lo, lo + len(positions))
                v = prefill_batched.rows_verdict(got[span], want[span], stated[span])
                line[name][part] = {k: v[k] for k in ("yardsticks", "rows_over_limit", "ok")}
                line[name][part]["ok"] = bool(  # the cell's own share, laid over the parent's
                    v["ok"] and v["rows_over_limit"] <= prefill_hyper.TOSSED_ROWS_SHARE)
                lo += len(positions)
            line[name]["ok"] = all(v["ok"] for part, v in line[name].items()
                                   if part not in prefill_batched.RECORD_ONLY)
        own = rows["program"][-1:]
        head = [program.reference_logits(own, c)
                for c in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn)]
        v = harness.precision_verdict(head[2], head[0], head[1])
        line["float8_head"] = {"head": {
            "yardsticks": v["logits_relative_rms"] / max(v["yardstick_relative_rms"], 1e-30),
            "ok": v["ok"]}, "ok": v["ok"]}
        line["caught"] = {name: not v["ok"] for name, v in line.items()
                          if isinstance(v, dict) and "ok" in v and name not in ("program", "stream_float32")}
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
        del program  # 11.3 GB of weights: the next seed's do not fit beside them
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
