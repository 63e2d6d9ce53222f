#!/usr/bin/env python3
"""Can ``laguna_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/laguna_controls.py --seeds 5,3000000006

For each seed, at the cell's own size and on the batch's LAST sequence,
the check's comparisons (``programs/prefill_batched.py``, as
``programs/prefill_windowed.py`` runs them) with the reference in the
program's place, a fault put into it (``reference/laguna_decoder.sizes``):

- ``float8``: float8-rounded operands (the nearest precision below the
  stated one) as the rows, and the reference's head with them as the logits
  (``float8.head``);
- ``no_window``: the sliding layers attend to every earlier key;
- ``window_1024``: a band twice as wide;
- ``full_rotary_in_sliding``: the full layers' rotary (partial, YaRN, theta
  500,000) turns a sliding layer's heads; ``sliding_rotary_in_full``: the
  reverse;
- ``no_attention_factor``: the full layers' cosines and sines as they are;
- ``no_head_gate``: the per-head output gate left out;
- ``eight_of_ten_experts``: a token's two least chosen experts dropped (and
  the gates renormalised over eight);
- ``softmax_router``, ``no_shared_expert``: as the other cells' controls.

Each has to come out as not correct by one of the rows' limits (the level
at 4 yardsticks; rows over the limit at
``prefill_batched.TOSSED_ROWS_SHARE``, which the cell keeps) or, for the
float8 head, by the head's; a fault that no limit can catch under random weights is RECORDED as
such (``caught`` false), not dropped. The program's own reading is printed
beside them. Lines go to ``chiprun_out/laguna_controls.jsonl``. A tool for a
builder, not a proof: nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FULL, SLIDING = "full_attention", "sliding_attention"
FAULTS = {"no_window": {"window": 0}, "window_1024": {"window": 1024},
          "full_rotary_in_sliding": {"rotary_of": {FULL: FULL, SLIDING: FULL}},
          "sliding_rotary_in_full": {"rotary_of": {FULL: SLIDING, SLIDING: SLIDING}},
          "no_attention_factor": {"attention_factor": False}, "no_head_gate": {"attn_gate": False},
          "eight_of_ten_experts": {"k_e": 8}, "softmax_router": {"scoring": "softmax"},
          "no_shared_expert": {"shared": False}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", default="", help="comma-separated faults (default: all)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.programs import prefill_batched, prefill_windowed
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()  # every fault's layer compiles once a checkout, not once a seed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs", "laguna_s21_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    faults = {k: v for k, v in FAULTS.items() if not args.only or k in args.only.split(",")}
    if args.rehearse:  # the rehearsal's window is 8 of 24 tokens, its experts 5 a token
        small = {"window_1024": {"window": 16}, "eight_of_ten_experts": {"k_e": 4}}
        faults = {k: small.get(k, v) for k, v in faults.items()}
    out_path = os.path.join(ROOT, "chiprun_out", "laguna_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    s, n = int(cfg["sequence_tokens"]), int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    last = (n - 1) * s + at  # the last sequence

    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_windowed.Program(cfg, seed, "", None)
        batch = harness.fill_batch(harness.make_check_frames(cfg["detector"], n, seed), n)
        frame = batch[n - 1:]
        want, stated = (np.asarray(program.reference_hidden(frame, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        rows = {"program": np.asarray(program.hidden(batch)[0][last], np.float32),
                "float8": np.asarray(program.reference_hidden(frame, jnp.float8_e4m3fn)[at])}
        for name, fault in faults.items():
            rows[name] = np.asarray(program.reference_hidden(frame, jnp.float32, **fault)[at])
            print(f"[controls] seed {seed}: {name} read", file=sys.stderr, flush=True)
        line = {"seed": seed}
        for name, got in rows.items():
            lo, line[name] = 0, {}
            for part, positions in parts.items():
                span = slice(lo, lo + len(positions))
                v = prefill_batched.rows_verdict(got[span], want[span], stated[span])
                line[name][part] = {k: v[k] for k in ("yardsticks", "rows_over_limit", "ok")}
                lo += len(positions)
            line[name]["ok"] = all(v["ok"] for part, v in line[name].items()
                                   if part not in prefill_batched.RECORD_ONLY)
        head = [program.reference_logits(rows["program"][-1:], c)
                for c in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn)]
        v = harness.precision_verdict(head[2], head[0], head[1])
        line["float8"]["head"] = {
            "yardsticks": v["logits_relative_rms"] / max(v["yardstick_relative_rms"], 1e-30),
            "ok": v["ok"]}
        line["caught"] = {name: not line[name]["ok"] for name in rows if name != "program"}
        line["caught"]["float8_head"] = not line["float8"]["head"]["ok"]
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
        del program  # 11.4 GB of weights: the next seed's do not fit beside them
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
