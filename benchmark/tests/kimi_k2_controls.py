#!/usr/bin/env python3
"""Can ``kimi_k2_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/kimi_k2_controls.py --seeds 5,3000000006

For each seed, at the cell's own size and on the batch's LAST sequence,
the check's comparisons (``programs/prefill_batched.py``, as
``programs/prefill_latent.py`` runs them) with the reference in the
program's place, a fault put into it (``reference/kimi_k2_decoder.sizes``):

- ``float8``: float8-rounded operands (the nearest precision below the
  stated one) as the rows, and the reference's head with them as the logits
  (``float8.head``);
- ``unturned_key``: the shared rotary key ``k_r`` left unturned;
- ``no_mscale``: the softmax scale without YaRN's ``m^2`` (1.8133);
- ``plain_rope``: plain rotary frequencies, pairs 20-31 not divided by 32;
- ``no_kv_norm``: the latent ``c_kv`` not normed before its decompression;
- ``no_shared_expert``: the shared expert left out;
- ``softmax_router``: a softmax router (no bias) in place of the sigmoid
  affinities under a selection bias;
- ``no_selection_bias``: the experts chosen by the affinity alone.

Each has to come out as not correct by one of the rows' limits (the level
at 4 yardsticks; rows over the limit at ``prefill_latent.TOSSED_ROWS_SHARE``)
or, for the float8 head, by the head's; the program's own reading is printed
beside them. Lines go to ``chiprun_out/kimi_k2_controls.jsonl``. A tool for
a builder, not a proof: nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {"unturned_key": {"turn_key": False}, "no_mscale": {"mscale": False},
          "plain_rope": {"yarn": False}, "no_kv_norm": {"kv_norm": False},
          "no_shared_expert": {"shared": False}, "softmax_router": {"scoring": "softmax"},
          "no_selection_bias": {"select_bias": False}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.programs import prefill_batched, prefill_latent
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    with open(os.path.join(ROOT, "benchmark", "configs", "kimi_k2_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    out_path = os.path.join(ROOT, "chiprun_out", "kimi_k2_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    s, n = int(cfg["sequence_tokens"]), int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    last = (n - 1) * s + at
    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_latent.Program(cfg, seed, "", None)
        batch = harness.fill_batch(harness.make_check_frames(cfg["detector"], n, seed), n)
        frame = batch[n - 1:]
        want, stated = (np.asarray(program.reference_hidden(frame, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        rows = {"program": np.asarray(program.hidden(batch)[0][last], np.float32),
                "float8": np.asarray(program.reference_hidden(frame, jnp.float8_e4m3fn)[at])}
        for name, fault in FAULTS.items():
            rows[name] = np.asarray(program.reference_hidden(frame, jnp.float32, **fault)[at])
        line = {"seed": seed}
        for name, got in rows.items():
            lo, line[name] = 0, {}
            for part, positions in parts.items():
                span = slice(lo, lo + len(positions))
                v = prefill_batched.rows_verdict(got[span], want[span], stated[span])
                line[name][part] = {k: v[k] for k in ("yardsticks", "rows_over_limit", "ok")}
                line[name][part]["ok"] = bool(  # the cell's own share, laid over the parent's
                    v["ok"] and v["rows_over_limit"] <= prefill_latent.TOSSED_ROWS_SHARE)
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
        del program  # 9.7 GB of weights: the next seed's do not fit beside them
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
