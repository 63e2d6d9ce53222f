#!/usr/bin/env python3
"""Can ``minicpm_sala_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/minicpm_sala_controls.py --seeds 5,3000000006

For each seed, at the cell's own size, the check's comparisons
(``programs/prefill_batched.py``, as ``programs/prefill_block_sparse.py`` runs
them) with the reference in the program's place, a fault put into it
(``reference/minicpm_sala_decoder.sizes``):

- ``float8``, ``float8_e5m2``: float8-rounded operands (the nearest precision
  below the stated one: the pooled scores', the attention's, the recurrence's
  two products and the MLP alike) as the rows, and the reference's head with
  e4m3 operands as the logits (``float8_head``);
- ``no_selection``: dense causal attention in the sparse layer;
- ``no_forced_local_blocks``: the selection without the latest 32 blocks forced;
- ``one_head_s_scores``: the group's first head's probabilities for the sum
  over its sixteen;
- ``first_key_of_a_pool``: a pool's first key for its mean;
- ``first_head_s_decay``: head 0's decay in every head; ``no_decay``: lambda = 1;
- ``state_not_carried``: the state dropped every 256 tokens (the kernel's
  chunk): nothing crosses a chunk's boundary;
- ``linear_unturned``: the linear layers without their rotary;
- ``rotary_in_sparse``: a plain rotary planted in the sparse layer (expected in
  ``first_rows``, as in granite);
- ``no_gate``: the sparse layer's output gate left out;
- ``m_is_1``: every branch added whole (``scale_depth / sqrt(32)`` = 1);
- ``logits_unscaled``: the logits not divided by ``hidden_size /
  dim_model_base`` (read by the head's limit).

Each has to come out as not correct by one of the rows' limits (the level at
4 yardsticks; rows over the limit at
``prefill_block_sparse.TOSSED_ROWS_SHARE``; in ``first_rows`` too, which
decides in this adapter) or, for the head's faults, by the head's; a fault
that no limit can catch under random weights is RECORDED as such (``caught``
false), not dropped. The program's own reading is printed beside them. Lines
go to ``chiprun_out/minicpm_sala_controls.jsonl``. A tool for a builder, not
a proof: nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {"no_selection": {"select": False}, "no_forced_local_blocks": {"window_size": 0},
          "one_head_s_scores": {"group_sum": False}, "first_key_of_a_pool": {"pool": "first"},
          "first_head_s_decay": {"decay": "first"}, "no_decay": {"decay": "none"},
          "state_not_carried": {"carry": 256}, "linear_unturned": {"rotary": False},
          "rotary_in_sparse": {"attn_rotary": True}, "no_gate": {"gate": False},
          "m_is_1": {"residual": 1.0}}
HEAD_FAULTS = {"logits_unscaled": {"logits_scaling": 1.0}}


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
    from benchmark.programs import prefill_batched, prefill_block_sparse
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()  # every fault's layer compiles once a checkout, not once a seed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm_sala_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    only = args.only.split(",") if args.only else None
    faults = {k: v for k, v in FAULTS.items() if only is None or k in only}
    if args.rehearse:  # a chunk of the rehearsal's 72 tokens
        faults = {k: {"carry": 24} if "carry" in v else v for k, v in faults.items()}
    out_path = os.path.join(ROOT, "chiprun_out", "minicpm_sala_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    n = int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    share = prefill_block_sparse.TOSSED_ROWS_SHARE

    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_block_sparse.Program(cfg, seed, "", None)
        frames = harness.make_check_frames(cfg["detector"], min(8, n), seed)
        batch = harness.fill_batch(frames, n)
        line = {"seed": seed, "batch": n}
        want, stated = (np.asarray(program.reference_hidden(batch, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        rows = {"program": np.asarray(program.hidden(batch)[0][at], np.float32)}
        if only is None or "float8" in only:
            rows["float8"] = np.asarray(program.reference_hidden(batch, jnp.float8_e4m3fn)[at])
            rows["float8_e5m2"] = np.asarray(program.reference_hidden(batch, jnp.float8_e5m2)[at])
        for name, fault in faults.items():
            rows[name] = np.asarray(program.reference_hidden(batch, jnp.float32, **fault)[at])
            print(f"[controls] seed {seed}: {name} read", file=sys.stderr, flush=True)
        for name, got in rows.items():
            lo, line[name] = 0, {}
            for part, positions in parts.items():
                span = slice(lo, lo + len(positions))
                v = prefill_batched.rows_verdict(got[span], want[span], stated[span])
                line[name][part] = {k: v[k] for k in ("yardsticks", "rows_over_limit", "ok")}
                line[name][part]["ok"] = bool(  # the cell's own share, laid over the parent's
                    v["ok"] and v["rows_over_limit"] <= share)
                lo += len(positions)
            line[name]["ok"] = all(v["ok"] for v in line[name].values())  # first_rows decides too
        # the head's faults, on the program's own last hidden row
        own = rows["program"][-1:]
        head = [program.reference_logits(own, c) for c in (jnp.float32, jnp.bfloat16)]
        logits = {"float8_head": program.reference_logits(own, jnp.float8_e4m3fn),
                  **{name: program.reference_logits(own, jnp.float32, **fault)
                     for name, fault in HEAD_FAULTS.items()}}
        for name, got in logits.items():
            v = harness.precision_verdict(got, head[0], head[1])
            line[name] = {"head": {
                "yardsticks": v["logits_relative_rms"] / max(v["yardstick_relative_rms"], 1e-30),
                "ok": v["ok"]}, "ok": v["ok"]}
        line["caught"] = {name: not v["ok"] for name, v in line.items()
                          if isinstance(v, dict) and "ok" in v and name != "program"}
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
        del program  # the next seed's weights do not fit beside these and the reference
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
