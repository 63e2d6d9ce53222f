#!/usr/bin/env python3
"""Can ``ling3_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/ling3_controls.py --seeds 5,3000000006

For each seed, at the cell's own size and on the batch's LAST sequence,
the check's comparisons (``programs/prefill_batched.py``, as
``programs/prefill_hybrid.py`` runs them) with the reference in the
program's place, a fault put into it (``reference/ling3_decoder.sizes``):

- ``float8``: float8-rounded operands (the nearest precision below the
  stated one: the recurrence's three products, attention and experts
  alike) as the rows, and the reference's head with them as the logits
  (``float8.head``);
- ``bf16_state``: the recurrence's state rounded to bfloat16 after every token;
- ``no_decay``: alpha = 1 (the delta rule without its gate);
- ``decay_per_head``: a head's mean log-decay in all its 128 channels (the
  gated delta rule this model is NOT);
- ``beta_one``: the step size 1;
- ``state_not_carried``: the state dropped every 128 tokens (the kernel's
  chunk): nothing crosses a chunk's boundary;
- ``latest_taps_only``: the convolutions' two earlier taps dropped;
- ``no_l2_norm``: q and k as the SiLU left them;
- ``no_output_norm``, ``no_output_gate``: the linear layers' per-head norm,
  or their sigmoid gate, left out;
- ``no_head_gate``: the latent layer's head-wise gate left out;
- ``softmax_router``, ``no_shared_expert``: as the other cells' controls;
- ``no_reset``: the PROGRAM with the kernel told that the batch's rows are
  ONE sequence, so that a sequence starts from its neighbour's last state
  (read by the check's ``isolated``: the same program with the batch's
  frames moved one place on).

Each has to come out as not correct by one of the rows' limits (the level
at 4 yardsticks; rows over the limit at
``prefill_hybrid.TOSSED_ROWS_SHARE``), by ``isolated``'s or, for the
float8 head, by the head's; a fault that no limit can catch under random
weights is RECORDED as such (``caught`` false), not dropped. The
program's own reading is printed beside them. Lines go to
``chiprun_out/ling3_controls.jsonl``. A tool for a builder, not a proof:
nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {"bf16_state": {"state": "bfloat16"}, "no_decay": {"decay": "none"},
          "decay_per_head": {"decay": "head"}, "beta_one": {"beta": False},
          "state_not_carried": {"carry": 128}, "latest_taps_only": {"taps_used": (2, 3)},
          "no_l2_norm": {"l2": False}, "no_output_norm": {"o_norm": False},
          "no_output_gate": {"o_gate": False}, "no_head_gate": {"attn_gate": False},
          "softmax_router": {"scoring": "softmax"}, "no_shared_expert": {"shared": False}}


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
    from benchmark.programs import prefill_batched, prefill_hybrid
    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()  # every fault's layer compiles once a checkout, not once a seed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs", "ling3_flash_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    faults = {k: v for k, v in FAULTS.items() if not args.only or k in args.only.split(",")}
    if args.rehearse:  # a chunk of the rehearsal's 24 tokens
        faults = {k: ({"carry": 8} if "carry" in v else v) for k, v in faults.items()}
    out_path = os.path.join(ROOT, "chiprun_out", "ling3_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    s, n = int(cfg["sequence_tokens"]), int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    last, moved_to = (n - 1) * s + at, parts["first_rows"]  # the last sequence; it, moved to the front
    share = prefill_hybrid.TOSSED_ROWS_SHARE
    kernel = decoder.gated_delta_rule

    def one_sequence(qkv, *rest, seq_len, **kwargs):  # the fault: no state starts at 0 but the first
        return kernel(qkv, *rest, seq_len=qkv.shape[0], **kwargs)

    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_hybrid.Program(cfg, seed, "", None)
        batch = harness.fill_batch(harness.make_check_frames(cfg["detector"], n, seed), n)
        frame = batch[n - 1:]
        want, stated = (np.asarray(program.reference_hidden(frame, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        rows = {"program": np.asarray(program.hidden(batch)[0][last], np.float32),
                "float8": np.asarray(program.reference_hidden(frame, jnp.float8_e4m3fn)[at])}
        for name, fault in faults.items():
            rows[name] = np.asarray(program.reference_hidden(frame, jnp.float32, **fault)[at])
            print(f"[controls] seed {seed}: {name} read", file=sys.stderr, flush=True)
        moved = {"program": np.asarray(program.hidden(np.roll(batch, 1, axis=0))[0][moved_to],
                                       np.float32)}
        if not args.only or "no_reset" in args.only.split(","):
            decoder.gated_delta_rule = one_sequence
            try:
                rows["no_reset"] = np.asarray(program.hidden(batch)[0][last], np.float32)
                moved["no_reset"] = np.asarray(
                    program.hidden(np.roll(batch, 1, axis=0))[0][moved_to], np.float32)
            finally:
                decoder.gated_delta_rule = kernel
        line = {"seed": seed}
        for name, got in rows.items():
            lo, line[name] = 0, {}
            for part, positions in parts.items():
                span = slice(lo, lo + len(positions))
                v = prefill_batched.rows_verdict(got[span], want[span], stated[span])
                line[name][part] = {k: v[k] for k in ("yardsticks", "rows_over_limit", "ok")}
                line[name][part]["ok"] = bool(  # the cell's own share, laid over the parent's
                    v["ok"] and v["rows_over_limit"] <= share)
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
        line["caught"] = {name: not line[name]["ok"] for name in rows if name != "program"}
        line["caught"]["float8_head"] = not line["float8"]["head"]["ok"]
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
        del program  # 10.5 GB of weights: the next seed's do not fit beside them
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
