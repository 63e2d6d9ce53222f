#!/usr/bin/env python3
"""Can ``olmo_hybrid_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/olmo_hybrid_controls.py --seeds 5,3000000006
    python3 benchmark/tests/olmo_hybrid_controls.py --seeds 7 --batch 2      # `isolated` on the chip

For each seed, at the cell's own size and on the batch's LAST sequence, the
check's comparisons (``programs/prefill_batched.py``, as
``programs/prefill_reordered.py`` runs them) with the reference in the
program's place, a fault put into it
(``reference/olmo_hybrid_decoder.sizes``):

- ``float8``, ``float8_e5m2``: float8-rounded operands (the nearest
  precision below the stated one: the recurrence's three products, attention
  and the MLP alike; e4m3 and, because e4m3 ends at 448 with no infinity
  and an un-normed branch input can pass that, e5m2) as the rows, and the
  reference's head with e4m3 operands as the logits (``float8_head``);
- ``bf16_state``: the delta rule's state rounded to bfloat16 after every token;
- ``state_not_carried``: the state dropped every 128 tokens (the kernel's
  chunk): nothing crosses a chunk's boundary;
- ``beta_without_its_2``: the step size in (0, 1);
- ``no_decay``: alpha = 1; ``first_head_s_decay``: the first head's decay in
  every head;
- ``sigmoid_gate``: a sigmoid for the SiLU gate; ``gate_before_norm``:
  Mamba-2's order, ``rms(o * silu(z))``;
- ``no_l2_norm``: q and k as their convolutions left them;
- ``latest_taps_only``: a convolution without its earlier taps;
- ``norm_before_branch``: ``x + Op(rms(x))`` in place of ``x + rms(Op(x))``;
- ``qk_norm_a_head``: each head's 128 columns normed on their own in place of
  the whole projection;
- ``rotary``: a plain rotary at ``rope_theta`` 500,000 in the full layers
  (expected in ``first_rows`` alone, as in granite);
- with ``--batch 2``, ``no_reset``: the PROGRAM with the kernel and the
  convolutions told that the batch's rows are ONE sequence, so that a sequence
  starts from its neighbour's last state and last three rows (read by the
  check's ``isolated``: the same program with the batch's frames moved one
  place on). ``--batch 2`` also runs the adapter's own ``check`` on that batch
  and prints its verdict (``isolated.0``, ``isolated.1``): the cell itself
  serves one frame a step, where ``isolated`` is vacuous.

Each has to come out as not correct by one of the rows' limits (the level at
4 yardsticks; rows over the limit at ``prefill_reordered.TOSSED_ROWS_SHARE``;
in ``first_rows`` too, which decides in this adapter), by ``isolated``'s or,
for the head's fault, by the head's; a fault that no limit can catch under
random weights is RECORDED as such (``caught`` false), not dropped. The
program's own reading is printed beside them. Lines go to
``chiprun_out/olmo_hybrid_controls.jsonl``. A tool for a builder, not a proof:
nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {"bf16_state": {"state": "bfloat16"}, "state_not_carried": {"carry": 128},
          "beta_without_its_2": {"beta_scale": 1.0}, "no_decay": {"decay": "none"},
          "first_head_s_decay": {"decay": "first"}, "sigmoid_gate": {"gate": "sigmoid"},
          "gate_before_norm": {"gate_first": True}, "no_l2_norm": {"l2": False},
          "latest_taps_only": {"taps_used": (2, 3)}, "norm_before_branch": {"norm_place": "before"},
          "qk_norm_a_head": {"qk_norm": "head"}, "rotary": {"rotary": True}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--batch", type=int, default=0, help="frames a step (default: the cell's)")
    ap.add_argument("--only", default="", help="comma-separated faults (default: all)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.programs import prefill_batched, prefill_reordered
    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()  # every fault's layer compiles once a checkout, not once a seed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo_hybrid_7b_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    if args.batch:
        cfg.update(batch_size=args.batch, step_tokens=args.batch * int(cfg["sequence_tokens"]))
        cfg["reference"] = {**cfg["reference"], "sequences": [0, -1]}
    only = args.only.split(",") if args.only else None
    faults = {k: v for k, v in FAULTS.items() if only is None or k in only}
    if args.rehearse:  # a chunk of the rehearsal's 24 tokens
        faults = {k: {"carry": 8} if "carry" in v else v for k, v in faults.items()}
    out_path = os.path.join(ROOT, "chiprun_out", "olmo_hybrid_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    s, n = int(cfg["sequence_tokens"]), int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    last, moved_to = (n - 1) * s + at, parts["first_rows"]  # the last sequence; it, moved to the front
    share = prefill_reordered.TOSSED_ROWS_SHARE
    scan, conv = decoder.gated_delta_net, decoder.conv_silu

    def one_sequence(*operands, seq_len, **kwargs):  # the fault: no state starts at 0 but the first
        return scan(*operands, seq_len=operands[0].shape[0], **kwargs)

    def one_run_of_rows(u, taps_w, seq_len, bias=None):  # and no convolution meets zeros but the first
        return conv(u, taps_w, u.shape[0], bias)

    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_reordered.Program(cfg, seed, "", None)
        frames = harness.make_check_frames(cfg["detector"], min(8, n), seed)
        batch = harness.fill_batch(frames, n)
        line = {"seed": seed, "batch": n}
        if args.batch:  # the adapter's own check at this batch: isolated.0, isolated.1
            verdict = program.check(frames)
            line["check"] = {k: v for k, v in verdict.items() if not k.startswith("first_rows")}
            print(f"[controls] seed {seed}: check at batch {n}: {json.dumps(line['check'])}",
                  file=sys.stderr, flush=True)
        frame = batch[n - 1:]
        want, stated = (np.asarray(program.reference_hidden(frame, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        rows = {"program": np.asarray(program.hidden(batch)[0][last], np.float32),
                "float8": np.asarray(program.reference_hidden(frame, jnp.float8_e4m3fn)[at]),
                # (e4m3 has no infinity and ends at 448: where nothing norms a branch's input an
                # operand can pass that and the reading is NaN, not correct but no number; e5m2, two
                # bits of mantissa, reaches 57,344 and gives the number)
                "float8_e5m2": np.asarray(program.reference_hidden(frame, jnp.float8_e5m2)[at])}
        for name, fault in faults.items():
            rows[name] = np.asarray(program.reference_hidden(frame, jnp.float32, **fault)[at])
            print(f"[controls] seed {seed}: {name} read", file=sys.stderr, flush=True)
        moved = {}
        if n > 1 and (only is None or "no_reset" in only):
            moved["program"] = np.asarray(
                program.hidden(np.roll(batch, 1, axis=0))[0][moved_to], np.float32)
            decoder.gated_delta_net, decoder.conv_silu = one_sequence, one_run_of_rows
            jax.clear_caches()  # q's and k's convolution is traced inside a function of its own
            try:
                rows["no_reset"] = np.asarray(program.hidden(batch)[0][last], np.float32)
                moved["no_reset"] = np.asarray(
                    program.hidden(np.roll(batch, 1, axis=0))[0][moved_to], np.float32)
            finally:
                decoder.gated_delta_net, decoder.conv_silu = scan, conv
                jax.clear_caches()
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
            line[name]["ok"] = all(v["ok"] for v in line[name].values())  # first_rows decides too
        # the head's fault, on the program's own last hidden row
        own = rows["program"][-1:]
        head = [program.reference_logits(own, c) for c in (jnp.float32, jnp.bfloat16)]
        for name, logits in (("float8_head", program.reference_logits(own, jnp.float8_e4m3fn)),):
            v = harness.precision_verdict(logits, head[0], head[1])
            line[name] = {"head": {
                "yardsticks": v["logits_relative_rms"] / max(v["yardstick_relative_rms"], 1e-30),
                "ok": v["ok"]}, "ok": v["ok"]}
        line["caught"] = {name: not v["ok"] for name, v in line.items()
                          if isinstance(v, dict) and "ok" in v and name not in ("program", "check")}
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
        del program  # 8.2 GB of weights: the next seed's do not fit beside them and the reference
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
