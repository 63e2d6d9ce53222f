#!/usr/bin/env python3
"""Can ``nemotron3_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/nemotron3_controls.py --seeds 5,3000000006

For each seed, at the cell's own size and on the batch's LAST sequence, the
check's comparisons (``programs/prefill_batched.py``, as
``programs/prefill_blocks.py`` runs them) with the reference in the program's
place, a fault put into it (``reference/nemotron3_decoder.sizes``):

- ``float8``: float8-rounded operands (the nearest precision below the
  stated one: the scan's two products, attention and the experts alike) as
  the rows, and the reference's head with them as the logits
  (``float8_head``);
- ``relu``: ``relu`` for ``relu^2`` in every expert and the shared one;
- ``gated_silu``: a GATED SiLU expert over the one product there is,
  ``silu(u) * u``;
- ``one_bc``: group 0's ``B`` and ``C`` for all 64 heads;
- ``norm_all_channels``: the gated norm over all 4,096 channels, not by group;
- ``norm_before_gate``: Mamba-2's other order, ``rms(y) * silu(z)``;
- ``state_not_carried``: the state dropped every 512 tokens (the kernel's
  chunk): nothing crosses a chunk's boundary;
- ``no_skip``, ``no_dt_bias``, ``no_conv_bias``: ``D x``, the step's bias or
  the convolution's bias left out;
- ``rotary``: a plain rotary at ``rope_theta`` planted in the ``*`` layers
  (what ``first_rows`` sees where anything does);
- ``softmax_router``, ``no_select_bias``, ``scaling_one`` (1 for 2.5),
  ``no_shared_expert``;
- ``no_reset``: the PROGRAM with the scan and the convolution told that the
  batch's rows are ONE sequence, so that a sequence starts from its
  neighbour's last state and last three rows (read by the check's
  ``isolated``: the same program with the batch's frames moved one place on).

Each has to come out as not correct by one of the rows' limits (the level at
4 yardsticks; rows over the limit at ``prefill_blocks.TOSSED_ROWS_SHARE``, in
``patch_rows`` or ``prompt_rows``: ``first_rows`` is printed and decides
nothing, as in the check), by ``isolated``'s or, for the head's fault, by the
head's; a fault that no limit can catch under random weights is RECORDED as
such (``caught`` false), not dropped, and ``caught_by_first_rows`` says what
that part alone would have said. The program's own reading is printed beside
them. Lines go to ``chiprun_out/nemotron3_controls.jsonl``. A tool for a
builder, not a proof: nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {"relu": {"act": "relu"}, "gated_silu": {"act": "gated_silu"}, "one_bc": {"one_bc": True},
          "norm_all_channels": {"norm_groups": 1}, "norm_before_gate": {"gate_first": False},
          "state_not_carried": {"carry": 512}, "no_skip": {"skip": False},
          "no_dt_bias": {"dt_bias": False}, "no_conv_bias": {"conv_bias": False},
          "rotary": {"rotary": True}, "softmax_router": {"scoring": "softmax"},
          "no_select_bias": {"select_bias": False}, "scaling_one": {"scale": 1.0},
          "no_shared_expert": {"shared": False}}


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
    from benchmark.programs import prefill_batched, prefill_blocks
    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()  # every fault's layer compiles once a checkout, not once a seed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_prefill_epix10k2m.json")) as f:
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
    out_path = os.path.join(ROOT, "chiprun_out", "nemotron3_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    s, n = int(cfg["sequence_tokens"]), int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    last, moved_to = (n - 1) * s + at, parts["first_rows"]  # the last sequence; it, moved to the front
    share = prefill_blocks.TOSSED_ROWS_SHARE
    scan, conv = decoder.ssd_scan, decoder.conv_silu

    def one_sequence(*operands, seq_len, **kwargs):  # the fault: no state starts at 0 but the first
        return scan(*operands, seq_len=operands[0].shape[0], **kwargs)

    def one_run_of_rows(u, taps_w, seq_len, bias=None):  # and no convolution meets zeros but the first
        return conv(u, taps_w, u.shape[0], bias)

    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_blocks.Program(cfg, seed, "", None)
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
                "float8": np.asarray(program.reference_hidden(frame, jnp.float8_e4m3fn)[at])}
        for name, fault in faults.items():
            rows[name] = np.asarray(program.reference_hidden(frame, jnp.float32, **fault)[at])
            print(f"[controls] seed {seed}: {name} read", file=sys.stderr, flush=True)
        moved = {}
        if n > 1 and (only is None or "no_reset" in only):
            moved["program"] = np.asarray(
                program.hidden(np.roll(batch, 1, axis=0))[0][moved_to], np.float32)
            decoder.ssd_scan, decoder.conv_silu = one_sequence, one_run_of_rows
            try:
                rows["no_reset"] = np.asarray(program.hidden(batch)[0][last], np.float32)
                moved["no_reset"] = np.asarray(
                    program.hidden(np.roll(batch, 1, axis=0))[0][moved_to], np.float32)
            finally:
                decoder.ssd_scan, decoder.conv_silu = scan, conv
        for name, got in rows.items():
            lo, line[name] = 0, {}
            for part, positions in parts.items():
                span = slice(lo, lo + len(positions))
                v = prefill_batched.rows_verdict(got[span], want[span], stated[span])
                line[name][part] = {k: v[k] for k in ("yardsticks", "rows_over_limit", "ok")}
                line[name][part]["ok"] = bool(  # the share the check holds a part to
                    v["ok"] and v["rows_over_limit"] <= share)
                lo += len(positions)
            if name in moved:  # the check's `isolated`: the same program, the sequence moved
                first = slice(0, len(moved_to))
                apart = harness.relative_rms(moved[name], got[first])
                limit = prefill_batched.rows_verdict(
                    got[first], want[first], stated[first])["yardstick_relative_rms_level"]
                line[name]["isolated"] = {"relative_rms_to_itself_moved": apart, "limit": limit,
                                          "ok": bool(apart <= limit)}
            # as in the check: a sequence's first rows are printed and decide nothing
            line[name]["ok"] = all(v["ok"] for part, v in line[name].items()
                                   if part not in prefill_batched.RECORD_ONLY)
        # the head's fault, on the program's own last hidden row
        own = rows["program"][-1:]
        head = [program.reference_logits(own, c) for c in (jnp.float32, jnp.bfloat16)]
        v = harness.precision_verdict(program.reference_logits(own, jnp.float8_e4m3fn), *head)
        line["float8_head"] = {"head": {
            "yardsticks": v["logits_relative_rms"] / max(v["yardstick_relative_rms"], 1e-30),
            "ok": v["ok"]}, "ok": v["ok"]}
        line["caught"] = {name: not v["ok"] for name, v in line.items()
                          if isinstance(v, dict) and "ok" in v and name not in ("program", "check")}
        line["caught_by_first_rows"] = {
            name: not v["first_rows"]["ok"] for name, v in line.items()
            if isinstance(v, dict) and "first_rows" in v and name != "program"}
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
        del program  # 9.2 GB of weights: the next seed's do not fit beside them and the reference
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
