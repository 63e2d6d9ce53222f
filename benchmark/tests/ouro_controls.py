#!/usr/bin/env python3
"""Can ``ouro_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/ouro_controls.py --seeds 5,3000000006,77

For each seed, at the cell's own size and on the batch's LAST sequence, the
check's comparisons (``programs/prefill_batched.py``, as
``programs/prefill_looped.py`` runs them) with the reference in the
program's place, a fault put into it (``reference/ouro_decoder.sizes``);
the faults are the LOOP's:

- ``float8``: float8-rounded operands (the nearest precision below the
  stated one) as the rows and the exits, and the reference's head with them
  as the logits (``float8_head``, against the head's stated limit);
- ``three_passes``: a pass too few (its exit distribution has no fourth row:
  0 there);
- ``weights_not_shared``: in pass 2 every layer reads its neighbour's
  weights, another draw of the same distribution: that pass has weights of
  its own;
- ``no_norm_between``: the final norm after the last pass only;
- ``no_sandwich``: the branches' second norms left out;
- ``gate_before_norm``: the gate reads a pass's rows before their final norm
  (the last pass's rows are what they were: the exits alone can see it).

Each has to come out as not correct by one of the rows' limits (the level at
4 yardsticks; rows over the limit at ``prefill_looped.TOSSED_ROWS_SHARE``;
in ``first_rows`` too, which decides in this adapter) or by the exits'
(``prefill_looped.EXITS_FACTOR`` yardsticks); a fault that no limit can catch under random weights is
RECORDED as such (``caught`` false), not dropped. The program's own reading
is printed beside them, and the adapter's whole ``check`` (``isolated`` is
live: two frames a step). Lines go to ``chiprun_out/ouro_controls.jsonl``. A
tool for a builder, not a proof: nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {"three_passes": {"passes": 3}, "weights_not_shared": {"unshared_pass": 1},
          "no_norm_between": {"norm_between": False}, "no_sandwich": {"sandwich": False},
          "gate_before_norm": {"gate_before_norm": True}}


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
    from benchmark.programs import prefill_batched, prefill_looped
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()  # every fault's layer compiles once a checkout, not once a seed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs", "ouro_2p6b_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    only = args.only.split(",") if args.only else None
    faults = {k: v for k, v in FAULTS.items() if only is None or k in only}
    out_path = os.path.join(ROOT, "chiprun_out", "ouro_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    s, n = int(cfg["sequence_tokens"]), int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    last = (n - 1) * s + at  # the last sequence's checked rows
    share = prefill_looped.TOSSED_ROWS_SHARE

    def at_rows(result, passes=0):
        """``(h [S, d], p [R, S])`` -> the checked rows of each; a ``p`` short of ``passes`` rows reads 0 there."""
        rows, exits = (np.asarray(u) for u in result)
        exits = np.concatenate([exits, np.zeros((max(passes - len(exits), 0), s), exits.dtype)])
        return rows[at], exits[:, at]

    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_looped.Program(cfg, seed, "", None)
        frames = harness.make_check_frames(cfg["detector"], min(8, n), seed)
        batch = harness.fill_batch(frames, n)
        frame = batch[n - 1:]
        x, _, p = program._program(batch)
        got = {"program": (np.asarray(x[last], np.float32), np.asarray(p)[:, last])}
        (want, want_p), (stated, stated_p), got["float8"] = (
            at_rows(program._reference(frame, c))
            for c in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn))
        for name, fault in faults.items():
            got[name] = at_rows(program._reference(frame, jnp.float32, **fault), len(want_p))
            print(f"[controls] seed {seed}: {name} read", file=sys.stderr, flush=True)
        line = {"seed": seed, "batch": n}
        for name, (rows, exits) in got.items():
            lo, line[name] = 0, {}
            for part, positions in parts.items():
                span = slice(lo, lo + len(positions))
                v = prefill_batched.rows_verdict(rows[span], want[span], stated[span])
                line[name][part] = {k: v[k] for k in ("yardsticks", "rows_over_limit")}
                line[name][part]["ok"] = bool(v["ok"] and v["rows_over_limit"] <= share)
                lo += len(positions)
            v = prefill_looped.exits_verdict(exits, want_p, stated_p)
            line[name]["exits"] = {"yardsticks": v["yardsticks"], "ok": v["ok"]}
            line[name]["ok"] = all(v["ok"] for v in line[name].values())
        # the head's fault, on the program's own last hidden row, against the stated limit
        own = got["program"][0][-1:]
        apart = harness.relative_rms(program.reference_logits(own, jnp.float8_e4m3fn),
                                     program.reference_logits(own, jnp.float32))
        line["float8_head"] = {"head": {"logits_relative_rms": apart},
                               "ok": bool(apart <= prefill_looped.HEAD_LIMIT)}
        line["caught"] = {name: not v["ok"] for name, v in line.items()
                          if isinstance(v, dict) and "ok" in v and name != "program"}
        verdict = program.check(frames)  # the adapter's own, whole: isolated.0, isolated.1, head, served
        line["check"] = {k: v for k, v in verdict.items() if not isinstance(v, dict)}
        line["check"].update({k: {f: v[f] for f in ("ok", "yardsticks", "rows_over_limit",
                                                   "logits_relative_rms", "relative_rms_to_itself_moved",
                                                   "relative_rms_to_own_program", "limit") if f in v}
                              for k, v in verdict.items() if isinstance(v, dict)})
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
        del program  # 5.3 GB of weights: the next seed's do not fit beside them and the reference
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
