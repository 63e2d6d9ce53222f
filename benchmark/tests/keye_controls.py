#!/usr/bin/env python3
"""Can ``keye_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/keye_controls.py --seeds 5,3000000006

For each seed, at the cell's own size, the check's comparisons
(``programs/prefill.py``) with something else in the program's place:

- ``float8``: the reference with float8-rounded operands (the nearest
  precision below the stated one) as the rows, and its head as the logits;
- ``plain_causal``: the program itself with the indexer taken out, every
  query attending to every earlier key: what a broken ``Sel`` looks like;
- ``latest_keys``: the reference with ``Sel`` replaced by the 2,048 keys
  just before the query (a sliding window).

Each has to come out as not correct by one of the cell's limits; the
program's own reading is printed beside them. Lines go to
``chiprun_out/keye_controls.jsonl``. A tool for a builder, not a proof:
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
    import dataclasses

    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.programs import prefill
    from benchmark.reference import keye_decoder as ref
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()
    with open(os.path.join(ROOT, "benchmark", "configs", "keye_vl2_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    out_path = os.path.join(ROOT, "chiprun_out", "keye_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    parts = prefill.check_parts(cfg)
    at = np.concatenate(list(parts.values()))
    topk = int(cfg["sa_config"]["topk"])

    def latest(scores, t, k):
        keys = jnp.arange(scores.shape[1])[None, :]
        return (keys <= t[:, None]) & (keys > t[:, None] - k)

    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill.Program(cfg, seed, "", None)
        batch = harness.fill_batch(harness.make_check_frames(cfg["detector"], 1, seed), 1)
        want, stated = (np.asarray(program.reference_hidden(batch, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        rows = {"program": np.asarray(program.hidden(batch)[0][at], np.float32),
                "float8": np.asarray(program.reference_hidden(batch, jnp.float8_e4m3fn)[at]),
                "plain_causal": np.asarray(program.hidden(
                    batch, dataclasses.replace(program.dcfg, indexer_heads=None))[0][at], np.float32)}
        select, ref.select = ref.select, latest
        try:
            rows["latest_keys"] = np.asarray(program.reference_hidden(batch, jnp.float32)[at])
        finally:
            ref.select = select
        line = {"seed": seed, "topk": topk}
        for name, got in rows.items():
            lo, line[name] = 0, {}
            for part, positions in parts.items():
                span = slice(lo, lo + len(positions))
                v = prefill.rows_verdict(got[span], want[span], stated[span])
                line[name][part] = {k: v[k] for k in ("yardsticks", "rows_over_limit", "ok")}
                lo += len(positions)
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
