#!/usr/bin/env python3
"""Can ``dsv32_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/dsv32_controls.py --seeds 5,3000000006

For each seed, at the cell's own size, the check's comparisons
(``programs/prefill_batched.py``, as ``programs/prefill_latent_selected.py``
runs them) with the reference in the program's place, a fault put into it
(``reference/deepseek_v32_decoder.sizes``):

- ``float8``: float8-rounded operands (the nearest precision below the
  stated one: index scores, attention and experts alike) as the rows, and
  the reference's head with them as the logits (``float8.head``);
- ``no_indexer``: every causal key attended (DeepSeek-V3's attention);
- ``latest_keys``: ``Sel`` as the latest 2,048 keys (a sliding window);
- ``query_from_input``: the index queries from the layer's normed input
  (its first 1,536 components: what ``W_Iq``'s shape takes) instead of the
  query's normed low rank;
- ``rms_index_key``: the index key RMS-normed, not LayerNormed;
- ``whole_index_rope``: the rotary over all 128 components of an index vector;
- ``no_group_limit``: plain top 8 of 256;
- ``no_selection_bias``: the experts chosen by the affinity alone;
- ``no_shared_expert``: the shared expert left out;
- ``no_mscale``: the softmax scale without YaRN's ``m^2`` (1.8739).

Each has to come out as not correct by one of the rows' limits (the level
at 4 yardsticks; rows over the limit at
``prefill_latent_selected.TOSSED_ROWS_SHARE``) or, for the float8 head, by
the head's; a fault that no limit can catch under random weights is
RECORDED as such (``caught`` false), not dropped. The program's own reading
is printed beside them, and ``selected_keys``: of layer 0's ``Sel(t)`` at 64
queries past the 2,048th, how many keys differ between the program's mask
(bf16 index operands, the kernel's sum) and the reference's float32 scores
on the same rows. Lines go to ``chiprun_out/dsv32_controls.jsonl``. A tool
for a builder, not a proof: nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {"no_indexer": {"indexer": False}, "latest_keys": {"select": "latest"},
          "query_from_input": {"index_query": "input"}, "rms_index_key": {"index_key_norm": "rms"},
          "whole_index_rope": {"index_rope": "whole"}, "no_group_limit": {"group_limit": False},
          "no_selection_bias": {"select_bias": False}, "no_shared_expert": {"shared": False},
          "no_mscale": {"mscale": False}}


def selected_keys(program, x0) -> dict:
    """Layer 0's ``Sel``: the program's mask against the reference's sets,
    both from the embedded rows ``x0 [S, d]`` the program has."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.keye_decoder import rms, select
    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.parallel import sparse_attention as sa

    ref, dcfg = program.ref, program.dcfg
    m = ref.sizes(program.cfg)
    p = program.params["layers"][0]
    s = x0.shape[0]
    at = np.unique(np.linspace(min(m["topk"], s - 1), s - 1, 64).round().astype(np.int64))
    angles = decoder.rotary_angles(np.arange(s), dcfg.rope_theta, dcfg.rope_dim // 2, None,
                                   dcfg.rope_yarn)

    @jax.jit
    def ours(p, x):
        *_, a, c_q = decoder._latent_projections(p, x, angles, dcfg)
        mask, _ = decoder._indexer(p, a, angles, dcfg, q_from=c_q)
        return sa.mask_to_dense(mask)[at]

    @jax.jit
    def theirs(p, x):
        a = rms(x.astype(jnp.float32), p["norm1"], m["eps"])
        c_q = rms(ref._mm(a, p["wq_a"], jnp.float32), p["q_a_norm"], m["eps"])
        ang = jnp.asarray(np.arange(s, dtype=np.float64)[:, None] * ref.yarn_inv_freq(m), jnp.float32)
        q_i, k_i, w_i = ref.index_vectors(p, a, c_q, ang, m, jnp.float32)
        dots = ref._mm(q_i[at].reshape(len(at) * m["HI"], m["dI"]), k_i.T, jnp.float32)
        scores = jnp.sum(w_i[at][:, :, None] * jax.nn.relu(dots.reshape(len(at), m["HI"], s)),
                         axis=1) / np.sqrt(m["dI"])
        return select(scores, jnp.asarray(at), m["topk"])

    got = np.asarray(ours(p, x0))  # the program's own precision: its kernel takes bf16 operands
    with jax.default_matmul_precision("highest"):
        want = np.asarray(theirs(p, x0))
    differ = (got & ~want).sum(axis=1)  # keys of ours that are not theirs: as many the other way
    return {"queries": int(len(at)), "topk": m["topk"], "keys_differ_median": float(np.median(differ)),
            "keys_differ_max": int(differ.max()), "keys_differ_min": int(differ.min())}


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
    from benchmark.programs import prefill_batched, prefill_latent_selected
    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()  # every fault's layer compiles once a checkout, not once a seed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs", "deepseek_v32_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    faults = {k: v for k, v in FAULTS.items() if not args.only or k in args.only.split(",")}
    out_path = os.path.join(ROOT, "chiprun_out", "dsv32_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    share = prefill_latent_selected.TOSSED_ROWS_SHARE
    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_latent_selected.Program(cfg, seed, "", None)
        batch = harness.fill_batch(harness.make_check_frames(cfg["detector"], 1, seed), 1)
        want, stated = (np.asarray(program.reference_hidden(batch, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        rows = {"program": np.asarray(program.hidden(batch)[0][at], np.float32),
                "float8": np.asarray(program.reference_hidden(batch, jnp.float8_e4m3fn)[at])}
        for name, fault in faults.items():
            rows[name] = np.asarray(program.reference_hidden(batch, jnp.float32, **fault)[at])
            print(f"[controls] seed {seed}: {name} read", file=sys.stderr, flush=True)
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
        x0 = jax.jit(lambda p, c, f, ids: decoder.frame_hidden(
            {**p, "layers": []}, c, f, ids, cfg=program.dcfg,
            threshold=float(cfg["calib_threshold"]))[0])(
            program.params, program.calib_d, jax.device_put(batch), program.prompt_ids)
        try:  # for the record: a fault here must not lose the seed's controls
            line["selected_keys"] = selected_keys(program, x0)
        except Exception as e:  # noqa: BLE001
            line["selected_keys"] = {"error": repr(e)[:300]}
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
        del program  # 7.7 GB of weights: the next seed's do not fit beside them
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
