#!/usr/bin/env python3
"""Can ``phi4flash_epix_saturated``'s ``correct`` tell a fault? On the chip:

    python3 benchmark/tests/phi4flash_controls.py --seeds 5,3000000006
    python3 benchmark/tests/phi4flash_controls.py --seeds 7 --only no_skip,rotary

For each seed, at the cell's own size (batch 2) and on the batch's LAST
sequence, the check's comparisons (``programs/prefill_batched.py``, as
``programs/prefill_cut.py`` runs them) with the reference in the program's
place, a fault put into it (``reference/phi4flash_decoder.sizes``):

- ``float8``: float8-rounded operands (the nearest precision below the
  stated one: every product's, the recurrence's two among them) as the rows,
  and the reference's head with them as the logits (``float8_head``);
- the scan's: ``state_not_carried`` (the state dropped every 512 tokens, the
  kernel's chunk), ``first_channel_s_decays`` (``A`` of channel 0 in every
  channel), ``initialiser_s_decays`` (``A = -(n + 1)`` assumed) — these two
  read 0 under the initialiser's own ``A`` and are RECORDED as not caught:
  ``tests/test_decoder_phi4flash.py`` holds both under a random ``A`` —
  ``no_softplus``, ``no_skip``, ``last_tap_alone`` (a convolution without its
  earlier taps);
- differential attention's: ``one_softmax`` (lambda 0), ``lambda_init_alone``,
  ``no_sub_norm``, ``second_values_twice`` (``v2`` for ``v1`` in both halves),
  ``no_window``, ``window_doubled`` (1,024), ``plain_softmax`` (40 / 20 heads
  of 64), ``rotary`` (planted);
- what is handed on: ``memory_after_gate``, ``memory_from_an_earlier_scan``
  (layer 14's ``y``), ``keys_from_a_windowed_layer`` (layer 15's);
- THE CUT'S OWN, in the PROGRAM's place, read by ``served`` (the timed step's
  logits against the all-rows program's) and by nothing else:
  ``keys_cut_to_the_served_rows`` (layer 17's keys and values of the served
  rows alone), ``the_other_frame_s_row`` (the cross-decoder fed the batch's
  rows one place on), ``memory_at_row_0``;
- ``no_reset``: the PROGRAM with the scan and the convolution told that the
  batch's rows are ONE sequence (read by the check's ``isolated``: the same
  program with the batch's frames moved one place on, against 0 exactly).

Each has to come out as not correct by one of the rows' limits (the level at
4 yardsticks; rows over the limit at ``prefill_cut.TOSSED_ROWS_SHARE``; in
``first_rows`` too, which decides in this adapter), by ``isolated``'s, by
``served``'s or, for the head's fault, by the head's; a fault that no limit
can catch under random weights is RECORDED as such (``caught`` false), not
dropped. The program's own reading is printed beside them. Lines go to
``chiprun_out/phi4flash_controls.jsonl``. A tool for a builder, not a proof:
nothing reads its output."""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {"state_not_carried": {"carry": 512}, "first_channel_s_decays": {"a": "first"},
          "initialiser_s_decays": {"a": "ramp"}, "no_softplus": {"softplus": False},
          "no_skip": {"skip": False}, "last_tap_alone": {"taps_used": (3,)},
          "one_softmax": {"lam": "zero"}, "lambda_init_alone": {"lam": "init"},
          "no_sub_norm": {"sub_norm": False}, "second_values_twice": {"values": "second"},
          "no_window": {"window": 0}, "window_doubled": {"window": 1024},
          "plain_softmax": {"plain": True}, "rotary": {"rotary": True},
          "memory_after_gate": {"memory": "gated"}, "memory_from_an_earlier_scan": {"memory_from": 14},
          "keys_from_a_windowed_layer": {"kv_from": 15}}
CUT = ("keys_cut_to_the_served_rows", "the_other_frame_s_row", "memory_at_row_0")


def plant(decoder, name):
    """Put one of the cut's own faults (or ``no_reset``) into the package's
    functions -> the call that takes it out again."""
    import jax.numpy as jnp

    saved = {}

    def put(attr, fn):
        saved[attr] = getattr(decoder, attr)
        setattr(decoder, attr, fn)

    if name == "keys_cut_to_the_served_rows":
        inputs, rows_of = decoder._diff_inputs, decoder._row_attention

        def cut(p, x, cfg, batch, rows, cross):
            out = inputs(p, x, cfg, batch, rows, cross)
            if rows is None or cross:
                return out
            at = np.asarray(rows)
            return (*out[:3], *(u.reshape(batch, -1, u.shape[-1])[:, at].reshape(-1, u.shape[-1])
                                for u in out[3:]))

        put("_diff_inputs", cut)
        put("_row_attention", lambda q, k, v, at, g: rows_of(q, k, v, tuple(range(len(at))), g))
    elif name == "the_other_frame_s_row":
        unit = decoder.gated_memory
        put("gated_memory", lambda p, x, memory, cfg: unit(p, jnp.roll(x, 1, axis=0), memory, cfg))
    elif name == "memory_at_row_0":
        layer = decoder.selective_state_space

        def first_row(p, x, batch, cfg, keep=False):
            out = layer(p, x, batch, cfg, keep)
            if not keep:
                return out
            y = out[1].reshape(batch, -1, out[1].shape[-1])
            return out[0], jnp.broadcast_to(y[:, :1], y.shape).reshape(out[1].shape)

        put("selective_state_space", first_row)
    elif name == "no_reset":  # no state starts at 0 but the first, no convolution meets zeros
        scan, conv = decoder.selective_scan, decoder.conv_silu
        put("selective_scan", lambda *operands, seq_len, **kw: scan(
            *operands, seq_len=operands[0].shape[0], **kw))
        put("conv_silu", lambda u, taps_w, seq_len, bias=None: conv(u, taps_w, u.shape[0], bias))

    def restore():
        for attr, fn in saved.items():
            setattr(decoder, attr, fn)

    return restore


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
    from benchmark.programs import prefill_batched, prefill_cut
    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    configure_compile_cache()  # every fault's layer compiles once a checkout, not once a seed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4_mini_flash_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    if args.rehearse:
        cfg.update(cfg["rehearse"])
    only = args.only.split(",") if args.only else None
    faults = {k: v for k, v in FAULTS.items() if only is None or k in only}
    if args.rehearse:  # the rehearsal's chunk, window and layers
        small = {"carry": 8, "window": 16, "memory_from": 2, "kv_from": 3}
        faults = {k: {key: small[key] if key in small and val else val for key, val in v.items()}
                  for k, v in faults.items()}  # (a window of 0 stays none)
    planted = [name for name in (*CUT, "no_reset") if only is None or name in only]
    out_path = os.path.join(ROOT, "chiprun_out", "phi4flash_controls.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    s, n = int(cfg["sequence_tokens"]), int(cfg["batch_size"])
    parts = prefill_batched.first_and_spread(cfg)
    at = np.concatenate(list(parts.values()))
    last, moved_to = (n - 1) * s + at, parts["first_rows"]  # the last sequence; it, moved to the front
    share = prefill_cut.TOSSED_ROWS_SHARE

    for seed in (int(x) for x in args.seeds.split(",")):
        program = prefill_cut.Program(cfg, seed, "", None)
        frames = harness.make_check_frames(cfg["detector"], min(8, n), seed)
        batch = harness.fill_batch(frames, n)
        line = {"seed": seed, "batch": n}
        frame = batch[n - 1:]
        want, stated = (np.asarray(program.reference_hidden(frame, c)[at])
                        for c in (jnp.float32, jnp.bfloat16))
        hidden, own_logits = program.hidden(batch)
        own_logits = np.asarray(own_logits)
        rows = {"program": np.asarray(hidden[last], np.float32),
                "float8": np.asarray(program.reference_hidden(frame, jnp.float8_e4m3fn)[at])}
        for name, fault in faults.items():
            rows[name] = np.asarray(program.reference_hidden(frame, jnp.float32, **fault)[at])
            print(f"[controls] seed {seed}: {name} read", file=sys.stderr, flush=True)
        # `served`: the timed step's logits against the all-rows program's, its limit the check's
        want_logits = program.reference_logits(want[-1:], jnp.float32)
        yard = harness.relative_rms(program.reference_logits(stated[-1:], jnp.bfloat16), want_logits)
        served = {"program": np.asarray(program._serve(jax.device_put(batch))[0])}
        moved = {"program": np.asarray(program.hidden(np.roll(batch, 1, axis=0))[0][moved_to],
                                       np.float32)}
        for name in planted:
            restore = plant(decoder, name)
            jax.clear_caches()
            try:
                if name == "no_reset":
                    rows[name] = np.asarray(program.hidden(batch)[0][last], np.float32)
                    moved[name] = np.asarray(
                        program.hidden(np.roll(batch, 1, axis=0))[0][moved_to], np.float32)
                else:  # the step compiled anew with the fault in it
                    step = jax.jit(lambda p, c, f, i: decoder.frame_step(
                        p, c, f, i, cfg=program.dcfg, threshold=float(cfg["calib_threshold"])))
                    served[name] = np.asarray(step(program.params, program.calib_d,
                                                   jax.device_put(batch), program.prompt_ids)[0])
            finally:
                restore()
                jax.clear_caches()
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
            if name in moved:  # the check's `isolated`: the same program, the sequence moved
                first = slice(0, len(moved_to))
                apart = harness.relative_rms(moved[name], got[first])
                limit = prefill_batched.rows_verdict(
                    got[first], want[first], stated[first])["yardstick_relative_rms_level"]
                line[name]["isolated"] = {"relative_rms_to_itself_moved": apart, "limit": limit,
                                          "ok": bool(apart <= limit)}
            line[name]["ok"] = all(v["ok"] for v in line[name].values())  # first_rows decides too
        for name, logits in served.items():  # the cut's faults show HERE alone
            apart = harness.relative_rms(logits, own_logits)
            verdict = {"served": {"relative_rms_to_own_program": apart,
                                  "yardsticks": apart / max(yard, 1e-30),
                                  "limit": harness.PRECISION_FACTOR * yard,
                                  "ok": bool(apart <= harness.PRECISION_FACTOR * yard)}}
            verdict["ok"] = verdict["served"]["ok"]
            line[name] = {**line.get(name, {}), **verdict,
                          "ok": verdict["ok"] and line.get(name, {}).get("ok", True)}
        # the head's fault, on the program's own last hidden row
        own = rows["program"][-1:]
        head = [program.reference_logits(own, c) for c in (jnp.float32, jnp.bfloat16)]
        v = harness.precision_verdict(program.reference_logits(own, jnp.float8_e4m3fn), *head)
        line["float8_head"] = {"head": {
            "yardsticks": v["logits_relative_rms"] / max(v["yardstick_relative_rms"], 1e-30),
            "ok": v["ok"]}, "ok": v["ok"]}
        line["caught"] = {name: not v["ok"] for name, v in line.items()
                          if isinstance(v, dict) and "ok" in v and name != "program"}
        print(json.dumps(line), flush=True)
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(line) + "\n")
        del program  # 7.7 GB of weights: the next seed's do not fit beside them and the reference
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
