"""A LOOPED decoder (``models/decoder.py`` reading Ouro's keys: one stack of
sandwich-normed layers run ``total_ut_steps`` times with the SAME weights,
the final norm and an exit gate after every pass) against the benchmark's
plain reference (``benchmark/reference/ouro_decoder.py``: the passes written
out) at small sizes on the CPU; the new cell's configuration file,
adapter, counters and counts."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_kit
from benchmark.reference import ouro_decoder as ref
from decoder_kit import Kit, apart, checked, embedded, inputs, rehearse
from psana_ray_tpu.models import decoder
from test_manifest_entries import BENCH

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmark", "configs")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME, CELL = "ouro_2p6b_prefill_epix10k2m", "ouro_epix_saturated"
# the controls' faults (benchmark/tests/ouro_controls.py)
FAULTS = {"three_passes": {"passes": 3}, "weights_not_shared": {"unshared_pass": 1},
          "no_norm_between": {"norm_between": False}, "no_sandwich": {"sandwich": False},
          "gate_before_norm": {"gate_before_norm": True}}
# the decoder cells the benchmark had before this one
OTHERS = ("keye_vl2_prefill_epix10k2m", "lfm2_8b_a1b_prefill_epix10k2m", "kimi_k2_prefill_epix10k2m",
          "deepseek_v32_prefill_epix10k2m", "ling3_flash_prefill_epix10k2m",
          "laguna_s21_prefill_epix10k2m", "granite4_h_micro_prefill_epix10k2m")


def mapping(**over):
    """Ouro's Hugging Face keys at a small size: 3 layers of 4 heads of 16, 4 passes."""
    m = dict(
        model_type="ouro", hidden_size=64, num_hidden_layers=3, layer_types=["full_attention"] * 3,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16, vocab_size=256, rms_norm_eps=1e-6,
        rope_theta=1000000, rope_scaling=None, sliding_window=None, use_sliding_window=False,
        intermediate_size=96, tie_word_embeddings=False, total_ut_steps=4, early_exit_threshold=1,
        patch=8,
    )
    m.update(over)
    return m


def loud(params, by=5.0):
    """The same tree with its 0.02-matrices (and the gate's vector) scaled up,
    so that every part moves the output by more than a rounding."""
    params = decoder_kit.loud(params, by)
    return {**params, "exit_gate": {**params["exit_gate"], "w": params["exit_gate"]["w"] * by}}


def reference_of(params, patches, ids, sizes):
    x, p = zip(*(ref.hidden(params, frame, ids, sizes, block=16) for frame in patches))
    x = jnp.concatenate(x)
    return x, ref.logits_of(params, x, sizes), jnp.concatenate(p, axis=1)


# the program's last-pass rows, logits, statistics and exit distribution at every position of the
# batch; 64 tokens in 32 x 32 tiles
trunk_of = functools.partial(Kit.trunk_of, exits=True)
KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=32, causal_kv_tile=32), loud=loud, trunk_of=trunk_of,
          reference_of=reference_of)
small = KIT.small


def _near(a, b, tol):
    scale = float(jnp.sqrt(jnp.mean(jnp.asarray(b, jnp.float32) ** 2)))
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               atol=tol * scale, rtol=0)


def _calls(jaxpr, name):
    """The call sites of the jitted function ``name`` among ``jaxpr``'s own equations."""
    return [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit") and e.params["name"] == name]


def _file(name=NAME):
    with open(os.path.join(CONFIGS, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------

def test_the_looped_trunk_matches_the_reference_at_all_positions_of_a_batch_of_two():
    cfg = small(mapping())
    x, logits, stats, p = KIT.trunk(3, batch=2)
    want_x, want_logits, want_p = KIT.reference(3, batch=2)
    _near(x, want_x, 2e-4)
    _near(logits, want_logits, 2e-4)
    _near(p, want_p, 2e-4)
    assert p.shape == (4, 128)
    # fifteen statistics: every group the step has not reads 0, the loop's two come last
    names = (decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS + decoder.LINEAR_STATS
             + decoder.AHEAD_STATS + decoder.LOOP_STATS)
    assert len(stats) == len(names) == 15 and cfg.layer_stats == 4
    got = dict(zip(names, (float(v) for v in stats)))
    assert got["loop_passes_total"] == 4
    assert got["attn_tiles_causal_total"] == got["attn_tiles_live_total"] == 4 * 3 * 2  # passes x layers x frames
    assert got["decoder_tokens_total"] == 128 and got["decoder_sequences_total"] == 2  # once a step
    assert all(got[n] == 0 for n in decoder.SHARE_STATS + decoder.PAIR_STATS + decoder.LINEAR_STATS
               + decoder.AHEAD_STATS)
    # the pass the gate would have left at, in expectation, summed over the two frames' last tokens
    last = np.asarray(want_p)[:, [63, 127]]
    np.testing.assert_allclose(got["exit_pass_sum"], float(np.sum(np.arange(1, 5) @ last)), rtol=1e-4)
    assert 2.0 < got["exit_pass_sum"] < 8.0


def test_the_passes_are_the_same_layers_applied_again_with_the_final_norm_between():
    """The loop in the program against a Python loop over the one-pass trunk:
    the same weights four times, ``rms_f`` at the end of every pass."""
    cfg = small(mapping())
    once = dataclasses.replace(cfg, passes=1)
    params = KIT.params(4)
    patches, ids = inputs(4, batch=2)
    looped = KIT.trunk(4, batch=2)[0]
    with jax.default_matmul_precision("highest"):
        def by_hand(q):
            x = embedded(q, patches, ids)
            for _ in range(4):
                x, _ = decoder.trunk(q, x, np.arange(64), once, 2)
                x = decoder.rms_norm(x, q["norm"], cfg.rms_eps)
            return x

        want = jax.jit(by_hand)(params)
    _near(looped, want, 1e-6)


def test_the_passes_are_a_loop_in_the_program_and_not_its_layers_laid_flat():
    cfg = small(mapping())
    params = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))
    x = jax.ShapeDtypeStruct((128, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, x: decoder.trunk(q, x, np.arange(64), cfg, 2))(params, x).jaxpr
    (loop,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert loop.params["length"] == 4 and not _calls(jaxpr, "masked_gqa_attention")
    body = loop.params["jaxpr"].jaxpr
    assert len(_calls(body, "masked_gqa_attention")) == 3  # L call sites in the body, not R x L
    assert len(_calls(body, "_pass_end")) == 1
    # the layers' parts are jitted by NAME: the three layers call ONE traced function each
    for part in ("_projections", "masked_gqa_attention", "_onto_normed", "_mlp_onto_normed"):
        calls = _calls(body, part)
        assert len(calls) == 3 and len({id(e.params["jaxpr"]) for e in calls}) == 1, part


def test_one_pass_is_a_plain_sandwich_decoder_and_holds_no_loop():
    m = mapping(total_ut_steps=1)
    cfg = small(m)
    assert (cfg.passes, cfg.sandwich, cfg.exit_gate) == (1, True, True)
    params = KIT.params(6, over=dict(total_ut_steps=1))
    patches, ids = inputs(6)
    jaxpr = jax.make_jaxpr(lambda q: decoder.trunk(q, embedded(q, patches, ids), np.arange(64),
                                                   cfg, 1))(params).jaxpr
    assert not [e for e in jaxpr.eqns if e.primitive.name in ("scan", "while")]
    assert len(_calls(jaxpr, "masked_gqa_attention")) == 3
    with jax.default_matmul_precision("highest"):
        x, stats = jax.jit(lambda q: decoder.trunk(q, embedded(q, patches, ids), np.arange(64), cfg, 1))(
            params)
        logits = decoder.logits_of(decoder.head_params(params), x, cfg)  # norms here, once
        want_x, want_logits, want_p = reference_of(params, patches, ids, ref.sizes(m))
    assert len(stats) == 6  # no loop, no group of its own
    _near(decoder.rms_norm(x, params["norm"], cfg.rms_eps), want_x, 2e-4)
    _near(logits, want_logits, 2e-4)
    np.testing.assert_array_equal(np.asarray(want_p), 1.0)  # one pass takes everybody


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_with_a_control_s_fault_in_it_is_another_model(fault):
    """Each of the controls' faults moves the reference's own output (the last
    pass's rows, or for the gate's reading the exit distribution alone) by far
    more than the program lies from it."""
    x, _, _, p = KIT.trunk(5)  # made once for the five cases, as the clean reference
    want_x, _, want_p = KIT.reference(5)
    other_x, _, other_p = KIT.reference(5, **FAULTS[fault])
    if fault == "three_passes":  # a pass short: no fourth row
        other_p = jnp.concatenate([other_p, jnp.zeros_like(other_p[:1])])
    assert apart(x, want_x) < 1e-5 and apart(p, want_p) < 1e-5
    if fault == "gate_before_norm":  # the rows are what they were: only the exits see it
        assert apart(other_x, want_x) == 0 and apart(other_p, want_p) > 0.05
    else:
        assert apart(other_x, want_x) > 0.05, apart(other_x, want_x)


def test_the_exit_distribution_sums_to_one_and_a_sure_gate_leaves_at_the_first_pass():
    cfg = small(mapping())
    params = KIT.params(7)
    patches, ids = inputs(7, batch=2)
    p = np.asarray(KIT.trunk(7, batch=2, under=())[3])
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    assert 0.02 < p.min() and p.max() < 0.98  # seen, not saturated
    sure = {**params, "exit_gate": {"w": params["exit_gate"]["w"], "b": jnp.float32(40.0)}}
    _, _, stats, p = trunk_of(sure, patches, ids, cfg)
    np.testing.assert_allclose(np.asarray(p), np.array([1.0, 0, 0, 0])[:, None] * np.ones((1, 128)),
                               atol=1e-6)
    assert float(stats[-1]) == pytest.approx(2.0)  # both frames at pass 1
    lam = jnp.asarray([[0.5, 0.25], [0.5, 0.25], [0.5, 0.25], [0.9, 0.9]])
    got = decoder.exit_distribution(jnp.log(lam / (1 - lam)))
    np.testing.assert_allclose(np.asarray(got), [[0.5, 0.25], [0.25, 0.1875], [0.125, 0.140625],
                                                 [0.125, 0.421875]], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.exit_distribution(list(lam))), np.asarray(got), rtol=1e-6)


def test_a_sequence_of_the_batch_does_not_move_when_its_neighbour_changes():
    cfg = small(mapping())
    params = loud(decoder.init_params(cfg, jax.random.key(8)))  # bf16, as served
    patches, ids = inputs(8, batch=2)
    other = patches.at[0].set(inputs(9)[0][0])  # frame 0 is another frame
    x, _, _, p = trunk_of(params, patches, ids, cfg)
    moved_x, _, _, moved_p = trunk_of(params, other, ids, cfg)
    np.testing.assert_array_equal(np.asarray(x[64:], np.float32), np.asarray(moved_x[64:], np.float32))
    np.testing.assert_array_equal(np.asarray(p[:, 64:]), np.asarray(moved_p[:, 64:]))
    assert not np.array_equal(np.asarray(x[:64], np.float32), np.asarray(moved_x[:64], np.float32))


def test_an_exit_before_the_last_pass_is_refused_and_not_half_built():
    with pytest.raises(ValueError, match="an exit before the last pass"):
        decoder.DecoderConfig.from_mapping(mapping(early_exit_threshold=0.9))
    assert decoder.DecoderConfig.from_mapping(mapping(early_exit_threshold=1.0)).passes == 4


def test_the_sandwich_is_refused_on_what_it_is_not_built_on():
    cfg = dataclasses.replace(small(mapping()), residual_multiplier=0.5)
    params = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))
    x = jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="sandwich"):
        jax.eval_shape(lambda q, x: decoder.trunk(q, x, np.arange(64), cfg, 1), params, x)


def test_the_loop_s_counters_reach_the_pipeline_s():
    from psana_ray_tpu.utils.metrics import PipelineMetrics

    cfg = small(mapping())
    params = decoder.init_params(cfg, jax.random.key(2))
    patches, ids = inputs(2, batch=2)
    stats = trunk_of(params, patches, ids, cfg)[2]
    metrics = PipelineMetrics()
    for _ in range(3):
        decoder.fold_step_stats(metrics, stats)
    counters = metrics.snapshot()
    assert counters["loop_passes_total"] == 12 and counters["decoder_sequences_total"] == 6
    assert 6.0 < counters["exit_pass_sum"] < 24.0
    assert counters["exit_pass_sum"] == pytest.approx(3 * float(stats[-1]))
    assert counters["expert_rows_ahead_total"] == 0  # the groups before the loop's read 0


# ---------------------------------------------------------------------------
# the spelling, the file, the manifest
# ---------------------------------------------------------------------------

def test_from_mapping_reads_the_published_keys():
    got = decoder.DecoderConfig.from_mapping(_file())
    assert (got.passes, got.sandwich, got.exit_gate, got.qk_norm) == (4, True, True, False)
    assert (got.hidden_size, got.num_layers, got.num_heads, got.num_kv_heads, got.head_dim) == (
        2048, 48, 16, 16, 128)
    assert got.layer_types == ("full_attention",) * 48 and got.intermediate_size == 5632
    assert (got.rope_theta, got.rms_eps, got.vocab_size, got.tie_embedding) == (1e6, 1e-6, 49152, False)
    assert got.rope_dim == 128 and got.rope_yarn is None and got.stream_dtype is None
    assert got.num_experts == 0 and got.layer_stats == 4 and got.patch == 32
    assert all(got.layer_kind(i) == ("full_attention", False) for i in range(48))


def test_the_file_holds_the_catalog_s_numbers_unchanged_and_cuts_nothing():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    cfg = _file()
    assert cfg["source"] == row["source_url"] and len(cfg["source"]) <= 200
    assert {k for k, v in row["config"].items() if cfg.get(k, "absent") != v} == set() == set(
        cfg["reduced"])
    assert "the whole model on one chip: 1 stage, 1 chip a layer, the whole vocabulary" in cfg[
        "deployment"]
    said = " ".join(cfg["assumed"])
    for reading in ("linear patch embedding W_patch [1024, 2048]", "input_layernorm_2",
                    "end of EVERY pass", "sigmoid(h_r w_e + b_e)", "no bias", "no norm on q or k",
                    "[0, 49,152)", "b_e 0", "the STREAM between the layers bf16"):
        assert reading in said, reading
    assert cfg["step_tokens"] == cfg["batch_size"] * cfg["sequence_tokens"] == 4608
    assert cfg["sequence_tokens"] == 16 * 11 * 12 + cfg["prompt_tokens"] == 18 * 128
    assert cfg["transport"] == {"scheme": "shm", "slots": 8} and cfg["vocab_size"] % 128 == 0
    assert cfg["reference"] == {"module": "ouro_decoder", "query_block": 768, "sequences": [0, -1]}
    assert cfg["trace_names"] == {"step": "jit_ouro_step", "calib_kernel": "fused_calibrate",
                                  "attention_kernel": "masked_gqa_attention"}
    # weights, recounted: 2.670 G parameters, 5.34 GB in bf16, held once for four passes
    got = decoder.DecoderConfig.from_mapping(cfg)
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert round(count / 1e9, 3) == 2.670 and round(2 * count / 1e9, 2) == 5.34
    by_layer = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(p)) for p in shapes["layers"]]
    assert set(by_layer) == {4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048}
    assert sorted(shapes["layers"][0]) == ["norm1", "norm1_post", "norm2", "norm2_post", "w_down",
                                           "w_gate", "w_up", "wk", "wo", "wq", "wv"]
    assert shapes["head"].shape == (2048, 49152) and shapes["embed"].shape == (49152, 2048)
    assert shapes["exit_gate"]["w"].shape == (2048,) and shapes["exit_gate"]["b"].shape == ()
    rehearsal = decoder.DecoderConfig.from_mapping({**cfg, **cfg["rehearse"]})
    assert (rehearsal.hidden_size, rehearsal.num_layers, rehearsal.num_heads, rehearsal.head_dim,
            rehearsal.passes) == (64, 3, 4, 16, 4) and cfg["rehearse"]["batch_size"] == 2


@pytest.mark.parametrize("name", OTHERS)
def test_the_other_seven_readers_have_nothing_of_what_this_one_brought(name):
    got = decoder.DecoderConfig.from_mapping(_file(name))
    assert (got.passes, got.sandwich, got.exit_gate) == (1, False, False)
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    assert "exit_gate" not in shapes
    assert not [k for layer in shapes["layers"] for k in layer if k.endswith("_post")]


def test_the_ouro_cell_follows_granite_s_and_reports_the_host_path_as_the_decoders_do():
    cell = BENCH.cell(CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "saturated", NAME)
    config = BENCH.config(NAME)
    assert config["file"] == f"benchmark/configs/{NAME}.json" and len(cell["why"]) <= 200
    assert config["reduced"] == _file()["reduced"] == [] and len(config["why"]) <= 200
    assert "catalog row Ouro-2.6B" in config["why"]


# ---------------------------------------------------------------------------
# the adapter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lacks", ["passes", "sandwich", "exit_gate"])
def test_the_adapter_ends_the_run_where_the_package_lacks_the_mechanism(monkeypatch, lacks):
    from benchmark.programs import prefill_looped

    fields = [f for f in dataclasses.fields(decoder.DecoderConfig) if f.name != lacks]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: fields)
    cfg = _file()
    cfg.update(cfg["rehearse"])
    with pytest.raises(SystemExit, match=lacks):
        prefill_looped.Program(cfg, 1, "", None)


def _verdict(**over):
    parts = {name: {"rows_over_limit": 0.0, "ok": True}
             for name in ("first_rows.0", "patch_rows.0", "prompt_rows.0")}
    verdict = {**parts, "isolated.0": {"ok": True}, "served": {"ok": True},
               "head": {"logits_relative_rms": 1e-7, "ok": False}, "ok": False}
    for name, v in over.items():
        verdict[name.replace("_0", ".0")].update(v)
    return verdict


@pytest.mark.parametrize("part,fault,ok", [
    ("head", {}, True),  # the parents' yardstick of 0 refused it: the stated limit holds
    ("head", {"logits_relative_rms": 1e-3}, False),  # a bf16 accumulation
    ("first_rows_0", {"rows_over_limit": 0.5}, False),  # record-only in prefill_batched, decides here
    ("prompt_rows_0", {"rows_over_limit": 0.5}, False),
    ("isolated_0", {"ok": False}, False),
    ("exits", {}, False),  # an exit distribution apart from the reference's
])
def test_the_adapter_s_own_limits_decide(part, fault, ok, monkeypatch):
    from benchmark import harness
    from benchmark.programs import prefill_batched, prefill_looped

    cfg = _file()
    cfg.update(cfg["rehearse"])
    cfg["reference"] = {**cfg["reference"], "sequences": [0]}
    verdict = _verdict(**({part: fault} if part != "exits" else {}))
    monkeypatch.setattr(prefill_batched.Program, "check", lambda self, frames: verdict)
    program = object.__new__(prefill_looped.Program)
    program.cfg, program.frames_per_batch, program._kept = cfg, 2, {}
    want = np.tile(np.array([0.5, 0.25, 0.125, 0.125], np.float32)[:, None], (1, 24))
    got = np.tile(want, (1, 2)) * (0.9 if part == "exits" else 1.0)
    program._program = lambda batch: (None, None, got)
    program._reference = lambda frame, compute: (
        None, want * (1.0 if np.dtype(compute) == np.float32 else 1.001))
    out = prefill_looped.Program.check(program, np.zeros((2, 2, 16, 128), np.uint16))
    assert out["ok"] is ok and out["head"]["limit"] == prefill_looped.HEAD_LIMIT
    assert out["exits.0"]["ok"] is (part != "exits")
    assert out["exits.0"]["exit_pass_mean"] == pytest.approx(1.875)
    assert out["exits.0"]["limit"] == pytest.approx(
        prefill_looped.EXITS_FACTOR * out["exits.0"]["yardstick_relative_rms"])
    assert harness.PRECISION_FACTOR < prefill_looped.EXITS_FACTOR < 19.3  # the controls' least
    assert 0 < prefill_looped.TOSSED_ROWS_SHARE < prefill_batched.TOSSED_ROWS_SHARE
    assert prefill_looped.STEP_NAME == "ouro_step"


def test_the_adapter_draws_a_layer_at_a_time_and_the_tree_is_init_params_own():
    from benchmark.programs import prefill_looped

    cfg = _file()
    cfg.update(cfg["rehearse"])
    program = prefill_looped.Program(cfg, 3000000007, "", None)
    want = jax.eval_shape(lambda k: decoder.init_params(program.dcfg, k), jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), program.params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.leaves(got) == jax.tree.leaves(want)
    assert program.dcfg.num_layers == len(program.params["layers"]) == 3 and program.cfg is cfg
    first, second = (np.asarray(p["wq"], np.float32) for p in program.params["layers"][:2])
    assert not np.array_equal(first, second) and 0.015 < first.std() < 0.025  # each its own draw


def test_the_cell_s_rehearsal_runs_the_served_path_and_is_correct():
    line, done = rehearse(CELL, seed=3000000007)
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0 and line["cell"] == CELL
    for name in ("ring_depth.hit", "device_wait_ms.hit", "startup_trace_s", "startup_compile_s"):
        assert name in line["would_report"], name
    # the check ran both sequences of the rehearsal's batch: rows, a sequence moved, the exits
    verdict = checked(done)
    for i in (0, 1):
        assert verdict[f"isolated.{i}"]["ok"] and verdict[f"exits.{i}"]["ok"]
        assert verdict[f"first_rows.{i}"]["rows_over_share_limit"] == 0.1
        assert 1.5 < verdict[f"exits.{i}"]["exit_pass_mean"] < 2.5  # p about 1/2, 1/4, 1/8, 1/8
    assert verdict["head"]["ok"] and verdict["served"]["sequences"] == 2


def test_ouro_roofline_counts_a_step_as_passes_times_layers():
    from benchmark.roofline import lfm2, ouro

    cfg = _file()
    step = ouro.step(2, 2304, 2048, 48, 4, 5632, 16, 16, 128, 49152, 192, 32)["flops"]
    assert round(step / 1e12, 1) == 99.3
    layer = 2 * 51380224 * 4608 + lfm2.causal_attention(2, 2304, 2048, 16, 16)["flops"]
    assert step == 192 * layer + 4 * 2 * 4608 * 2048 + 2 * 2 * 2048 * 49152 + 2 * 2 * 2112 * 1024 * 2048
    assert cfg["total_ut_steps"] * cfg["num_hidden_layers"] == 192
    assert ouro.step(2, 2304, 2048, 48, 4, 5632, 16, 16, 128, 49152, 192, 32)["bytes"] == 0.0


# -- the benchmark's readers at a looped step: the roofline count from the cell's keys, and a scope
# -- inside the loop's body (here and not in benchmark/tests/test_readers.py: that file is the
# -- accepted benchmark's, and tier-1 collects tests/ alone)

def test_ouro_step_counts_passes_times_layers_at_the_cell_s_keys():
    from benchmark.roofline import lfm2, ouro

    cfg = _file()
    keys = {"batch": "batch_size", "tokens": "sequence_tokens", "hidden": "hidden_size",
            "layers": "num_hidden_layers", "passes": "total_ut_steps", "dense_width": "intermediate_size",
            "heads": "num_attention_heads", "kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "vocab": "vocab_size", "prompt": "prompt_tokens", "patch": "patch"}
    step = ouro.step(**{k: cfg[path] for k, path in keys.items()})["flops"]
    assert step / 1e12 == pytest.approx(99.3, abs=0.1)
    # by hand: 192 layer applications of 2 x 51.38 M parameters a token and 8,192 FLOPs a causal
    # pair, the gate a pass, the head on two rows, the patch embedding
    pairs = 2304 * 2305 // 2
    layer = 2 * (4 * 2048 ** 2 + 3 * 2048 * 5632) * 4608 + 2 * pairs * 4 * 128 * 16
    assert layer == 2 * 51380224 * 4608 + lfm2.causal_attention(2, 2304, 2048, 16, 16)["flops"]
    assert step == 192 * layer + 4 * 2 * 4608 * 2048 + 2 * 2 * 2048 * 49152 + 2 * 2 * 2112 * 1024 * 2048
    assert 0.075 < 192 * 2 * pairs * 8192 / step < 0.085  # attention: 8% of the FLOPs at this length
    once = ouro.step(**{**{k: cfg[path] for k, path in keys.items()}, "passes": 1})["flops"]
    assert step / once == pytest.approx(4.0, rel=1e-3)


def test_a_scope_inside_a_loop_s_body_is_found_and_its_runs_summed():
    """A step whose passes are a ``while``: the body's instruction runs four
    times a run of the module, under a name stack that holds the scope
    between ``while/body`` and the jitted function. ``in_scope`` reads the
    stack; ``scope_ms`` sums the instruction's events inside the run."""
    from benchmark import check_scope as enc
    from benchmark import trace_reduce as tr
    from benchmark.readers import trace_scope_time as ts

    body = enc.ld(1, b"region_1.19") + enc.ld(2, enc.instruction(
        "fusion.7", "jit(ouro_step)/while/body/closed_call/pass_end/jit(_pass_end)/mul"))
    body += enc.ld(2, enc.instruction(
        "masked_gqa_attention.3", "jit(ouro_step)/while/body/closed_call/sparse_attn/jit(masked_gqa_attention)/pallas_call"))
    main = enc.ld(1, b"main") + enc.ld(2, enc.instruction("fusion.1", "jit(ouro_step)/head/jit(_lambda)/dot"))
    hlo = enc.ld(1, enc.ld(1, b"jit_ouro_step") + enc.ld(3, main) + enc.ld(3, body))
    plane = enc.vi(1, 3) + enc.ld(2, b"/host:metadata") + enc.ld(
        4, enc.vi(1, 9) + enc.ld(2, enc.vi(1, 9) + enc.ld(2, b"jit_ouro_step(9)") + enc.ld(5, enc.vi(1, 4) + enc.ld(6, hlo))))
    scopes = {}
    for proto in ts.hlo_protos(enc.ld(1, plane)):
        scopes.update(ts.instruction_scopes(proto))
    assert ts.in_scope(scopes["fusion.7"], "pass_end") and not ts.in_scope(scopes["fusion.7"], "head")
    ops = []
    for r in range(4):  # a pass: the kernel 100 ns, the pass's end 10
        ops += [("%masked_gqa_attention.3 = bf16[8]{0} custom-call(x)", 100.0 + 200 * r, 100.0),
                ("%fusion.7 = bf16[8]{0} fusion(x)", 250.0 + 200 * r, 10.0)]
    ops.append(("%fusion.1 = f32[8]{0} fusion(x)", 900.0, 20.0))
    trace = tr.Trace(device={0: {"XLA Modules": [("jit_ouro_step(9)", 100.0, 850.0)], "XLA Ops": ops}},
                     host={})
    assert ts.scope_ms(trace, scopes, "pass_end", "^%?jit_ouro_step", 0.0, 1000.0) == pytest.approx(40e-6)
    assert ts.scope_ms(trace, scopes, "sparse_attn", "^%?jit_ouro_step", 0.0, 1000.0) == pytest.approx(400e-6)
    assert ts.scope_ms(trace, scopes, "head", "^%?jit_ouro_step", 0.0, 1000.0) == pytest.approx(20e-6)
    assert ts.scope_ms(trace, scopes, "mlp", "^%?jit_ouro_step", 0.0, 1000.0) is None
