"""Manifold-constrained hyper-connections around DeepSeek-V3's block
(``models/decoder.py`` reading Xing4.0-29B-A4B's keys; the two mixes:
``ops/hyper_connection.py``) against the benchmark's plain reference
(``benchmark/reference/xing4_decoder.py``) at small sizes on the CPU: the
trunk, a branch's mixing numbers, the kernels at a row count no tile divides,
the faults the controls plant and every ``assumed`` point's other reading, the
two counters, the spelling's refusals and the cell's counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing4_decoder as ref
from decoder_kit import HIGHEST, PROMPT, Kit, apart, checked, inputs, loud, rehearse, streamed
from psana_ray_tpu.models import decoder
from psana_ray_tpu.ops import hyper_connection as hc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "xing4_29b_a4b_prefill_epix10k2m.json")
CELL = "xing4_epix_saturated"
YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def mapping(**over):
    """Xing4.0's Hugging Face keys at a small size: 16 routed experts, all held."""
    m = dict(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        vocab_size=256, rms_norm_eps=1e-6, rope_theta=10000, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1,
        n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=32,
        intermediate_size=96, norm_topk_prob=True, scoring_func="sigmoid", topk_method="noaux_tc",
        routed_scaling_factor=2.0, tie_word_embeddings=False, rope_scaling=dict(YARN), patch=8,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30, num_nextn_predict_layers=1,
    )
    m.update(over)
    return m


def _with_gains(params):
    """A gain on each branch's wide norm, for the reading that has one (the
    program draws none: the reference alone reads it)."""
    rng = np.random.default_rng(11)
    return {**params, "layers": [{**p, **{f"{which}_gain": jnp.asarray(
        rng.uniform(0.5, 1.5, p[which + "_phi"].shape[0]), jnp.float32) for which in ("hc1", "hc2")}}
        for p in params["layers"]]}


# loud weights (decoder_kit.loud: phi with the other matrices, so x~ phi has a deviation of 1.6 and
# the Sinkhorn has NOT converged at twenty steps: the twentieth shows); tiles that cut 64 tokens
KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=16, causal_kv_tile=32),
          loud=lambda params: _with_gains(loud(params)))
small = KIT.small


# ---------------------------------------------------------------------------
# the trunk against the reference, float32, all positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [1, 2])
def test_trunk_under_hyper_connections_matches_reference_at_all_positions(batch):
    x, got, stats = KIT.trunk(3, batch, jit=False)
    want_x, want = KIT.reference(3, batch)
    assert got.shape == (batch * 64, 256) and x.shape == (batch * 64, 64)  # the streams SUMMED
    for a, b in ((x, want_x), (got, want)):
        scale = float(jnp.sqrt(jnp.mean(b ** 2)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4 * scale, rtol=0)
    assert len(stats) == 22 and not any(float(v) for v in stats[6:20])  # every group the step has not
    assert float(stats[1]) == 2 * batch * 64 * 4 / 16  # two expert layers' even share
    assert [float(v) for v in stats[4:6]] == [batch * 64.0, batch]
    assert float(stats[20]) == 2 * 3 * batch * 64  # (branch, token) pairs: two branches a layer
    assert 0 < float(stats[21]) < 0.05  # the largest defect: loud weights, twenty steps


def test_a_sequence_does_not_read_its_neighbour_in_the_batch():
    """``isolated``: sequence 0 of a batch of two, with its neighbour after it
    or before it, to the bit (the mixes are a token's own)."""
    cfg, params = small(mapping()), KIT.params(3)
    patches, ids = inputs(3, 2)
    with jax.default_matmul_precision(HIGHEST):
        there = KIT.trunk_of(params, patches, ids, cfg, jit=False)[0]
        moved = KIT.trunk_of(params, patches[::-1], ids, cfg, jit=False)[0]
    assert float(jnp.abs(there[:64] - moved[64:]).max()) == 0.0
    assert float(jnp.abs(there[64:] - moved[:64]).max()) == 0.0


# ---------------------------------------------------------------------------
# a branch's mixing numbers, a layer at a time; the constraint; the kernels
# ---------------------------------------------------------------------------

def _stream(seed, rows, width=64, n=4, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((rows, n * width)), dtype),
            jnp.asarray(rng.standard_normal((rows, width)), dtype))


def _branch(p, which, cfg, x, **kw):
    return hc.hyper_in(x, p[which + "_phi"], p[which + "_alpha"], p[which + "_b"],
                       streams=cfg.hc_mult, iters=cfg.hc_iters, eps=cfg.hc_eps, norm_eps=cfg.rms_eps,
                       clamp=cfg.hc_clamp, **kw)


@pytest.mark.parametrize("layer,which", [(0, "hc1"), (0, "hc2"), (2, "hc1"), (2, "hc2")])
def test_a_branch_s_mixing_numbers_are_the_reference_s(layer, which):
    cfg, p = small(mapping()), KIT.params(5)["layers"][layer]
    x, _ = _stream(layer, 64)
    m = ref.sizes(mapping())
    with jax.default_matmul_precision(HIGHEST):
        u, mix = _branch(p, which, cfg, x)
        want = ref.mixing(p, x.reshape(64, 4, 64), which, m, jnp.float32)
        want_u = ref.mix_in(x.reshape(64, 4, 64), want[0], m)
    for got, b in zip(hc.mixing_numbers(mix, 4), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(b), atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(u), np.asarray(want_u), atol=1e-5, rtol=1e-5)


def test_the_projected_matrix_is_doubly_stochastic_and_conserves_the_streams_mean():
    """Under the weights AS DRAWN (normal(0, 0.02): what a step runs) every
    column of ``H_res`` sums to 1 within 1e-5 (the last division is the
    columns'), every row within the step's reported defect, which is small;
    and a branch whose output is 0 leaves the streams' mean where it was."""
    cfg = small(mapping())
    p = decoder.init_params(cfg, jax.random.key(9), jnp.float32)["layers"][1]
    x, y = _stream(9, 200)
    with jax.default_matmul_precision(HIGHEST):
        _, mix = _branch(p, "hc1", cfg, x)
        out = hc.hyper_out(x, jnp.zeros_like(y), mix, streams=4)
    res = np.asarray(hc.mixing_numbers(mix, 4)[2], np.float64)
    assert np.abs(res.sum(axis=1) - 1).max() < 1e-5  # columns
    by_row = np.abs(res.sum(axis=2) - 1).max()
    defect = float(hc.sum_defect(mix, 4))
    assert abs(defect - max(by_row, np.abs(res.sum(axis=1) - 1).max())) < 1e-6 and defect < 1e-3
    assert np.median(np.abs(res.sum(axis=2) - 1)) < 1e-5  # a token's rows: converged but for a few
    assert (res > 0).all()
    mean = lambda a: np.asarray(a, np.float64).reshape(200, 4, 64).mean(axis=1)  # noqa: E731
    np.testing.assert_allclose(mean(out), mean(x), atol=1e-5)


@pytest.mark.parametrize("rows,block", [(200, 128), (72, 128), (136, 64)])
def test_the_two_kernels_are_the_plain_form_at_a_row_count_no_tile_divides(rows, block):
    cfg, p = small(mapping()), KIT.params(7)["layers"][1]
    x, y = _stream(rows, rows)
    m = ref.sizes(mapping())
    with jax.default_matmul_precision(HIGHEST):
        u, mix = _branch(p, "hc2", cfg, x, block_rows=block)
        got = hc.hyper_out(x, y, mix, streams=4, block_rows=block)
        xs = x.reshape(rows, 4, 64)
        pre, post, res = ref.mixing(p, xs, "hc2", m, jnp.float32)
        want = ref.mix_out(xs, y, post, res).reshape(rows, -1)
    assert mix.shape == (rows, 128) and bool(jnp.isfinite(mix).all())
    np.testing.assert_allclose(np.asarray(u), np.asarray(ref.mix_in(xs, pre, m)), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_a_bf16_stream_is_mixed_in_float32_and_rounded_once():
    cfg, p = small(mapping()), KIT.params(7)["layers"][1]
    x, y = _stream(4, 64, dtype=jnp.bfloat16)
    with jax.default_matmul_precision(HIGHEST):
        u, mix = _branch(p, "hc1", cfg, x)
        got = hc.hyper_out(x, y, mix, streams=4)
        pre, post, res = hc.mixing_numbers(mix, 4)
        xs = x.astype(jnp.float32).reshape(64, 4, 64)
        want = ref.mix_out(xs, y.astype(jnp.float32), post, res).reshape(64, -1)
    assert u.dtype == got.dtype == jnp.bfloat16 and mix.dtype == jnp.float32
    # rounded ONCE: the float32 sums' last places differ with the order of a sum (an einsum's is its own),
    # so a bf16 rounding flips now and then; nothing is two roundings off
    got, want = np.asarray(got, np.float32), np.asarray(want.astype(jnp.bfloat16), np.float32)
    assert np.mean(got == want) > 0.99
    np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=2.0 ** -9)


# ---------------------------------------------------------------------------
# what a control plants, and each assumed point's other reading, is another trunk
# ---------------------------------------------------------------------------

FAULTS = {
    "plain_residual_a_stream": {"res": "identity"}, "exp_alone": {"iters": 0},
    "one_iteration": {"iters": 1},
    "columns_never_normed": {"sinkhorn": "rows"}, "post_without_its_2": {"post_two": False},
    "pre_without_its_sigmoid": {"pre_sigmoid": False}, "no_wide_norm": {"wide_norm": False},
    "alpha_0": {"alpha_scale": 0.0}, "branch_fed_stream_0": {"reads": "stream0"},
    "exit_takes_stream_0": {"exit": "stream0"}, "attention_s_numbers_for_the_feed_forward": {"ff_mix": "attention"},
    # kimi's own
    "unturned_key": {"turn_key": False}, "no_mscale": {"mscale": False},
    "softmax_router": {"scoring": "softmax"}, "no_selection_bias": {"select_bias": False},
    # the other readings of the configuration's `assumed`
    "columns_first": {"order": "columns_first"}, "a_gain_on_the_wide_norm": {"wide_gain": True},
    "alpha_on_the_sum": {"alpha_on": "sum"}, "exit_averages": {"exit": "mean"},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_with_a_fault_or_another_reading_in_it_is_another_trunk(fault):
    x, logits = KIT.trunk(5, jit=False)[:2]  # made once for the cases
    want_x, want = KIT.reference(5, **FAULTS[fault])
    if fault == "exit_averages":  # the final norm takes a quarter back: the ROWS differ, by 4
        assert apart(x, want_x) > 0.5 and apart(logits, want) < 1e-4
    elif fault == "columns_first":
        # both orders end where the Sinkhorn converges to (its limit is ONE matrix, whichever division
        # comes first): they differ by what twenty steps leave unconverged, which loud weights keep visible
        assert 1e-5 < apart(logits, want) < 1e-2, apart(logits, want)
    else:
        assert apart(logits, want) > 1e-2, apart(logits, want)


def test_the_twentieth_iteration_is_run_and_the_twenty_first_is_not():
    """No logit shows one Sinkhorn step of twenty (3e-6 under loud weights): the
    mixing numbers do, where a loud bias keeps the matrix from converging."""
    cfg, p = small(mapping()), dict(KIT.params(5)["layers"][1])
    p["hc1_b"] = p["hc1_b"] * 4.0
    x, _ = _stream(3, 64)
    with jax.default_matmul_precision(HIGHEST):
        got = hc.mixing_numbers(_branch(p, "hc1", cfg, x)[1], 4)[2]
        by = {n: ref.mixing(p, x.reshape(64, 4, 64), "hc1", ref.sizes(mapping(), iters=n), jnp.float32)[2]
              for n in (19, 20, 21)}
    assert float(jnp.abs(got - by[20]).max()) < 2e-6
    assert min(float(jnp.abs(got - by[n]).max()) for n in (19, 21)) > 1e-4


def test_hc_eps_in_the_norm_or_under_the_sums_differs_by_a_rounding():
    """The one ``assumed`` point whose other reading no weights can show: both
    epsilons are 1e-6."""
    logits = KIT.trunk(5, jit=False)[1]
    assert apart(logits, KIT.reference(5, eps_in="norm")[1]) < 1e-4


def test_the_clamp_is_the_file_s_and_binds_where_the_logits_pass_it():
    over = {"mhc_h_res_clamp_min": -0.5, "mhc_h_res_clamp_max": 0.5}
    assert small(mapping(**over)).hc_clamp == (-0.5, 0.5)
    logits = KIT.trunk(5, over=over, jit=False)[1]
    assert apart(logits, KIT.reference(5, over=over)[1]) < 1e-4
    assert apart(logits, KIT.reference(5, over=over, clamp=False)[1]) > 1e-2
    assert apart(logits, KIT.trunk(5, jit=False)[1]) > 1e-2  # (at +-30 it does not bind: another trunk)


# ---------------------------------------------------------------------------
# the spelling
# ---------------------------------------------------------------------------

def test_from_mapping_reads_the_thirteenth_spelling_from_the_cell_s_file():
    with open(CONFIG) as f:
        file = json.load(f)
    cfg = decoder.DecoderConfig.from_mapping(file)
    assert (cfg.hc_mult, cfg.hc_iters, cfg.hc_eps, cfg.hc_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank) == (3584, 32, 768, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.expert_width, cfg.experts_held) == (64, 4, 1024, (0, 64))
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.intermediate_size, cfg.vocab_size) == (8, 2, 9216, 131072)
    assert cfg.routed_scaling_factor == 2.0 and cfg.router_scoring == "sigmoid" and cfg.expert_bias
    assert cfg.rope_yarn.factor == 64 and cfg.rope_yarn.beta_fast == 32 and not cfg.holds_a_share
    assert cfg.stream_dtype is None and cfg.layer_stats == 4
    assert file["reduced"] == ["num_hidden_layers"] and file["published"]["num_hidden_layers"] == 40
    shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))
    p = shapes["layers"][2]
    assert p["hc1_phi"].shape == p["hc2_phi"].shape == (4 * 3584, 24) and p["hc1_phi"].dtype == jnp.bfloat16
    assert p["hc1_alpha"].shape == (3,) and p["hc2_b"].shape == (24,) and p["hc2_b"].dtype == jnp.float32
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 5.66e9 < total < 5.68e9  # ISSUE 78's 5,667 M parameters: 11.33 GB in bf16


@pytest.mark.parametrize("over,said", [
    ({"kv_lora_rank": 0}, "without the latent-attention keys"),
    ({"hc_mult": 1}, "2 to 8 streams"),  # ONE stream is no plain residual: sigmoid(h) x in, 2 sigmoid(h) y on
    ({"hc_mult": 9}, "2 to 8 streams"),
    ({"gated_attention_proj_granularity_type": "head_wise"}, "an output gate"),
    ({"index_n_heads": 4, "index_head_dim": 16, "index_topk": 8}, "an indexer"),
])
def test_from_mapping_refuses_what_the_streams_mixes_are_not_built_around(over, said):
    with pytest.raises(ValueError, match=said):
        decoder.DecoderConfig.from_mapping(mapping(**over))


def test_a_file_without_hc_mult_is_the_plain_block_and_draws_no_mixing_weights():
    m = mapping()
    for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max"):
        m.pop(key)
    cfg = decoder.DecoderConfig.from_mapping(m)
    assert cfg.hc_mult == 0
    p = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))["layers"][0]
    assert not [k for k in p if k.startswith("hc")]


# ---------------------------------------------------------------------------
# the counters, the cell's counts, the rehearsal
# ---------------------------------------------------------------------------

def test_the_two_counters_reach_the_snapshot_the_second_as_a_maximum():
    outs, snap, text = streamed(small(mapping()))
    steps, tokens = 2, 2 * (2 * 2 * 14 + PROMPT)
    assert snap["decoder_tokens_total"] == steps * tokens
    assert snap["hyper_mixes_total"] == steps * 2 * 3 * tokens
    defects = [float(out[1][21]) for out in outs]
    assert snap["hyper_sum_defect_max"] == max(defects) and 0 < max(defects) < 1e-2  # raised, not summed
    for name in decoder.HYPER_STATS:
        assert f'psana_ray_{name}{{source="reader"}}' in text, name


def test_a_step_without_streams_counts_neither():
    from test_decoder_kimi import mapping as kimi_mapping, small as kimi_small

    _, snap, _ = streamed(kimi_small(kimi_mapping()))
    assert not set(decoder.HYPER_STATS) & set(snap)


def test_xing4_roofline_counts_at_the_published_sizes():
    from benchmark.roofline import xing4 as roofline

    with open(CONFIG) as f:
        c = json.load(f)
    both = roofline.hyper_connection(17408, 3584, 4, 20)
    assert both["bytes"] == 17408 * 3584 * 14 * 2 + 2 * 14336 * 24 + 2 * 4 * 17408 * 24
    assert 2.1e-3 < both["bytes"] / 819e9 < 2.2e-3  # ISSUE 78's 2.1 ms a branch, 34 ms a step of sixteen
    a, b = roofline.hyper_in(17408, 3584, 4, 20), roofline.hyper_out(17408, 3584, 4, 20)
    assert both["flops"] == a["flops"] + b["flops"] and a["bytes"] < b["bytes"]
    step = roofline.step(
        batch=c["batch_size"], tokens=c["sequence_tokens"], hidden=c["hidden_size"],
        layers=c["num_hidden_layers"], dense_layers=c["first_k_dense_replace"],
        dense_width=c["intermediate_size"], expert_width=c["moe_intermediate_size"],
        experts=c["n_routed_experts"], per_token=c["num_experts_per_tok"], shared=c["n_shared_experts"],
        heads=c["num_attention_heads"], q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
        nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"], value=c["v_head_dim"],
        vocab=c["vocab_size"], prompt=c["prompt_tokens"], patch=c["patch"], streams=c["hc_mult"],
        iters=c["hc_sinkhorn_iters"])
    assert 38.5e12 < step["flops"] < 40.5e12  # ISSUE 78's 38.9 T, and the mixes' own sums
    assert roofline.held_products(17408, 4, 3584, 1024, 64, 8, 2)["call_sites"] == 18


def test_the_cell_rehearses_correct_on_the_cpu():
    line, done = rehearse(CELL, 1)
    assert line["correct"] and line["failed"] == 0 and checked(done)["isolated.1"]["relative_rms_to_itself_moved"] == 0.0
