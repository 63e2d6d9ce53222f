"""Linear attention by the gated delta rule with a decay per channel
(``ops/delta_rule.py``), latent attention with a full-rank query and a
head-wise output gate, and the trunk that mixes them (``models/decoder.py``
reading Ling-3.0's keys) against the benchmark's plain reference
(``benchmark/reference/ling3_decoder.py``: the recurrence token by token) at
small sizes on the CPU; the shares of the expert layer at 8 groups; the new
cell's counters and counts; and, for every decoder cell at
once, what each configuration's reader has and has not."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_kit
from benchmark.reference import ling3_decoder as ref
from decoder_kit import F32_PRODUCTS, PROMPT, Kit, embedded, inputs, rehearse, share_of, streamed
from psana_ray_tpu.models import decoder
from psana_ray_tpu.ops import delta_rule as dr
from psana_ray_tpu.parallel import moe
from test_manifest_entries import BENCH, asked, need, ratio_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmark", "configs")
CONFIG = os.path.join(CONFIGS, "ling3_flash_prefill_epix10k2m.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "ling3_epix_saturated"
KDA, MLA = decoder.LINEAR, decoder.ATTENTION
# the controls' faults (benchmark/tests/ling3_controls.py), at this size's chunk
FAULTS = {"bf16_state": {"state": "bfloat16"}, "no_decay": {"decay": "none"},
          "decay_per_head": {"decay": "head"}, "beta_one": {"beta": False},
          "state_not_carried": {"carry": 16}, "latest_taps_only": {"taps_used": (2, 3)},
          "no_l2_norm": {"l2": False}, "no_output_norm": {"o_norm": False},
          "no_output_gate": {"o_gate": False}, "no_head_gate": {"attn_gate": False},
          "softmax_router": {"scoring": "softmax"}, "no_shared_expert": {"shared": False}}
# the decoder cells the benchmark had before this one: configuration file -> its cell's suffix
OTHERS = {"keye_vl2_prefill_epix10k2m": "keye", "lfm2_8b_a1b_prefill_epix10k2m": "lfm2",
          "kimi_k2_prefill_epix10k2m": "kimi", "deepseek_v32_prefill_epix10k2m": "dsv32"}


def mapping(**over):
    """Ling-3.0's Hugging Face keys at a small size: two linear layers, the
    second with experts, then a latent layer; 16 routed experts in 4 groups
    of which 2 stay, all held."""
    m = dict(
        hidden_size=64, num_hidden_layers=3, layer_types=[KDA, KDA, MLA], num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, vocab_size=256, rms_norm_eps=1e-6, rope_theta=6000000,
        q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling=None, short_conv_kernel_size=4, kda_lower_bound=-5,
        gated_attention_proj_granularity_type="head_wise", first_k_dense_replace=1,
        num_experts=16, num_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=32, n_group=4, topk_group=2, intermediate_size=96,
        norm_topk_prob=True, score_function="sigmoid", scoring_func="sigmoid",
        moe_router_enable_expert_bias=True, topk_method="noaux_tc", routed_scaling_factor=2.5,
        tie_word_embeddings=False, patch=8,
    )
    m.update(over)
    return m


# loud: the taps and the decay's A and b are of order 1 as drawn
loud = functools.partial(decoder_kit.loud, keep=("conv_w",))
PATCHES_OF = {"float32_products": lambda: decoder_kit.float32_products(dr, dr.gated_delta_rule)}
# 64 tokens in several tiles: the delta rule in chunks of 16, attention in 32 x 32
KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=32, causal_kv_tile=32, linear_chunk=16), loud=loud,
          patches=PATCHES_OF)
small = KIT.small


@pytest.fixture
def float32_products():
    with PATCHES_OF["float32_products"]():
        yield


# ---------------------------------------------------------------------------
# the kernel against the recurrence, token by token
# ---------------------------------------------------------------------------

def _kernel_case(case, seed=0, heads=4, d=16, seq=48, batch=2):
    rng = np.random.default_rng(seed)
    t = batch * seq
    qkv = rng.standard_normal((t, 3 * heads * d))
    f = 2.0 * rng.standard_normal((t, heads * d))
    beta = rng.uniform(0.05, 0.95, (t, heads))
    log_a = rng.uniform(-0.7, 0.7, heads)
    bias = rng.uniform(-6.0, 2.0, heads * d)
    if case == "decay_at_the_bound":  # every channel forgets at -5 a token: e^-75 over a block
        bias = np.full(heads * d, 40.0)
    elif case == "decay_near_none":  # alpha = 1 to six places: the plain delta rule
        bias = np.full(heads * d, -40.0)
    elif case == "identical_keys":  # a detector's blank patches: one key again and again, kept
        qkv = np.tile(qkv[:1], (t, 1))
        bias, beta = np.full(heads * d, -40.0), np.full((t, heads), 0.9)
    elif case == "beta_near_0":
        beta = np.full((t, heads), 1e-3)
    elif case == "beta_near_1":
        beta = np.full((t, heads), 1.0 - 1e-3)
    arrays = [jnp.asarray(a, jnp.float32) for a in (qkv, f, rng.standard_normal((t, heads * d)),
                                                    beta, log_a, bias, rng.uniform(0.5, 1.5, d))]
    return arrays, dict(seq_len=seq, heads=heads, lower=-5.0, eps=1e-6)


def _recurrence(arrays, seq_len, heads, lower, eps, **fault):
    """The reference's own lines on the kernel's operands, sequence by sequence."""
    qkv, f, z, beta, log_a, bias, gain = arrays
    t, d = qkv.shape[0], f.shape[1] // heads
    m = {"carry": 0, "state": "float32", **fault}
    q, k, v = (u.reshape(t, heads, d) for u in jnp.split(qkv, 3, axis=1))
    q, k = (u / jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True) + ref.L2_EPS) for u in (q, k))
    g = lower * jax.nn.sigmoid(jnp.exp(log_a)[None, :, None]
                               * (f.reshape(t, heads, d) + bias.reshape(heads, d)))
    out = [ref.delta_rule(q[lo:lo + seq_len] * d ** -0.5, k[lo:lo + seq_len], v[lo:lo + seq_len],
                          g[lo:lo + seq_len], beta[lo:lo + seq_len], m, jnp.float32)
           for lo in range(0, t, seq_len)]
    o = ref.rms(jnp.concatenate(out), gain, eps) * jax.nn.sigmoid(z.reshape(t, heads, d))
    return o.reshape(t, heads * d), g


# sequences of 48 rows in chunks of 8, 16 and 48, and (PR 56) of 256 in the SERVED chunk of 128:
# eight row blocks whose keys come one from the other, seven levels of the inverse
CHUNKED = [(case, chunk, 48) for chunk in (8, 16, 48)
           for case in ("spread_decay", "decay_at_the_bound", "decay_near_none", "beta_near_0",
                        "beta_near_1", "identical_keys")]
CHUNKED += [(case, 128, 256) for case in ("spread_decay", "decay_at_the_bound", "identical_keys")]


@pytest.mark.parametrize("case,chunk,seq", CHUNKED, ids=[f"{c}-{n}" for c, n, _ in CHUNKED])
def test_the_chunked_form_is_the_recurrence_and_not_an_approximation(case, chunk, seq,
                                                                     float32_products):
    """Two sequences of 48 in one array (a boundary inside it), in chunks of
    8 (six a sequence), 16 (three) and 48 (one chunk of three blocks), and two
    of 256 in chunks of 128 (one group of four heads, two chunks a sequence):
    with float32 products the kernel IS the token-by-token recurrence to
    float32's own rounding, so the state, the running sums and the exponents
    are float32 and no term is dropped, whatever the decay and the step size."""
    arrays, sizes = _kernel_case(case, seq=seq)
    with jax.default_matmul_precision("highest"):
        got = dr.gated_delta_rule(*arrays, chunk=chunk, **sizes)
        want, g = _recurrence(arrays, **sizes)
    if case == "decay_at_the_bound":
        assert float(g.max()) < -4.999
    if case in ("decay_near_none", "identical_keys"):
        assert float(g.min()) > -1e-5
    assert dr.chunk_rows(seq, chunk) == chunk
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=0)
    # and a state that crossed the sequences' boundary, or stopped at a chunk's, is another result
    leaked, _ = _recurrence(arrays, **{**sizes, "seq_len": 2 * seq})
    seen = {"decay_at_the_bound": 1e-4, "identical_keys": 1e-3}.get(case, 1e-2)
    assert float(jnp.abs(leaked[seq:] - want[seq:]).max()) > seen
    if chunk < seq and seen == 1e-2:
        dropped, _ = _recurrence(arrays, carry=chunk, **sizes)
        assert float(jnp.abs(dropped - want).max()) > 1e-2


def test_a_block_s_keys_rescaled_from_the_block_before_are_the_keys_scaled_anew():
    """``_keys_of_blocks`` over a chunk of 128 rows in 8 blocks, a channel's
    decay from -0.01 to -5 a row: block ``I`` reads rows ``0 .. lo + 16``
    times ``e^(G_lo - G_j)``. Its own rows and the block's before are made
    so; the older ones went through up to six factors of at most 1, and are
    the direct form to float32's rounding. At -5 a row the direct form
    underflows two blocks back, and there both are 0."""
    rng = np.random.default_rng(56)
    c, d, block = 128, 16, dr.BLOCK
    k = rng.standard_normal((c, d))
    k = jnp.asarray(k / np.linalg.norm(k, axis=1, keepdims=True), jnp.float32)
    gam = jnp.asarray(np.arange(1, c + 1)[:, None] * -np.linspace(0.01, 5.0, d)[None], jnp.float32)
    got = dr._keys_of_blocks(k, gam, block)
    assert [u.shape for u in got] == [(lo + block, d) for lo in range(0, c, block)]
    underflowed = rescaled = 0
    for lo, keys in zip(range(0, c, block), got):
        exact = np.asarray(k[:lo + block], np.float64) * np.exp(
            np.asarray(gam[lo:lo + 1], np.float64) - np.asarray(gam[:lo + block], np.float64))
        direct = np.asarray(k[:lo + block] * jnp.exp(jnp.minimum(gam[lo:lo + 1] - gam[:lo + block],
                                                                 dr._CAP)))
        keys = np.asarray(keys)
        assert np.isfinite(keys).all() and np.abs(keys[:lo]).max(initial=0.0) <= 1.0
        # the block's own rows and the block's before ARE the direct form
        np.testing.assert_array_equal(keys[max(lo - block, 0):], direct[max(lo - block, 0):])
        # either form is 4e-6 from the exact value here: an exponent is a difference of two G
        np.testing.assert_allclose(keys, direct, rtol=2e-5, atol=1e-37)
        np.testing.assert_allclose(keys, exact, rtol=2e-5, atol=1e-37)
        assert not keys[direct == 0.0].any()
        underflowed += int((direct[:, -1] == 0.0).sum())
        rescaled += int((np.abs(keys[:max(lo - block, 0)]) > 1e-30).sum())
    assert underflowed > 100 and rescaled > 1000  # both kinds of entry were there to compare


@pytest.mark.parametrize("case", ["spread_decay", "decay_at_the_bound", "beta_near_1",
                                  "identical_keys"])
def test_bf16_products_keep_the_kernel_within_their_rounding_of_the_recurrence(case):
    arrays, sizes = _kernel_case(case, seed=1)
    arrays[0] = arrays[0].astype(jnp.bfloat16).astype(jnp.float32)  # what a bf16 [q | k | v] holds
    got = dr.gated_delta_rule(arrays[0].astype(jnp.bfloat16), *arrays[1:], chunk=16, **sizes)
    with jax.default_matmul_precision("highest"):
        want, _ = _recurrence(arrays, **sizes)
    assert got.dtype == jnp.bfloat16 and np.isfinite(np.asarray(got, np.float32)).all()
    err = np.sqrt(np.mean((np.asarray(got, np.float64) - np.asarray(want, np.float64)) ** 2)
                  / np.mean(np.asarray(want, np.float64) ** 2))
    assert err < 2e-2, err


def test_a_block_of_sixteen_rows_at_the_bound_stays_inside_float32_and_a_lower_bound_is_refused():
    arrays, sizes = _kernel_case("decay_at_the_bound")
    assert (dr.BLOCK - 1) * 5.0 <= dr._CAP < 88.0 - np.log(128.0)  # e^75 kept, 128 e^80 summed
    with pytest.raises(ValueError, match="leave the exponent's cap"):
        dr.gated_delta_rule(*arrays, chunk=16, **{**sizes, "lower": -6.0})
    with pytest.raises(ValueError, match="no chunk of whole 8-row tiles"):
        dr.chunk_rows(12)
    assert [dr.chunk_rows(s) for s in (8704, 24, 64, 136)] == [128, 24, 64, 8]
    assert 8704 % dr.CHUNK == 0 and dr.CHUNK % dr.BLOCK == 0


# ---------------------------------------------------------------------------
# ops/short_conv.conv_silu_taps: the kernel `decoder.conv_silu` is (PR 73), interpreted here


def _reference_conv_silu(u, w, bias, seq_len):
    """``benchmark/reference``'s convolution and SiLU (granite's form: a bias
    or none), a sequence at a time, float32 out."""
    from benchmark.reference import granite_decoder

    m = {"taps": w.shape[1], "conv_bias": bias is not None}
    return jnp.concatenate([granite_decoder.conv_silu(u[at:at + seq_len], w, bias, m)
                            for at in range(0, u.shape[0], seq_len)])


CONV_SILU_CASES = {  # rows, seq_len, channels, taps, a bias, the array's type, block_rows, block_cols
    "4_taps": (32, 32, 128, 4, False, jnp.bfloat16, 512, 1024),
    "4_taps_and_a_bias": (32, 32, 128, 4, True, jnp.bfloat16, 512, 1024),
    "2_taps": (32, 32, 128, 2, False, jnp.bfloat16, 512, 1024),
    "2_taps_and_a_bias": (32, 32, 128, 2, True, jnp.bfloat16, 512, 1024),
    "float32_in_and_out": (32, 16, 24, 4, True, jnp.float32, 512, 1024),
    "two_sequences_of_three_strips_the_second_starts_anew": (96, 48, 128, 4, False, jnp.bfloat16, 512, 1024),
    "tiles_of_one_strip_the_carry_rides_between_them": (128, 64, 128, 4, True, jnp.bfloat16, 16, 1024),
    "a_width_of_one_and_a_half_channel_blocks": (32, 16, 384, 4, True, jnp.bfloat16, 512, 256),
    "a_row_tile_of_24_that_does_not_divide_512_taken_whole": (48, 24, 128, 4, False, jnp.bfloat16, 512, 1024),
    "a_row_tile_of_8_eight_taps_all_of_the_carry": (24, 8, 128, 8, True, jnp.bfloat16, 512, 1024),
}


@pytest.mark.parametrize("case", sorted(CONV_SILU_CASES))
def test_conv_silu_taps_equals_the_reference_to_the_bit(case):
    from psana_ray_tpu.ops.short_conv import conv_silu_taps

    t, seq_len, c, taps, has_bias, dtype, block_rows, block_cols = CONV_SILU_CASES[case]
    rng = np.random.default_rng(len(case))
    u = jnp.asarray(rng.standard_normal((t, c)), dtype)
    w = jnp.asarray(rng.standard_normal((c, taps)), dtype)
    bias = jnp.asarray(rng.standard_normal(c), dtype) if has_bias else None
    got = conv_silu_taps(u, w, bias, seq_len=seq_len, block_rows=block_rows, block_cols=block_cols)
    want = _reference_conv_silu(u, w, bias, seq_len).astype(dtype)
    assert got.dtype == dtype and got.shape == (t, c)
    # to the bit where the result is rounded (the cells' case); float32 out to float32's last
    # place: XLA's CPU backend contracts a fused body's multiply-adds as the eager reference's are not
    tol = 1e-6 if dtype == jnp.float32 else 0.0
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=10 * tol, atol=tol)
    if t > seq_len:  # read as ONE sequence, a later sequence's first rows meet the rows before them
        as_one = np.asarray(_reference_conv_silu(u, w, bias, t), np.float32)
        starts = np.arange(seq_len, t, seq_len)[:, None] + np.arange(taps - 1)
        assert np.abs(as_one[starts] - np.asarray(got, np.float32)[starts]).max() > 1e-2


def test_lanes_conv_silu_lays_the_taps_a_head_at_whole_lane_tiles_as_the_columns_are():
    """Olmo-Hybrid's q and k: 96 columns a head laid at 128, the published
    taps ``[H * 96, 4]`` laid out the same way; a zero column stays zero."""
    rng = np.random.default_rng(7)
    heads, d_k, t = 2, 96, 32
    u = jnp.asarray(rng.standard_normal((t, heads * d_k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((heads * d_k, 4)), jnp.bfloat16)
    got = decoder._lanes_conv_silu(dr.lanes_a_head(u, heads), w, 16, heads)
    want = _reference_conv_silu(u, w, None, 16).astype(jnp.bfloat16)
    assert got.shape == (t, heads * 128)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(dr.lanes_a_head(want, heads), np.float32))


@pytest.mark.parametrize("t,seq_len,taps", [(24, 12, 4), (32, 24, 4), (32, 16, 9)],
                         ids=["rows_that_are_no_whole_sublane_tiles", "rows_that_are_no_whole_sequences",
                              "taps_over_the_carry"])
def test_conv_silu_taps_refuses_what_its_carry_cannot_hold(t, seq_len, taps):
    from psana_ray_tpu.ops.short_conv import conv_silu_taps

    with pytest.raises(ValueError, match="sequences"):
        conv_silu_taps(jnp.zeros((t, 128), jnp.bfloat16), jnp.zeros((128, taps), jnp.bfloat16), seq_len=seq_len)


def test_conv_silu_is_four_shifted_sums_that_start_anew_with_every_sequence():
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.standard_normal((32, 24)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((24, 4)), jnp.float32)
    got = decoder.conv_silu(u, w, 16)
    m = {"taps": 4, "taps_used": (0, 1, 2, 3)}
    want = jnp.concatenate([ref.conv_silu(u[:16], w, m), ref.conv_silu(u[16:], w, m)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    as_one = ref.conv_silu(u, w, m)  # the second sequence's first three rows read the first's last
    assert float(jnp.abs(as_one[16:19] - want[16:19]).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(as_one[19:]), np.asarray(want[19:]), atol=1e-6)


# ---------------------------------------------------------------------------
# the trunk against the reference, float32, all positions, a batch of two
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", ["all_16", "experts_0_to_7_of_16"])
def test_the_hybrid_trunk_matches_the_reference_at_all_positions_of_a_batch_of_two(
        held, float32_products):
    m = mapping()
    cfg = small(m)
    params = loud(decoder.init_params(cfg, jax.random.key(3), jnp.float32))
    if held == "experts_0_to_7_of_16":  # a share: groups 0 and 1 of the four
        m.update(num_experts=8, router_experts=16, experts_held=[0, 8])
        cfg, params = small(m), share_of(params, 0, 8)
    patches, ids = inputs(3, batch=2)
    sizes = ref.sizes(m)
    with jax.default_matmul_precision("highest"):
        x, got, stats = KIT.trunk_of(params, patches, ids, cfg)
        want_x, want = KIT.reference_of(params, patches, ids, sizes)
    for a, b in ((x, want_x), (got, want)):
        scale = float(jnp.sqrt(jnp.mean(b ** 2)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4 * scale, rtol=0)
    # twelve statistics with linear layers, whatever the share, and the pass's rows from a holder of half
    names = decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS + decoder.LINEAR_STATS
    assert len(names) == 12 and cfg.rows_go_ahead == (held != "all_16")
    names += decoder.AHEAD_STATS if cfg.rows_go_ahead else ()
    assert len(stats) == len(names)
    got_stats = dict(zip(names, (float(v) for v in stats)))
    if cfg.rows_go_ahead:  # under 1.5 even shares a layer: the pass took every held row
        assert got_stats["expert_rows_ahead_total"] == got_stats["expert_rows_held_total"] > 0
    assert got_stats["expert_tokens_mean_total"] == 2 * 128 * 4 / 16
    assert got_stats["attn_tiles_causal_total"] == got_stats["attn_tiles_live_total"] == 2  # one latent layer
    assert (got_stats["decoder_tokens_total"], got_stats["decoder_sequences_total"]) == (128, 2)
    assert got_stats["expert_rows_routed_total"] == 2 * 128 * 4
    assert (got_stats["expert_rows_held_total"] == 2 * 128 * 4) == (held == "all_16")
    assert got_stats["attn_pairs_selected_total"] == got_stats["attn_pairs_causal_total"] == 0
    assert got_stats["linear_attn_tokens_total"] == 2 * 128  # two linear layers
    assert got_stats["linear_attn_chunks_total"] == 2 * 2 * 4 * (64 // 16)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_with_a_control_s_fault_in_it_is_another_trunk(fault):
    x, same = KIT.trunk(5, under=F32_PRODUCTS, jit=False)[0], KIT.reference(5)[0]  # made once for the twelve cases
    want = KIT.reference(5, **FAULTS[fault])[0]
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    assert float(jnp.abs(x - same).max()) < 1e-3 * scale
    # what a control puts in is seen; a bf16 state's rounding is small and still no float32 state
    seen = 1e-3 if fault == "bf16_state" else 1e-2
    assert float(jnp.abs(x - want).max()) > seen * scale


def test_a_sequence_of_the_batch_does_not_read_its_neighbour_s_state_or_taps():
    cfg = small(mapping())
    params = KIT.params(7)
    patches, ids = inputs(7, batch=2)
    run = jax.jit(lambda p, x: decoder.trunk(p, x, np.arange(64), cfg, 2)[0])
    x = run(params, embedded(params, patches, ids))
    moved = run(params, embedded(params, patches[::-1], ids))
    np.testing.assert_array_equal(np.asarray(x[:64]), np.asarray(moved[64:]))
    np.testing.assert_array_equal(np.asarray(x[64:]), np.asarray(moved[:64]))


# ---------------------------------------------------------------------------
# latent attention with a full-rank query and a head-wise gate
# ---------------------------------------------------------------------------

def test_a_null_query_rank_is_a_full_rank_query_and_the_gate_is_one_scalar_a_head():
    cfg = small(mapping())
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.head_dim, cfg.rope_dim) == (0, 16, 24, 8)
    assert cfg.attn_gate == "head_wise" and cfg.rope_yarn is None
    shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))
    latent = shapes["layers"][2]
    assert latent["wq"].shape == (64, 4 * 24) and latent["w_attn_gate"].shape == (64, 4)
    assert not {"wq_a", "q_a_norm", "wq_b"} & set(latent)
    with pytest.raises(ValueError, match="granularity"):
        decoder.DecoderConfig.from_mapping(mapping(gated_attention_proj_granularity_type="element_wise"))
    # kimi's spelling (a query rank, no gate) keeps its own parameters
    ranked = decoder.DecoderConfig.from_mapping(mapping(
        q_lora_rank=24, layer_types=[MLA] * 3, gated_attention_proj_granularity_type=None))
    theirs = jax.eval_shape(lambda k: decoder.init_params(ranked, k), jax.random.key(0))["layers"][2]
    assert {"wq_a", "q_a_norm", "wq_b"} <= set(theirs) and "w_attn_gate" not in theirs
    assert "wq" not in theirs and not ranked.has_linear and ranked.layer_stats == 4


def test_the_gated_full_rank_latent_layer_is_the_reference_s():
    latent = dict(layer_types=[MLA, MLA, MLA], first_k_dense_replace=3, num_hidden_layers=3)
    x, _, stats = KIT.trunk(11, over=latent, jit=False)
    want, ungated = KIT.reference(11, over=latent)[0], KIT.reference(11, over=latent, attn_gate=False)[0]
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    np.testing.assert_allclose(np.asarray(x), np.asarray(want), atol=2e-4 * scale, rtol=0)
    assert float(jnp.abs(x - ungated).max()) > 1e-2 * scale
    assert len(stats) == 6  # no share, no selection, no linear layer: the six every decoder counts


# ---------------------------------------------------------------------------
# the shares add up, at 8 groups of which 4 stay
# ---------------------------------------------------------------------------

def _expert_layer(seed, t=64, d=32, width=16, experts=32, k=4):
    rng = np.random.default_rng(seed)

    def w(*shape, by=0.2):
        return jnp.asarray(rng.standard_normal(shape) * by, jnp.float32)

    p = {"router": w(d, experts, by=0.5), "router_bias": w(experts, by=0.3),
         "w_gate": w(experts, d, width), "w_up": w(experts, d, width), "w_down": w(experts, width, d),
         "shared_gate": w(d, width), "shared_up": w(d, width), "shared_down": w(width, d)}
    m = ref.sizes(mapping(num_experts=experts, num_experts_per_tok=k, n_group=8, topk_group=4))
    return p, w(t, d, by=1.0), m


def test_four_shares_of_two_groups_each_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    p, b, m = _expert_layer(9)
    with jax.default_matmul_precision("highest"):
        parts, served = [], []
        for first in (0, 8, 16, 24):  # a share is two of the eight groups, as the cell's 128 of 512
            y, tokens = moe.dropless_moe(
                b, p["router"], p["w_gate"][first:first + 8], p["w_up"][first:first + 8],
                p["w_down"][first:first + 8], k=4, num_experts=32, experts_held=(first, 8),
                scoring="sigmoid", select_bias=p["router_bias"], gate_eps=1e-20, gate_scale=2.5,
                groups=8, groups_kept=4)
            parts.append(np.asarray(y, np.float64))
            served.append(int(np.asarray(tokens).sum()))
        shared = np.asarray(decoder._dense_mlp(
            {"w_gate": p["shared_gate"], "w_up": p["shared_up"], "w_down": p["shared_down"]}, b))
        routed, chosen = ref.experts(p, b, m, jnp.float32)
        want = np.asarray(routed + ref.shared_expert(p, b, jnp.float32))
        unlimited, _ = ref.experts(p, b, {**m, "group_limit": False}, jnp.float32)
    assert sum(served) == 64 * 4 and np.asarray(chosen).sum() == 64 * 4  # every slot, once
    assert min(np.abs(part).max() for part in parts) > 0 and np.abs(shared).max() > 0
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    assert np.abs(np.asarray(unlimited) - np.asarray(routed)).max() > 1e-2  # the limit is in the sum
    assert np.abs(sum(part + shared for part in parts) - want).max() > 1e-2  # counted four times: no
    held = {k: (v[8:16] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p.items()}
    one, _ = ref.experts(held, b, {**m, "experts_held": (8, 8)}, jnp.float32)
    np.testing.assert_allclose(parts[1], np.asarray(one), atol=2e-5)


@pytest.mark.parametrize("case", ["within_the_pass", "overflowing_into_the_loop"])
def test_a_large_share_takes_its_rows_in_one_pass_and_the_loop_takes_what_overflows(case):
    """A holder of a quarter of the experts takes 1.5 even shares of the slots
    ahead of the held rows' loop (``moe.rows_ahead``); where a selection bias
    sends EVERY token's four choices to the held eight, 256 rows are held for
    the pass's 96 and the loop adds the other 160: nothing is dropped, and
    the layer is the reference's either way."""
    p, b, m = _expert_layer(13)
    bias = p["router_bias"]
    if case == "overflowing_into_the_loop":
        bias = bias.at[8:16].add(5.0)
    assert moe.rows_ahead(64 * 4, 8, 32) == 96
    with jax.default_matmul_precision("highest"):
        y, tokens = moe.dropless_moe(
            b, p["router"], p["w_gate"][8:16], p["w_up"][8:16], p["w_down"][8:16], k=4,
            num_experts=32, experts_held=(8, 8), scoring="sigmoid", select_bias=bias,
            gate_eps=1e-20, gate_scale=2.5, groups=8, groups_kept=4)
        held = {k: (v[8:16] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p.items()}
        want, _ = ref.experts({**held, "router_bias": bias}, b, {**m, "experts_held": (8, 8)},
                              jnp.float32)
    rows = int(np.asarray(tokens).sum())
    assert (rows == 256) if case == "overflowing_into_the_loop" else (0 < rows <= 96)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)


def test_the_pass_ahead_is_a_large_share_s_alone():
    assert moe.rows_ahead(34816 * 8, 128, 512) == 104448 == 204 * 512  # the cell's: 1.5 x 69,632
    assert moe.rows_ahead(17408 * 8, 12, 384) == 0 == moe.rows_ahead(8704 * 8, 8, 256)  # kimi's, dsv32's
    assert moe.rows_ahead(64 * 4, 16, 32) == 192 and moe.rows_ahead(64 * 4, 31, 32) == 256


# ---------------------------------------------------------------------------
# the configuration's fifth spelling
# ---------------------------------------------------------------------------

def _catalog_row(name="Ling-3.0-flash"):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == name]
    return row


def _file(name=None):
    with open(os.path.join(CONFIGS, name + ".json") if name else CONFIG) as f:
        return json.load(f)


def test_from_mapping_reads_the_published_keys_given_the_layers_kinds():
    published = _catalog_row()["config"]
    kinds = [MLA if (i + 1) % published["layer_group_size"] == 0 else KDA
             for i in range(published["num_hidden_layers"])]
    got = decoder.DecoderConfig.from_mapping({**published, "layer_types": kinds})
    assert (got.hidden_size, got.num_layers, got.num_heads, got.head_dim, got.rope_dim) == (
        2560, 42, 32, 192, 64)
    assert (got.q_lora_rank, got.kv_lora_rank, got.qk_nope_head_dim, got.qk_rope_head_dim,
            got.v_head_dim, got.attn_gate) == (0, 512, 128, 64, 128, "head_wise")
    assert (got.linear_head_dim, got.linear_decay_floor, got.conv_taps, got.linear_chunk) == (
        128, -5.0, 4, 128)
    assert (got.num_experts, got.experts_held, got.experts_per_token, got.expert_width,
            got.shared_experts, got.num_dense_layers, got.intermediate_size) == (
        512, (0, 512), 8, 768, 1, 2, 6144)
    assert (got.router_groups, got.router_groups_kept, got.router_scoring, got.expert_bias,
            got.gate_eps, got.routed_scaling_factor) == (8, 4, "sigmoid", True, 1e-20, 2.5)
    assert got.rope_yarn is None and got.rope_theta == 6e6 and not got.tie_embedding
    assert got.has_linear and not got.holds_a_share and not got.selects_over_latent
    assert got.layer_stats == 10 and kinds.count(MLA) == 7 and kinds.count(KDA) == 35
    assert [got.layer_kind(i) for i in (0, 1, 2, 5, 11, 41)] == [
        (KDA, False), (KDA, False), (KDA, True), (decoder.LATENT, True), (decoder.LATENT, True),
        (decoder.LATENT, True)]
    with pytest.raises(ValueError, match="layer_types"):
        decoder.DecoderConfig.from_mapping({**published, "layer_types": kinds[:7]})


def test_the_file_holds_the_catalog_s_numbers_unchanged_and_names_its_cuts():
    row, cfg = _catalog_row(), _file()
    assert cfg["source"] == row["source_url"] and len(cfg["source"]) <= 200
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers", "first_k_dense_replace",
                                              "num_experts", "vocab_size"}
    assert {k: row["config"][k] for k in differs} == {k: cfg["published"][k] for k in differs}
    assert "4 chips of a v5e host" in cfg["deployment"] and "NO cited deployment" in cfg["deployment"]
    said = " ".join(cfg["assumed"])
    for reading in ("BOUNDED gate", "softplus", "L2-normed", "each head's query and key", "head_wise",
                    "layer_group_size", "uniform in (-0.7, 0.7)", "uniform in (-6, 2)",
                    "multi-token-prediction", "linear patch embedding", "rotate-half"):
        assert reading in said, reading
    assert "no kept layer has a limit" in cfg["published"]["swiglu_limits"]
    assert "6 KDA : 1 latent" in cfg["what"] and "lfm2's scope name" in cfg["what"]
    got = decoder.DecoderConfig.from_mapping(cfg)
    assert (got.num_layers, got.num_dense_layers, got.num_experts, got.experts_held,
            got.vocab_size) == (7, 1, 512, (0, 128), 39296)
    assert got.layer_types == (KDA,) * 6 + (MLA,) and got.holds_a_share and got.has_linear
    assert got.vocab_size % 128 == 0 and got.vocab_size * 4 == cfg["published"]["vocab_size"]
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 5.22e9 < n < 5.24e9  # the file's 5.23 G parameters, 10.46 GB in bf16
    linear, latent = shapes["layers"][1], shapes["layers"][6]
    assert linear["w_qkv"].shape == (2560, 3 * 4096) and linear["conv_w"].shape == (3 * 4096, 4)
    assert linear["decay_a"].shape == (32,) and linear["decay_b"].shape == (4096,)
    assert linear["router"].shape == (2560, 512) and linear["w_gate"].shape == (128, 2560, 768)
    assert latent["wq"].shape == (2560, 32 * 192) and latent["w_attn_gate"].shape == (2560, 32)
    assert shapes["layers"][0]["w_gate"].shape == (2560, 6144)
    assert cfg["sequence_tokens"] == 16 * (352 // 16) * (384 // 16) + cfg["prompt_tokens"] == 8704
    assert cfg["step_tokens"] == cfg["batch_size"] * cfg["sequence_tokens"] == 34816
    assert cfg["num_experts"] == cfg["experts_held"][1] == cfg["published"]["num_experts"] // 4
    # the rehearsal's size keeps every mechanism: both operators, a gate, a group limit, a share
    small_cfg = decoder.DecoderConfig.from_mapping({**cfg, **cfg["rehearse"]})
    assert small_cfg.has_linear and small_cfg.holds_a_share and small_cfg.attn_gate == "head_wise"
    assert (small_cfg.router_groups, small_cfg.router_groups_kept) == (4, 2)
    assert decoder.LATENT in [small_cfg.layer_kind(i)[0] for i in range(small_cfg.num_layers)]


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_four_readers_have_nothing_of_what_this_one_brought(name):
    cfg = _file(name)
    got = decoder.DecoderConfig.from_mapping(cfg)
    assert (got.linear_head_dim, got.linear_decay_floor, got.attn_gate) == (0, 0.0, "")
    assert not got.has_linear and got.layer_stats <= 8 and KDA not in got.layer_types
    assert bool(got.q_lora_rank) == bool(got.kv_lora_rank)  # a latent query there goes through a rank
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    brought = {"w_qkv", "w_f", "w_z", "w_beta", "decay_a", "decay_b", "o_norm", "wq", "w_attn_gate"}
    kept = {"wq"} if OTHERS[name] in ("lfm2", "keye") else set()  # grouped-query attention's own
    for layer in shapes["layers"]:
        assert not (brought - kept) & set(layer)


# ---------------------------------------------------------------------------
# the two new counters, in snapshot() and under /metrics
# ---------------------------------------------------------------------------

def test_linear_counters_reach_the_snapshot_and_the_exposition():
    cfg = small(mapping(num_experts=8, router_experts=16, experts_held=[0, 8]), linear_chunk=8)
    _, snap, text = streamed(cfg)
    steps, s = 2, 2 * 2 * 14 + PROMPT  # 64 tokens a frame, two frames a step
    assert snap["decoder_tokens_total"] == steps * 2 * s
    assert snap["linear_attn_tokens_total"] == steps * 2 * (2 * s)  # two linear layers
    assert snap["linear_attn_chunks_total"] == steps * 2 * (2 * 4 * (s // 8))
    assert snap["linear_attn_tokens_total"] * 4 / snap["linear_attn_chunks_total"] == 8  # the chunk
    assert snap["attn_pairs_causal_total"] == 0 == snap["attn_pairs_selected_total"]
    assert 0 < snap["expert_rows_held_total"] < snap["expert_rows_routed_total"] == steps * 2 * 2 * s * 4
    assert 0 < snap["expert_rows_ahead_total"] <= snap["expert_rows_held_total"]
    for name in (decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS
                 + decoder.LINEAR_STATS + decoder.AHEAD_STATS):
        assert f'psana_ray_{name}{{source="reader"}}' in text, name


# ---------------------------------------------------------------------------
# the cell, its readers and its counts (its manifest entries: tests/test_manifest_entries.py)
# ---------------------------------------------------------------------------

def test_the_ling3_cell_follows_dsv32_s_and_reports_the_host_path_as_the_decoders_do():
    cell = BENCH.cell(CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "saturated", "ling3_flash_prefill_epix10k2m")
    config = BENCH.config(cell["config"])
    assert config["file"] == os.path.relpath(CONFIG, REPO) and len(cell["why"]) <= 200
    assert config["reduced"] == _file()["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size"]
    cfg = _file()
    assert cfg["transport"]["slots"] == 16 and cfg["batch_size"] == 4
    assert cfg["trace_names"]["kda_kernel"] == "gated_delta_rule"  # the pallas_call's own name


# what ran under `moe` in a traced step of ONE expert layer: name -> (the name stack's leaf, ms)
PASS = {"gmm": ("pallas_call", 0.2), "gmm.1": ("pallas_call", 0.3), "gmm.2": ("pallas_call", 0.1)}
WAY_BACK = {"rows_as_words": ("pallas_call", 0.4), "sum_counted_rows.1": ("pallas_call", 0.5)}
LOOP = {"tpu_custom_call.3": ("pallas_call", 0.7), "fusion.8": ("scatter-add", 0.9)}
NAMED_TRACES = {
    # (what ran, counts the pass's rows, the rows' share read / None: nothing, said on stderr)
    "the_change_s_step": ({**PASS, **WAY_BACK}, True, 0.2, ""),
    "the_change_s_step_overflowing": ({**PASS, **WAY_BACK, **LOOP}, True, 0.2, ""),
    "the_parent_s_step": (PASS, False, 0.25, ""),
    "the_parent_s_step_overflowing": ({**PASS, **LOOP}, False, None, "tpu_custom_call.3"),
    "a_product_more": ({**PASS, "gmm.3": ("pallas_call", 0.1), **WAY_BACK}, True, None, "call sites"),
    "no_product_outside_a_loop": ({**WAY_BACK, **LOOP}, True, None, ""),
}


@pytest.mark.parametrize("case", sorted(NAMED_TRACES))
def test_the_pass_s_products_are_read_by_name_beside_the_kernels_of_its_way_back(
        case, tmp_path, monkeypatch, capsys):
    """``gmm_ahead_roofline_share.ling3`` (``readers/roofline_share_named_per_run``)
    over a hand-written trace of two runs of a step: the instructions named
    ``gmm`` under ``moe`` alone are summed, whatever else runs there; their
    need follows the rows the PASS took where the program counts them, the
    rows held where it does not and nothing else ran under the scope; three
    named instructions must have run (``call_sites`` at one expert layer)."""
    import types

    from benchmark.readers import roofline_share_named_per_run, trace_scope_leaf_time

    ran, counts_the_pass, share, said = NAMED_TRACES[case]
    stack = "jit(ling3_step)/moe/jit(mlp)"
    scopes = {name: f"{stack}/{leaf}" for name, (leaf, _) in ran.items()}
    scopes["fusion.9"] = "jit(ling3_step)/proj/dot_general"
    ops = [(f"%{name} = f32[8,8] custom-call(...)", run + 1e5 * (i + 1), ms * 1e6)
           for run in (0.0, 5e7) for i, (name, (_, ms)) in enumerate(ran.items())]
    ops += [("%fusion.9 = f32[8,8] fusion(...)", 4e7, 9e5)]
    device = {0: {"XLA Modules": [("jit_ling3_step(1)", 0.0, 4.5e7), ("jit_ling3_step(1)", 5e7, 4.5e7)],
                  "XLA Ops": sorted(ops, key=lambda e: e[1])}}
    counters = {"expert_rows_held_total": 50.0, "expert_rows_routed_total": 200.0}
    if counts_the_pass:
        counters["expert_rows_ahead_total"] = 40.0
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace" / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace_scope_leaf_time, "load_scopes", lambda path: scopes)
    ctx = types.SimpleNamespace(
        trace=types.SimpleNamespace(device=device), trace_window=(0.0, 1e9),
        cfg={"trace_names": {"step": "jit_ling3_step"},
             "t": 64, "k": 4, "d": 128, "f": 64, "n": 4, "l": 2, "dense": 1},
        spool_path=str(tmp_path / "spans" / "spool"),
        metrics=types.SimpleNamespace(snapshot=lambda: counters),
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e30})
    [read_with, *_] = asked(CELL, reader="roofline_share_named_per_run").values()  # the cell's file
    args = {**read_with,
            "shape_from": {"tokens": "t", "per_token": "k", "hidden": "d", "width": "f", "held": "n",
                           "layers": "l", "dense_layers": "dense"}}
    got = roofline_share_named_per_run.read(ctx, **args)
    err = capsys.readouterr().err
    assert (said in err) if said else not err
    if share is None:
        assert got is None
    else:  # three products over the rows' share of 64 x 4 slots, in the named three's 0.6 ms
        assert got == pytest.approx(3 * 2 * (64 * 4 * share) * 128 * 64 / 1e12 / 6e-4 * 100.0)
    ctx.trace = None
    assert roofline_share_named_per_run.read(ctx, **args) is None


def test_ling3_roofline_counts_at_the_published_sizes():
    from benchmark.roofline import kimi_k2
    from benchmark.roofline import ling3 as roofline

    fn, [shapes] = need(CELL, "ling3.delta_rule")
    rule = fn(**shapes)
    assert rule["flops"] == 7 * 128 * 128 * 32 * 34816  # 0.128 T a layer, whatever the chunk
    assert rule["bytes"] == 34816 * 32 * (5 * 2 * 128 + 4)  # 1.43 GB: bound by bytes
    assert rule["bytes"] / 819e9 > rule["flops"] / 197e12
    assert "chunk" not in fn.__code__.co_varnames
    fn, [shapes] = need(CELL, "kimi_k2.latent_attention")
    attention = fn(**shapes)["flops"]
    assert attention == kimi_k2.latent_attention(2, 8704, 64, 128, 64, 128)["flops"]  # kimi's grid
    assert abs(attention / 1e12 - 3.10) < 0.01
    fn, asked_for = need(CELL, "kimi_k2.held_products")
    for shapes in asked_for:  # of each entry that names it
        held = fn(held_share=128 / 512, **shapes)
        assert held["call_sites"] == 18 and held["flops"] == 18 * 2 * 69632 * 2560 * 768
    fn, [shapes] = need(CELL, "ling3.step")
    step = fn(**shapes)["flops"]
    assert abs(step / 1e12 - 43.7) < 0.1
    rows = 34816
    linear = 2 * rows * 2560 * (6 * 4096 + 32) + 2 * 4 * rows * 12288 + rule["flops"]
    latent = 2 * rows * (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 4096 * 2560
                         + 2560 * 32) + attention
    sparse = 6 * rows * 2560 * 768 * (1 + 8 * 128 / 512) + 2 * rows * 2560 * 512
    rest = 2 * 4 * 8448 * 256 * 2560 + 2 * 4 * 2560 * 39296 + 6 * rows * 2560 * 6144
    assert step == pytest.approx(6 * linear + latent + 6 * sparse + rest, rel=1e-12)
    assert abs(2 * 2560 * (6 * 4096 + 32) / 1e6 - 126) < 0.5  # a linear layer's products a token
    assert roofline.step.__code__.co_argcount == len(shapes)


# ---------------------------------------------------------------------------
# the adapter, and the cell's rehearsal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lacks", ["linear_head_dim", "attn_gate"])
def test_the_adapter_ends_the_run_where_the_package_lacks_the_mechanism(monkeypatch, lacks):
    from benchmark.programs import prefill_hybrid

    older = dataclasses.make_dataclass(
        "Older", [(f.name, f.type, dataclasses.field(default=None))
                  for f in dataclasses.fields(decoder.DecoderConfig) if f.name != lacks], frozen=True)
    monkeypatch.setattr(decoder, "DecoderConfig", older)
    with pytest.raises(SystemExit) as e:
        prefill_hybrid.Program({"name": "ling3_flash_prefill_epix10k2m"}, 1, "", None)
    assert e.value.code not in (0, None) and lacks in str(e.value.code)


def test_the_adapter_ends_the_run_where_the_file_counts_other_experts_than_it_holds():
    from benchmark.programs import prefill_hybrid

    with pytest.raises(SystemExit) as e:
        prefill_hybrid.Program({**_file(), "num_experts": 512}, 1, "", None)
    assert "is not the count of experts_held" in str(e.value.code)


def test_the_cell_s_rehearsal_runs_the_served_path_and_is_correct():
    line, done = rehearse(CELL, seed=1, seconds=2, xla_flags=False, timeout=600)
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0 and line["metrics"] == {}
    assert line["cell"] == CELL and line["attempted"] > 0
    for counted in (("linear_attn_tokens_total", "linear_attn_chunks_total"),
                    ("expert_rows_held_total", "expert_rows_routed_total"),
                    ("expert_tokens_max_total", "expert_tokens_mean_total")):
        assert ratio_of(CELL, *counted) in line["would_report"], line["would_report"]
    assert "device_wait_ms.hit" in line["would_report"]
    assert "compiles inside the window 0" in done.stderr
