"""A learned key selection OVER latent attention and a group-limited
router (``models/decoder.py`` reading DeepSeek-V3.2's keys) against the
benchmark's plain reference (``benchmark/reference/deepseek_v32_decoder.py``)
at small sizes on the CPU; the causal kernel under a mask with a shared key
part and a value width of its own; the shares of a group-limited expert
layer; the new cell's counters and counts."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_kit
from benchmark.reference import deepseek_v32_decoder as ref
from benchmark.reference.keye_decoder import select as ref_select
from decoder_kit import PROMPT, Kit, inputs, rehearse, share_of, streamed
from psana_ray_tpu.models import decoder
from psana_ray_tpu.parallel import moe
from psana_ray_tpu.parallel import sparse_attention as sa
from test_manifest_entries import BENCH, need, ratio_of
from xla_turn import TURNS, assert_the_kernel_s_turn_is_xla_s, turned_by_xla

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "deepseek_v32_prefill_epix10k2m.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "dsv32_epix_saturated"
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
FAULTS = {"no_indexer": {"indexer": False}, "latest_keys": {"select": "latest"},
          "query_from_input": {"index_query": "input"}, "rms_index_key": {"index_key_norm": "rms"},
          "whole_index_rope": {"index_rope": "whole"}, "no_group_limit": {"group_limit": False},
          "no_selection_bias": {"select_bias": False}, "no_shared_expert": {"shared": False},
          "no_mscale": {"mscale": False}}


def mapping(**over):
    """DeepSeek-V3.2's Hugging Face keys at a small size: 16 routed experts
    in 4 groups of which 2 stay, all held; 16 keys of 64 selected."""
    m = dict(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        vocab_size=256, rms_norm_eps=1e-6, rope_theta=10000, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1,
        index_n_heads=4, index_head_dim=16, index_topk=16,
        n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=32,
        n_group=4, topk_group=2, intermediate_size=96, norm_topk_prob=True,
        scoring_func="sigmoid", topk_method="noaux_tc", routed_scaling_factor=2.5,
        tie_word_embeddings=False, rope_scaling=dict(YARN), patch=8,
    )
    m.update(over)
    return m


def loud(params):
    """The same tree with its matrices scaled up (``tests/test_decoder_kimi.py``
    says why), the index key's LayerNorm given a gain and a bias that count."""
    params = decoder_kit.loud(params)
    params["layers"] = [{**p, "idx_k_norm": p["idx_k_norm"] * 1.5, "idx_k_bias": p["idx_k_bias"] * 20}
                        for p in params["layers"]]
    return params


# tiles that cut 64 tokens into several: masks of 16 x 32, attention in 32 x 32
KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=32, causal_kv_tile=32, q_tile=16, kv_tile=32), loud=loud)
small = KIT.small


def selected_pairs(s, topk):
    return sum(min(t + 1, topk) for t in range(s))


# ---------------------------------------------------------------------------
# the trunk against the reference, float32, all positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", ["all_16", "experts_0_to_3_of_16"])
def test_trunk_with_a_selection_over_latent_attention_matches_reference_at_all_positions(held):
    m = mapping()
    cfg = small(m)
    params = loud(decoder.init_params(cfg, jax.random.key(3), jnp.float32))
    if held == "experts_0_to_3_of_16":  # a share: group 0 of the four
        m.update(n_routed_experts=4, router_experts=16, experts_held=[0, 4])
        cfg, params = small(m), share_of(params, 0, 4)
    patches, ids = inputs(3)
    sizes = ref.sizes(m)
    with jax.default_matmul_precision("highest"):
        x, got, stats = KIT.trunk_of(params, patches, ids, cfg)
        want_x, want = KIT.reference_of(params, patches, ids, sizes)
    for a, b in ((x, want_x), (got, want)):
        scale = float(jnp.sqrt(jnp.mean(b ** 2)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4 * scale, rtol=0)
    # ten statistics under a selection over latent attention, whatever the share; a holder of a
    # quarter (not the cell's 8 of 256) takes a pass ahead of its loop and counts its rows, last
    assert len(decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS) == 10
    assert len(stats) == (10 if held == "all_16" else 13) and cfg.rows_go_ahead == (held != "all_16")
    if cfg.rows_go_ahead:
        assert [float(v) for v in stats[10:]] == [0.0, 0.0, float(stats[6])]
    assert float(stats[1]) == 2 * 64 * 4 / 16 and float(stats[3]) == 3  # one 64 x 64 tile a layer
    assert 0 < float(stats[2]) <= 3
    assert [float(v) for v in stats[4:6]] == [64.0, 1.0]
    assert float(stats[7]) == 2 * 64 * 4
    assert (float(stats[6]) == float(stats[7])) == (held == "all_16")
    assert [float(v) for v in stats[8:10]] == [3 * selected_pairs(64, 16), 3 * 64 * 65 // 2]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_with_a_control_s_fault_in_it_is_another_trunk(fault):
    x, same = KIT.trunk(5, jit=False)[0], KIT.reference(5)[0]  # made once for the nine cases
    want = KIT.reference(5, **FAULTS[fault])[0]
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    assert float(jnp.abs(x - same).max()) < 1e-3 * scale
    assert float(jnp.abs(x - want).max()) > 1e-2 * scale  # what a control puts in is seen


# ---------------------------------------------------------------------------
# Sel: the indexer over the query's low rank, a partial rotary, ties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["sixty_four_heads", "tied_scores"])
def test_sel_is_the_reference_s_sets_at_64_index_heads_and_a_partial_rotary(case):
    """The program's mask from ``decoder._indexer`` (index queries from the
    normed query rank, a LayerNormed key, the rotary on the first
    ``qk_rope_head_dim`` components) against the reference's ``Sel``: equal
    as sets for every query. ``tied_scores``: the tokens repeat with period
    3 and the index queries have no rotary part, so a query's scores tie
    in runs and the selected are the EARLIEST of the best."""
    m = mapping(index_n_heads=64, index_head_dim=32, index_topk=16, qk_rope_head_dim=8)
    cfg = small(m)
    s = 96
    p = loud(decoder.init_params(cfg, jax.random.key(11), jnp.float32))["layers"][0]
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((s, 64)), jnp.float32)
    if case == "tied_scores":
        x = x[jnp.arange(s) % 3]
        turned = (np.arange(64 * 32) % 32) < 8  # each index head's rotary columns
        p = {**p, "idx_wq": jnp.where(turned[None, :], 0.0, p["idx_wq"])}
    sizes = ref.sizes(m)
    angles = decoder.rotary_angles(np.arange(s), cfg.rope_theta, 4, None, cfg.rope_yarn)
    with jax.default_matmul_precision("highest"):
        a = decoder.rms_norm(x, p["norm1"], 1e-6)
        c_q = decoder.rms_norm(a @ p["wq_a"], p["q_a_norm"], 1e-6)
        mask, flags = decoder._indexer(p, a, angles, cfg, q_from=c_q)
        _, want = ref.latent_attention(p, ref.rms(x, p["norm1"], 1e-6), sizes, jnp.float32, 32,
                                       with_sel=True)
    got = np.asarray(sa.mask_to_dense(mask))
    want = np.asarray(want)
    assert mask.shape == (6, 3, 16, 32) and flags.shape == (6, 3)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got.sum(axis=1), np.minimum(np.arange(s) + 1, 16))
    assert got.sum() == selected_pairs(s, 16)  # what PAIR_STATS counts from the shapes
    if case == "tied_scores":
        best = np.flatnonzero(got[s - 1])
        assert len(set(best % 3)) == 1 and (best == best[0] + 3 * np.arange(16)).all()
    else:  # a selection, not a window: some query skips a recent key for an older one
        assert any(np.flatnonzero(got[t])[0] < t - 15 for t in range(16, s))


@pytest.mark.parametrize("case", ["random_scores", "tied_scores", "within_topk_is_dense"])
def test_the_wide_mask_is_the_same_sel_bit_for_bit_and_the_flags_stay_the_pieces_own(case):
    """8,704's geometry in small, a block of 4 keys standing for 128 lanes:
    272 keys = 68 blocks, scored, counted and flagged in pieces of 4 blocks
    (16 keys: the 512), the mask WRITTEN in key tiles of 17 blocks (68
    keys: the 2,176), so every fifth piece ends in the next tile. The
    dense mask of that layout is the piece-wide layout's and the
    reference's ``Sel`` bit for bit, ties included; the flags are the same
    array, and ``live_tiles`` reads the same two numbers from it."""
    rng = np.random.default_rng(47)
    s, heads, d = 272, 4, 16
    topk = {"random_scores": 24, "tied_scores": 24, "within_topk_is_dense": 512}[case]
    q = jnp.asarray(rng.standard_normal((heads, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((s, heads)), jnp.float32)
    if case == "tied_scores":  # every key one of five vectors: a query's scores tie in runs
        k, w = k[jnp.arange(s) % 5], jnp.abs(w)
    narrow, flags = sa.select_keys(q, k, w, topk=topk, block_q=8, block_k=16, mask_k=16)
    wide, wide_flags = sa.select_keys(q, k, w, topk=topk, block_q=8, block_k=16, mask_k=68)
    assert narrow.shape == (34, 17, 8, 16) and wide.shape == (34, 4, 8, 68)
    assert flags.shape == wide_flags.shape == (34, 17)
    got = np.asarray(sa.mask_to_dense(wide))
    np.testing.assert_array_equal(got, np.asarray(sa.mask_to_dense(narrow)))
    dots = jnp.einsum("htd,sd->ths", q, k, precision="highest")
    scores = jnp.sum(w[:, :, None] * jax.nn.relu(dots), axis=1) / np.sqrt(d)
    np.testing.assert_array_equal(got, np.asarray(ref_select(scores, jnp.arange(s), topk)))
    np.testing.assert_array_equal(got.sum(axis=1), np.minimum(np.arange(s) + 1, topk))
    np.testing.assert_array_equal(np.asarray(flags), np.asarray(wide_flags))
    np.testing.assert_array_equal(np.asarray(flags) != 0, np.asarray(narrow).any(axis=(2, 3)))
    live, causal = sa.live_tiles(wide_flags, s, stat_tile=16)
    assert causal == 17 * 18 // 2
    tiles = got.reshape(17, 16, 17, 16).any(axis=(1, 3))  # 16 x 16 tiles with a selected pair
    assert int(live) == int(tiles.sum()) == int(sa.live_tiles(flags, s, stat_tile=16)[0])
    if case == "tied_scores":
        best = np.flatnonzero(got[s - 1])
        assert len(set(best % 5)) == 1 and (best == best[0] + 5 * np.arange(24)).all()
    if case == "within_topk_is_dense":
        assert int(live) == causal


@pytest.mark.parametrize("s,pieces,want", [
    (8704, 512, 2176),    # 68 lane blocks: 17 of them, the widest under the limit
    # 268 = 4 x 67 lane blocks: the widest that divides is the pieces' 512, under half the limit, so
    # the tile does NOT divide: sixteen of 2,176 over keys padded to 34,816 (PR 68)
    (34304, 512, 2176),
    (17408, 512, 2176), (4352, 512, 2176), (2048, 512, 2048), (8704, 128, 2176),
    (96, 32, 32), (64, 32, 32), (272, 16, 16),  # no whole lane block: the pieces' own
])
def test_the_mask_s_key_tile_is_one_rule_of_the_shape(s, pieces, want):
    assert sa.mask_tile(s, pieces) == want
    if s == 34304:
        assert -(-s // want) * want == 34816 and sa.pick_tile(s, sa.MASK_TILE) == pieces
    else:
        assert s % want == 0


@pytest.mark.parametrize("s,want", [(2432, 1280), (2560, 1280), (6528, 2176), (4480, 1536),
                                    (1280, 1280), (2176, 2176)])
def test_a_tile_that_does_not_divide_pads_by_less_than_a_lane_block_a_tile(s, want):
    """Where no wide tile divides, the rule takes the narrowest whole number
    of lane blocks that covers ``s`` in as many tiles as the limit would
    (2,432 = 19 lane blocks: two tiles of 10, not one of 17 and a second
    nearly empty); a sequence under the limit is one tile as it was."""
    got = sa.mask_tile(s, 512)
    tiles = -(-s // got)
    assert got == want and got % 128 == 0 and tiles * got - s < 128 * tiles
    assert tiles == -(-s // sa.MASK_TILE) or s % got == 0


@pytest.mark.parametrize("case", ["random_scores", "tied_scores", "within_topk_is_dense"])
def test_a_mask_written_in_a_tile_that_does_not_divide_ends_in_zeros(case):
    """34,304's case in small: 256 keys scored, counted and flagged in
    sixteen pieces of 16, the mask WRITTEN in key tiles of 68 that do not
    divide them — four tiles, 272 keys, the last tile's last sixteen
    columns keys that do not exist (and every fifth piece ends in the next
    tile). Cropped to ``[S, S]`` the dense mask is the piece-wide layout's
    and the reference's ``Sel`` bit for bit, the tail is zeros, and the
    flags (the pieces' own, over the real keys) are the same array."""
    rng = np.random.default_rng(68)
    s, heads, d = 256, 4, 16
    topk = {"random_scores": 24, "tied_scores": 24, "within_topk_is_dense": 512}[case]
    q = jnp.asarray(rng.standard_normal((heads, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((s, heads)), jnp.float32)
    if case == "tied_scores":
        k, w = k[jnp.arange(s) % 5], jnp.abs(w)
    narrow, flags = sa.select_keys(q, k, w, topk=topk, block_q=8, block_k=16, mask_k=16)
    wide, wide_flags = sa.select_keys(q, k, w, topk=topk, block_q=8, block_k=16, mask_k=68)
    assert narrow.shape == (32, 16, 8, 16) and wide.shape == (32, 4, 8, 68)
    assert flags.shape == wide_flags.shape == (32, 16)
    got = np.asarray(sa.mask_to_dense(wide))
    assert got.shape == (s, 272) and not got[:, s:].any()
    np.testing.assert_array_equal(got[:, :s], np.asarray(sa.mask_to_dense(narrow)))
    dots = jnp.einsum("htd,sd->ths", q, k, precision="highest")
    scores = jnp.sum(w[:, :, None] * jax.nn.relu(dots), axis=1) / np.sqrt(d)
    np.testing.assert_array_equal(got[:, :s], np.asarray(ref_select(scores, jnp.arange(s), topk)))
    np.testing.assert_array_equal(got.sum(axis=1), np.minimum(np.arange(s) + 1, topk))
    np.testing.assert_array_equal(np.asarray(flags), np.asarray(wide_flags))
    assert [int(x) for x in sa.live_tiles(wide_flags, s, stat_tile=16)] == [
        int(x) for x in sa.live_tiles(flags, s, stat_tile=16)]


def test_the_two_indexers_differ_by_fields_and_keye_s_are_the_defaults():
    with open(os.path.join(REPO, "benchmark", "configs", "keye_vl2_prefill_epix10k2m.json")) as f:
        keye = decoder.DecoderConfig.from_mapping(json.load(f))
    plain = {f.name: f.default for f in dataclasses.fields(decoder.DecoderConfig)}
    assert (keye.indexer_rope_dim, keye.indexer_key_norm) == (0, "rms") == (
        plain["indexer_rope_dim"], plain["indexer_key_norm"])
    assert (keye.router_groups, keye.router_groups_kept) == (1, 1)
    assert not keye.selects_over_latent and keye.layer_stats == 4
    got = small(mapping())
    assert (got.indexer_rope_dim, got.indexer_key_norm, got.indexer_heads, got.topk) == (8, "layer", 4, 16)
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))["layers"][0]
    # the index queries read the query's low rank (24), the key and the weights the layer's input
    assert shapes["idx_wq"].shape == (24, 4 * 16) and shapes["idx_wk"].shape == (64, 16)
    assert shapes["idx_k_bias"].shape == (16,) and shapes["idx_ww"].shape == (64, 4)
    with pytest.raises(ValueError, match="per sequence"):
        p = decoder.init_params(got, jax.random.key(0), jnp.float32)["layers"][0]
        decoder.latent_attention(p, jnp.zeros((128, 64)), jnp.zeros((128, 4)), 2, got,
                                 jnp.zeros((64, 4)))


# ---------------------------------------------------------------------------
# the causal kernel under a mask, with a shared key part and values of another width
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,mq,bk,bq", [
    (96, 16, 32, 16), (96, 16, 32, 48), (96, 32, 16, 96), (96, 96, 96, 96), (96, 16, 48, 1088),
    # 8,704's geometry under a mask written 2,176 wide, a block of 4 keys for 128 lanes: 68
    # blocks, key tiles of 17, query tiles of 4 (17 of them, 44 pairs of tiles a head) or of
    # 2; one query tile over all four key tiles; two key tiles of 17 under query tiles of 1
    (272, 8, 68, 16), (272, 8, 68, 8), (272, 4, 68, 1088), (136, 4, 68, 4),
])
@pytest.mark.parametrize("rep", [1, 2])
@TURNS
def test_masked_causal_kernel_with_a_shared_key_part_and_values_of_another_width(s, mq, bk, bq,
                                                                                 rep, turn):
    rng = np.random.default_rng(mq + bk + rep)
    g, d, ds, dv = 2, 16, 8, 24
    h = g * rep
    q = jnp.asarray(rng.standard_normal((1, s, h * d)), jnp.float32) * 0.3
    qs = jnp.asarray(rng.standard_normal((1, s, h * ds)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((1, s, g * d)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((1, s, ds)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, g * dv)), jnp.float32)
    raw = qs
    if turn is not None:  # (the rest of the test on the query XLA turned)
        qs, tables = turned_by_xla(raw, turn, h)
    # a selection: each query's 12 best of random scores, itself among them or not
    dense = np.asarray(ref_select(jnp.asarray(rng.standard_normal((s, s)), jnp.float32),
                                  jnp.arange(s), 12))
    mask = jnp.asarray(dense.reshape(s // mq, mq, s // bk, bk).transpose(0, 2, 1, 3), jnp.int8)
    np.testing.assert_array_equal(np.asarray(sa.mask_to_dense(mask)), dense)
    got = sa.masked_gqa_attention(q, k, v, mask, num_kv_heads=g, block_q=bq, block_k=1088,
                                  q_shared=qs, k_shared=ks)
    if turn is not None:  # the same call on the float32 product and the tables
        by_xla, got = got, sa.masked_gqa_attention(
            q, k, v, mask, num_kv_heads=g, block_q=bq, block_k=1088, q_shared=raw, k_shared=ks,
            shared_turn=tables, shared_scale=turn)
        assert_the_kernel_s_turn_is_xla_s(got, by_xla, raw, qs, tables, turn, h, 3e-6)
    kh, vh = (jnp.repeat(x.reshape(1, s, g, -1), rep, axis=2) for x in (k, v))
    score = (jnp.einsum("bthd,bshd->bhts", q.reshape(1, s, h, d), kh, precision="highest")
             + jnp.einsum("bthd,bsd->bhts", qs.reshape(1, s, h, ds), ks, precision="highest"))
    score = jnp.where(dense, score, -jnp.inf)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(score, -1), vh, precision="highest")
    assert got.shape == (1, s, h * dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want.reshape(1, s, h * dv)), atol=3e-6)
    # every causal key selected: the maskless form's numbers
    full = jnp.asarray(np.tril(np.ones((s, s), np.int8)).reshape(
        s // mq, mq, s // bk, bk).transpose(0, 2, 1, 3))
    np.testing.assert_allclose(
        np.asarray(sa.masked_gqa_attention(q, k, v, full, num_kv_heads=g, block_q=bq,
                                           q_shared=qs, k_shared=ks)),
        np.asarray(sa.masked_gqa_attention(q, k, v, num_kv_heads=g, block_q=32, block_k=32,
                                           q_shared=qs, k_shared=ks)), atol=3e-6)


def test_latent_attention_runs_in_the_key_tile_the_rule_wrote_its_mask_in():
    """The operator end to end where the rule widens the tile: 384 tokens
    are three 128-lane blocks, the selection scores and flags them in
    pieces of one, ``mask_tile`` writes ONE key tile of three, and the
    attention under it runs in 32 x 384 (no configuration field says so)
    and is the reference's, with the statistics read from the pieces' flags."""
    m = mapping(index_topk=48)
    cfg = dataclasses.replace(small(m), kv_tile=128)
    s = 384
    p = loud(decoder.init_params(cfg, jax.random.key(5), jnp.float32))["layers"][0]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((s, 64)), jnp.float32)
    angles = decoder.rotary_angles(np.arange(s), cfg.rope_theta, 4, None, cfg.rope_yarn)
    with jax.default_matmul_precision("highest"):
        a = decoder.rms_norm(x, p["norm1"], 1e-6)
        c_q = decoder.rms_norm(a @ p["wq_a"], p["q_a_norm"], 1e-6)
        mask, flags = decoder._indexer(p, a, angles, cfg, q_from=c_q)
        got, live, causal = decoder.latent_attention(p, x, angles, 1, cfg, angles)
        want, sel = ref.latent_attention(p, ref.rms(x, p["norm1"], 1e-6), ref.sizes(m),
                                         jnp.float32, 32, with_sel=True)
    assert mask.shape == (24, 1, 16, 384) and flags.shape == (24, 3)
    np.testing.assert_array_equal(np.asarray(sa.mask_to_dense(mask)), np.asarray(sel))
    want = x + want
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4 * scale, rtol=0)
    assert (int(live), causal) == (1, 1)  # one 384 x 384 statistics tile


def test_a_mask_is_one_sequence_s_and_the_plain_form_keeps_its_own_shapes():
    z = jnp.zeros((2, 32, 32), jnp.float32)
    mask = jnp.ones((2, 1, 16, 32), jnp.int8)
    with pytest.raises(ValueError, match="one sequence"):  # a selection over a batch: ROADMAP R12
        sa.masked_gqa_attention(z, z, z, mask, num_kv_heads=2)
    with pytest.raises(ValueError, match="one sequence"):  # a mask of another length
        sa.masked_gqa_attention(z[:1], z[:1], z[:1], jnp.ones((1, 1, 16, 16), jnp.int8),
                                num_kv_heads=2)
    with pytest.raises(ValueError, match=r"\[B, S, \.\]"):  # [S, H*d] operands: no form takes them
        sa.masked_gqa_attention(z[0], z[0], z[0], mask, num_kv_heads=2)
    with pytest.raises(ValueError, match="one sequence"):  # a key tile too many past the sequence
        sa.masked_gqa_attention(z[:1], z[:1], z[:1], jnp.ones((2, 3, 16, 16), jnp.int8),
                                num_kv_heads=2)


# ---------------------------------------------------------------------------
# group-limited routing
# ---------------------------------------------------------------------------

def _reference_choice(s, by, **sizes):
    m = {"E": s.shape[1], "group_limit": True, **sizes}
    return np.asarray(ref.chosen_experts(jnp.asarray(s), jnp.asarray(by), m))


@pytest.mark.parametrize("case", ["random", "tied_groups_and_experts", "eight_groups_of_32"])
def test_group_limited_routing_is_the_reference_s_choice(case):
    rng = np.random.default_rng(13)
    t, e, groups, kept, k = 64, 16, 4, 2, 4
    if case == "eight_groups_of_32":  # the published router
        e, groups, kept, k = 256, 8, 4, 8
    probs = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((t, e)), jnp.float32))
    bias = jnp.asarray(rng.standard_normal(e) * 0.3, jnp.float32)
    if case == "tied_groups_and_experts":
        # two levels only: groups tie in their best-two sums and experts in their scores,
        # so the LOWER group and the LOWER index must win
        probs = jnp.asarray(rng.integers(1, 3, (t, e)) / 4.0, jnp.float32)
        bias = jnp.zeros(e, jnp.float32)
    ids, gates = moe.route_top_k(probs, k, True, select_bias=bias, gate_eps=1e-20, gate_scale=2.5,
                                 groups=groups, groups_kept=kept)
    want = _reference_choice(probs, bias, n_group=groups, topk_group=kept, k_e=k)
    got = np.zeros((t, e), bool)
    got[np.arange(t)[:, None], np.asarray(ids)] = True
    np.testing.assert_array_equal(got, want)
    assert (got.sum(axis=1) == k).all()
    # the chosen lie in at most `kept` groups, and some token's plain top k does not
    per = e // groups
    assert (np.asarray([len(set(row // per)) for row in np.asarray(ids)]) <= kept).all()
    plain, _ = moe.route_top_k(probs, k, True, select_bias=bias, gate_eps=1e-20, gate_scale=2.5)
    if case != "tied_groups_and_experts":
        assert (np.sort(np.asarray(plain), 1) != np.sort(np.asarray(ids), 1)).any()
    # weighted by the affinity WITHOUT the bias, over the chosen's sum, times the scaling factor
    chosen_p = np.take_along_axis(np.asarray(probs), np.asarray(ids), 1)
    np.testing.assert_allclose(np.asarray(gates), chosen_p / chosen_p.sum(1, keepdims=True) * 2.5,
                               rtol=1e-6)
    if case == "tied_groups_and_experts":
        # group scores are 0.5, 0.75 or 1.0: ties among four groups are the rule
        two = np.sort(np.asarray(probs).reshape(t, groups, per), -1)[..., -2:].sum(-1)
        assert all(len(set(row)) < groups for row in two)  # three levels, four groups: always a tie


def test_one_group_is_no_limit_and_gives_todays_ids_bit_for_bit():
    rng = np.random.default_rng(17)
    probs = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((64, 16)), jnp.float32))
    bias = jnp.asarray(rng.standard_normal(16) * 0.3, jnp.float32)
    for by in (None, bias):
        today = moe.route_top_k(probs, 4, True, select_bias=by, gate_eps=1e-20, gate_scale=2.5)
        one = moe.route_top_k(probs, 4, True, select_bias=by, gate_eps=1e-20, gate_scale=2.5,
                              groups=1, groups_kept=1)
        for a, b in zip(today, one):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the lowered program is the one it was: no group code on the path
        def lowered(**kw):
            return jax.jit(lambda p: moe.route_top_k(
                p, 4, True, select_bias=by, gate_eps=1e-20, gate_scale=2.5, **kw)).lower(
                    probs).as_text()
        assert lowered() == lowered(groups=1, groups_kept=1)
    kimi = os.path.join(REPO, "benchmark", "configs", "kimi_k2_prefill_epix10k2m.json")
    with open(kimi) as f:  # n_group = topk_group = 1 in the file: no limit
        got = decoder.DecoderConfig.from_mapping(json.load(f))
    assert (got.router_groups, got.router_groups_kept, got.indexer_heads) == (1, 1, None)


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _expert_layer(seed, t=64, d=32, width=16, experts=16, k=4):
    rng = np.random.default_rng(seed)

    def w(*shape, by=0.2):
        return jnp.asarray(rng.standard_normal(shape) * by, jnp.float32)

    p = {"router": w(d, experts, by=0.5), "router_bias": w(experts, by=0.3),
         "w_gate": w(experts, d, width), "w_up": w(experts, d, width), "w_down": w(experts, width, d),
         "shared_gate": w(d, width), "shared_up": w(d, width), "shared_down": w(width, d)}
    m = ref.sizes(mapping(n_routed_experts=experts, num_experts_per_tok=k))
    return p, w(t, d, by=1.0), m


def _routed(p, b, held):
    first, count = held
    return moe.dropless_moe(b, p["router"], p["w_gate"][first:first + count],
                            p["w_up"][first:first + count], p["w_down"][first:first + count],
                            k=4, num_experts=16, experts_held=held, scoring="sigmoid",
                            select_bias=p["router_bias"], gate_eps=1e-20, gate_scale=2.5,
                            groups=4, groups_kept=2)


def test_four_shares_of_a_group_each_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    p, b, m = _expert_layer(9)
    with jax.default_matmul_precision("highest"):
        parts, served = [], []
        for first in (0, 4, 8, 12):  # a share is one of the four groups
            y, tokens = _routed(p, b, (first, 4))
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
    # the group limit is in the sum: without it the layer is another
    assert np.abs(np.asarray(unlimited) - np.asarray(routed)).max() > 1e-2
    # every share WITH the shared expert would count it four times: not the layer
    assert np.abs(sum(part + shared for part in parts) - want).max() > 1e-2
    # and the reference, given one share, gives that share
    held = {k: (v[4:8] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p.items()}
    one, _ = ref.experts(held, b, {**m, "experts_held": (4, 4)}, jnp.float32)
    np.testing.assert_allclose(parts[1], np.asarray(one), atol=2e-5)


# ---------------------------------------------------------------------------
# the configuration's fourth spelling
# ---------------------------------------------------------------------------

def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3.2"]
    return row


def test_from_mapping_reads_the_catalog_row_s_config_verbatim():
    got = decoder.DecoderConfig.from_mapping(_catalog_row()["config"])
    assert (got.hidden_size, got.num_layers, got.num_heads, got.head_dim, got.rope_dim) == (
        7168, 61, 128, 192, 64)
    assert (got.q_lora_rank, got.kv_lora_rank, got.qk_nope_head_dim, got.qk_rope_head_dim,
            got.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (got.indexer_heads, got.indexer_head_dim, got.topk, got.indexer_rope_dim,
            got.indexer_key_norm) == (64, 128, 2048, 64, "layer")
    assert (got.num_experts, got.experts_held, got.experts_per_token, got.expert_width,
            got.shared_experts, got.num_dense_layers) == (256, (0, 256), 8, 2048, 1, 3)
    assert (got.router_groups, got.router_groups_kept, got.router_scoring, got.expert_bias,
            got.gate_eps, got.routed_scaling_factor) == (8, 4, "sigmoid", True, 1e-20, 2.5)
    yarn = got.rope_yarn
    assert (yarn.factor, yarn.original_positions, yarn.beta_fast, yarn.beta_slow) == (40, 4096, 32, 1)
    assert yarn.rotary_scale == 1.0 and abs(yarn.softmax_scale - 1.8739) < 1e-4
    # pairs 0-10 keep their frequency, 23-31 take it over 40, 11-22 blend (the file's `assumed`)
    plain = 10000.0 ** (-np.arange(32) / 32)
    ratio = yarn.inv_freq(10000.0, 32) / plain
    assert (ratio[:11] == 1.0).all() and np.allclose(ratio[23:], 1 / 40)
    assert ((ratio[11:23] < 1.0) & (ratio[11:23] > 1 / 40)).all()
    assert got.selects_over_latent and not got.holds_a_share and got.layer_stats == 8
    assert [got.layer_kind(i) for i in (0, 2, 3, 60)] == [
        (decoder.LATENT, False), (decoder.LATENT, False), (decoder.LATENT, True), (decoder.LATENT, True)]


def test_the_file_holds_the_catalog_s_numbers_unchanged_and_names_its_cuts():
    row = _catalog_row()
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["source"] == row["source_url"] and len(cfg["source"]) <= 200
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers", "first_k_dense_replace",
                                              "n_routed_experts", "vocab_size"}
    assert {k: row["config"][k] for k in differs} == {k: cfg["published"][k] for k in differs}
    assert "32 chips that share each layer" in cfg["deployment"] and "REDUNDANT" in cfg["deployment"]
    said = " ".join(cfg["assumed"])
    for departure in ("Hadamard", "FP8", "multi-token-prediction", "part of no cited deployment"):
        assert departure in said or departure in cfg["deployment"], departure
    got = decoder.DecoderConfig.from_mapping(cfg)
    assert (got.num_layers, got.num_dense_layers, got.num_experts, got.experts_held,
            got.vocab_size) == (6, 1, 256, (0, 8), 16384)
    assert got.holds_a_share and got.selects_over_latent and got.vocab_size % 128 == 0
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 3.82e9 < n < 3.84e9  # the file's 3.83 G parameters, 7.66 GB in bf16
    layer = shapes["layers"][1]
    assert layer["router"].shape == (7168, 256) and layer["w_gate"].shape == (8, 7168, 2048)
    assert layer["idx_wq"].shape == (1536, 64 * 128) and layer["wq_b"].shape == (1536, 128 * 192)
    assert cfg["sequence_tokens"] == 16 * (352 // 16) * (384 // 16) + cfg["prompt_tokens"] == 8704
    assert cfg["step_tokens"] == cfg["batch_size"] * cfg["sequence_tokens"] == 8704
    assert cfg["n_routed_experts"] == cfg["experts_held"][1] == cfg["published"]["n_routed_experts"] // 32
    # the rehearsal's size keeps every mechanism: a selection, a group limit, a share
    small_cfg = decoder.DecoderConfig.from_mapping({**cfg, **cfg["rehearse"]})
    assert small_cfg.selects_over_latent and small_cfg.holds_a_share
    assert (small_cfg.router_groups, small_cfg.router_groups_kept, small_cfg.topk) == (4, 2, 8)


# ---------------------------------------------------------------------------
# the two new counters, in snapshot() and under /metrics
# ---------------------------------------------------------------------------

def test_pair_counters_reach_the_snapshot_and_the_exposition():
    cfg = small(mapping(n_routed_experts=4, router_experts=16, experts_held=[0, 4]))
    _, snap, text = streamed(cfg, frames=3, batch=1)
    steps, s = 3, 2 * 2 * 14 + PROMPT
    assert snap["decoder_tokens_total"] == steps * s
    assert snap["attn_pairs_selected_total"] == steps * 3 * selected_pairs(s, 16)
    assert snap["attn_pairs_causal_total"] == steps * 3 * s * (s + 1) // 2
    assert snap["attn_tiles_causal_total"] == steps * 3
    assert 0 < snap["attn_tiles_live_total"] <= snap["attn_tiles_causal_total"]
    assert 0 < snap["expert_rows_held_total"] < snap["expert_rows_routed_total"] == steps * 2 * s * 4
    for name in decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS:
        assert f'psana_ray_{name}{{source="reader"}}' in text, name


# ---------------------------------------------------------------------------
# the cell and its counts (its manifest entries: tests/test_manifest_entries.py)
# ---------------------------------------------------------------------------

def test_the_dsv32_cell_follows_kimi_s_and_reports_the_host_path_as_the_decoders_do():
    cell = BENCH.cell(CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "saturated", "deepseek_v32_prefill_epix10k2m")
    config = BENCH.config(cell["config"])
    assert config["file"] == os.path.relpath(CONFIG, REPO) and len(cell["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                                 "vocab_size"]
    with open(CONFIG) as f:
        assert json.load(f)["transport"]["slots"] == 4


def test_dsv32_roofline_counts_at_the_published_sizes():
    from benchmark.roofline import decoder as counts
    from benchmark.roofline import deepseek_v32 as roofline
    from benchmark.roofline import kimi_k2

    assert counts.selected_pairs(8704, 2048) == 15_729_664 and counts.causal_pairs(8704) == 37_884_160
    fn, [shapes] = need(CELL, "deepseek_v32.sparse_latent_attention")
    attention = fn(**shapes)
    assert attention["flops"] == 15_729_664 * 128 * 2 * (192 + 128)  # 1.289 T a layer
    assert abs(attention["flops"] / 1e12 - 1.289) < 1e-3
    assert attention["flops"] / kimi_k2.latent_attention(1, 8704, 128, 128, 64, 128)["flops"] == \
        pytest.approx(0.4152, abs=1e-4)
    fn, [shapes] = need(CELL, "decoder.select_keys")
    assert fn(**shapes)["flops"] == 37_884_160 * 64 * 128 * 2  # 0.62 T a layer
    fn, [shapes] = need(CELL, "kimi_k2.held_products")
    held = fn(held_share=8 / 256, **shapes)
    assert held["call_sites"] == 15 and held["flops"] == 15 * 2 * 2176 * 7168 * 2048
    fn, [shapes] = need(CELL, "deepseek_v32.step")
    step = fn(**shapes)["flops"]
    assert abs(step / 1e12 - 44.3) < 0.1
    # the step's count is its parts': kimi's count at one frame, the selected pairs in the
    # causal pairs' place, and the indexer's projections and scores
    base = kimi_k2.step(1, 8704, 7168, 6, 1, 18432, 2048, 256, 8, 8, 1, 128, 1536, 512, 128, 64,
                        128, 16384, 256, 16)["flops"]
    indexer = 2 * 8704 * (1536 * 64 * 128 + 7168 * 128 + 7168 * 64) + 37_884_160 * 64 * 128 * 2
    causal = kimi_k2.latent_attention(1, 8704, 128, 128, 64, 128)["flops"]
    assert step == pytest.approx(base + 6 * (attention["flops"] - causal + indexer), rel=1e-12)
    assert roofline.step.__code__.co_argcount == len(shapes)


# ---------------------------------------------------------------------------
# the adapter, and the cell's rehearsal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lacks", ["indexer_rope_dim", "router_groups"])
def test_the_adapter_ends_the_run_where_the_package_lacks_the_mechanism(monkeypatch, lacks):
    from benchmark.programs import prefill_latent_selected

    older = dataclasses.make_dataclass(
        "Older", [(f.name, f.type, dataclasses.field(default=None))
                  for f in dataclasses.fields(decoder.DecoderConfig) if f.name != lacks], frozen=True)
    monkeypatch.setattr(decoder, "DecoderConfig", older)
    with pytest.raises(SystemExit) as e:
        prefill_latent_selected.Program({"name": "deepseek_v32_prefill_epix10k2m"}, 1, "", None)
    assert e.value.code not in (0, None) and lacks in str(e.value.code)


def test_the_cell_s_rehearsal_runs_the_served_path_and_is_correct():
    line, done = rehearse(CELL, seed=1, seconds=2, xla_flags=False, timeout=600)
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0 and line["metrics"] == {}
    assert line["cell"] == CELL and line["attempted"] > 0
    for counted in (("attn_pairs_selected_total", "attn_pairs_causal_total"),
                    ("expert_rows_held_total", "expert_rows_routed_total"),
                    ("expert_tokens_max_total", "expert_tokens_mean_total")):
        assert ratio_of(CELL, *counted) in line["would_report"], line["would_report"]
    assert "device_wait_ms.hit" in line["would_report"]
    assert "compiles inside the window 0" in done.stderr
