"""Grouped-query attention under a WINDOW and full attention in one trunk,
each layer with its own count of query heads, its layer type's rotary and a
sigmoid gate a head on its output (``models/decoder.py`` reading
Laguna-S-2.1's keys; ``parallel/sparse_attention.py``'s batched causal
kernel visiting a band's tiles alone) against the benchmark's plain
reference (``benchmark/reference/laguna_decoder.py``: a dense band mask) at
small sizes on the CPU; the expert layer at ten a token, the first k that
is no power of two; the shares of the expert layer; the new cell's
counters and counts."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from benchmark.reference import laguna_decoder as ref
from decoder_kit import Kit, embedded, inputs, loud, rehearse, share_of
from psana_ray_tpu.models import decoder
from psana_ray_tpu.ops import row_gather
from psana_ray_tpu.parallel import moe
from psana_ray_tpu.parallel import sparse_attention as sa
from test_manifest_entries import BENCH, need, ratio_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmark", "configs")
CONFIG = os.path.join(CONFIGS, "laguna_s21_prefill_epix10k2m.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "laguna_epix_saturated"
FULL, SLIDING = decoder.ATTENTION, decoder.SLIDING
# the controls' faults (benchmark/tests/laguna_controls.py), at this size's window of 16
FAULTS = {"no_window": {"window": 0}, "window_twice_as_wide": {"window": 32},
          "full_rotary_in_sliding": {"rotary_of": {FULL: FULL, SLIDING: FULL}},
          "sliding_rotary_in_full": {"rotary_of": {FULL: SLIDING, SLIDING: SLIDING}},
          "no_attention_factor": {"attention_factor": False}, "no_head_gate": {"attn_gate": False},
          "eight_of_ten_experts": {"k_e": 4}, "softmax_router": {"scoring": "softmax"},
          "no_shared_expert": {"shared": False}}
# the decoder cells the benchmark had before this one: configuration file -> its cell's suffix
OTHERS = {"keye_vl2_prefill_epix10k2m": "keye", "lfm2_8b_a1b_prefill_epix10k2m": "lfm2",
          "kimi_k2_prefill_epix10k2m": "kimi", "deepseek_v32_prefill_epix10k2m": "dsv32",
          "ling3_flash_prefill_epix10k2m": "ling3"}
ROPE = {FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 32,
               "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}}


def mapping(**over):
    """Laguna's Hugging Face keys at a small size: a full layer with a
    dense MLP, two windowed layers and a full one with experts; 6 and 4
    query heads of 16 over 2 key heads, a window of 16 of the 64 tokens; 16
    routed experts, 5 a token (no power of two), all held."""
    m = dict(
        model_type="laguna", hidden_size=64, num_hidden_layers=4,
        layer_types=[FULL, SLIDING, SLIDING, FULL], num_attention_heads=4,
        num_attention_heads_per_layer=[4, 6, 6, 4], num_key_value_heads=2, head_dim=16,
        sliding_window=16, rope_parameters=ROPE, gating="per-head", vocab_size=256,
        rms_norm_eps=1e-6, intermediate_size=96, mlp_only_layers=[0], num_experts=16,
        num_experts_per_tok=5, moe_intermediate_size=32, shared_expert_intermediate_size=32,
        norm_topk_prob=True, moe_routed_scaling_factor=2.5, moe_router_logit_softcapping=0,
        router_scoring="sigmoid", tie_word_embeddings=False, patch=8,
    )
    m.update(over)
    return m


KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=32, causal_kv_tile=32))  # 64 tokens in 32 x 32 tiles
small = KIT.small


# ---------------------------------------------------------------------------
# the windowed kernel against a dense softmax under the band
# ---------------------------------------------------------------------------

def band_softmax(q, k, v, g, window):
    """``q [B, S, H*d]``, ``k, v [B, S, G*d]`` -> ``[B, S, H*d]``: a dense
    softmax over ``t - window < j <= t``, query head ``h`` on key head ``h // (H/G)``."""
    b, s, hd = q.shape
    d = k.shape[2] // g
    rep = hd // (g * d)
    q, k, v = (u.astype(jnp.float32) for u in (q, k, v))
    score = jnp.einsum("bsgrd,btgd->bgrst", q.reshape(b, s, g, rep, d), k.reshape(b, s, g, d))
    t = jnp.arange(s)
    open_ = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
    prob = jax.nn.softmax(jnp.where(open_, score, -jnp.inf), axis=-1)
    return jnp.einsum("bgrst,btgd->bsgrd", prob, v.reshape(b, s, g, -1)).reshape(b, s, -1)


def qkv(seed, b, s, g, rep, d):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((b, s, heads * d)), jnp.float32)
            for heads in (g * rep, g, g)]


# (S, window, query tile, the asked key tile — not a windowed call's to take —, the key window's
# rows): the window under a tile, a tile, over a tile, over S, S itself, of one key; S no multiple
# of the window; a window that is two query tiles, two and a half (its lower edge crosses TWO
# tiles), two and one key (the lower edge at the first tile's diagonal); a query tile that holds
# the window, and the whole sequence. Wherever the sequence is longer than the key window its
# first query tiles start at key 0 and close what lies past their diagonal
BANDS = {"window_under_the_tile": (64, 5, 16, 16, 32), "window_is_the_tile": (64, 16, 16, 16, 32),
         "window_over_the_tile": (64, 24, 16, 16, 48), "window_over_the_sequence": (48, 100, 16, 16, 48),
         "window_is_the_sequence": (48, 48, 16, 16, 48),
         "the_query_s_own_key_alone": (48, 1, 16, 16, 16),
         "sequence_no_multiple_of_the_window": (80, 24, 16, 16, 48),
         "window_of_two_query_tiles": (64, 16, 8, 32, 24), "window_of_two_and_a_half": (64, 20, 8, 32, 32),
         "window_of_two_and_a_key": (64, 17, 8, 32, 24),
         "wide_query_tile": (64, 20, 32, 8, 64), "one_tile": (48, 7, 48, 48, 48)}


def _pallas_call(fn, *args):
    (call,) = (e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns if e.primitive.name == "pallas_call")
    return call.params


@pytest.mark.parametrize("case", sorted(BANDS))
@pytest.mark.parametrize("batch", [1, 2])
def test_the_windowed_kernel_is_a_dense_softmax_under_the_band(case, batch):
    s, window, bq, bk, keys = BANDS[case]
    g, rep, d = 2, 3, 16
    q, k, v = qkv(len(case), batch, s, g, rep, d)
    got = sa._causal_attention(q, k, v, g, bq, bk, True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(band_softmax(q, k, v, g, window)), atol=2e-5)
    # ONE grid step a query tile, against ONE window of keys that follows the diagonal
    call = _pallas_call(lambda q, k, v: sa._causal_attention(q, k, v, g, bq, bk, True, window=window), q, k, v)
    assert call["grid_mapping"].grid == (batch, g, s // bq) and sa.band_keys(s, bq, window) == keys
    assert [m.block_aval.shape for m in call["grid_mapping"].block_mappings[1:3]] == [(keys, d)] * 2
    # and it is another result than full causal attention wherever the window binds
    full = sa._causal_attention(q, k, v, g, bq, bk, True)
    assert (float(jnp.abs(got - full).max()) > 1e-3) == (window < s)
    # a sequence's rows do not depend on its neighbour
    if batch == 2:
        alone = sa._causal_attention(q[1:], k[1:], v[1:], g, bq, bk, True, window=window)
        np.testing.assert_array_equal(np.asarray(got[1:]), np.asarray(alone))


# the two forms the cells serve, small: (key heads, heads a group, head, values' width, S, query tile,
# window): laguna's nine heads of 128 a group, token-major, turned and gated by the kernel (three
# parts of three heads wherever the floor lets them: here `cut` asks); phi4flash's two half-heads
# of 64 over values of 128, head-major, no rotary and no gate
FORMS = {"laguna_nine_turned_and_gated": (2, 9, 128, 128, 96, 16, 32),
         "laguna_window_no_whole_tiles": (2, 9, 128, 128, 96, 16, 40),
         "phi4flash_half_heads_of_64": (3, 2, 64, 128, 64, 8, 16),
         "phi4flash_sequence_under_the_window": (3, 2, 64, 128, 32, 8, 48)}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_served_form_s_windowed_call_is_the_band_in_one_pass_and_its_parts_are_its_rows(form):
    """The windowed call in the cells' two forms against the plain float32
    band (``t - window < j <= t``): the outputs are held to the reference, not
    to the parent's bits (one softmax pass: no ``alpha``, one rounding chain
    shorter); a stacked group's rows in PARTS are the rows of the one product,
    to the bit; and ``causal_steps``' three counts are the grid the call is
    lowered with and the score products its body writes."""
    g, rep, d, dv, s, bq, window = FORMS[form]
    b, turned = 2, form.startswith("laguna")
    rng = np.random.default_rng(len(form))
    q, k = (jnp.asarray(np.round(rng.standard_normal((b, s, heads * d)) * 16) / 16, jnp.float32)
            for heads in (g * rep, g))
    v = jnp.asarray(rng.standard_normal((b, s, g * dv)), jnp.bfloat16)
    extra, scale = {}, d ** -0.5
    if turned:  # q and k float32 and unturned, the two tables, a gate a (token, head)
        angles = jnp.tile(decoder.rotary_angles(np.arange(s), 10000.0, d // 2), (b, 1))
        tables = tuple(jnp.round(t * 256) / 256 for t in decoder.turn_tables(angles, d))
        gate = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((b, s, g * rep)), jnp.float32))
        extra = dict(turn=tables, turn_width=d, q_scale=scale, out_gate=gate)

        def turn(x, heads, by=1.0):  # `_turned_head`'s arithmetic, rounded once as the kernel rounds
            x = x.reshape(b * s, heads, d)
            cos, sin = (t[:, None, :] for t in tables)
            return ((x * cos + jnp.roll(x, d // 2, -1) * sin) * by).astype(jnp.bfloat16).reshape(b, s, -1)

        want = band_softmax(turn(q, g * rep, scale), turn(k, g), v, g, window)
        want = (want.astype(jnp.bfloat16).astype(jnp.float32).reshape(b, s, g * rep, dv)
                * gate[..., None]).reshape(b, s, -1)
    else:
        q, k = (q * scale).astype(jnp.bfloat16), k.astype(jnp.bfloat16)
        want = band_softmax(q, k, v, g, window)

    def attend(cut):
        return lambda q, k, v: sa._causal_attention(q, k, v, g, bq, bq, True, window=window, cut=cut, **extra)

    got = attend(1)(q, k, v)
    assert got.dtype == jnp.bfloat16 and got.shape == (b, s, g * rep * dv)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=2e-2, rtol=2e-2)
    assert float(jnp.abs(got.astype(jnp.float32) - want).mean()) < 2e-3
    parts = 3 if rep == 9 else 2
    np.testing.assert_array_equal(np.asarray(attend(parts)(q, k, v), np.float32), np.asarray(got, np.float32))
    # the lowered call: ONE grid step a query tile, a body of ONE branch, two products a part
    for cut in (1, parts):
        call = _pallas_call(attend(cut), q, k, v)
        assert call["grid_mapping"].grid == (b, g, s // bq)
        text = str(call["jaxpr"])
        assert text.count("dot_general") == 2 * cut and "cond[" not in text and "exp " in text
        assert text.count("exp ") == cut  # one pass: a part's exponentials, and no alpha


@pytest.mark.parametrize("cell", ["laguna", "phi4flash"])
def test_causal_steps_counts_the_grid_a_cell_s_windowed_call_is_lowered_with(cell):
    """At the published sizes (2 x 8,704 tokens, traced and not run):
    ``causal_steps`` from what the call is given, against the ``pallas_call``
    the call lowers to — laguna's nine turned heads a group: 2 x 8 x 34 = 544
    grid steps where the band's tiles took 1,056, three parts a step;
    phi4flash's two half-heads: 2 x 10 x 34, one part (0.79 MB a part of
    two: under the floor)."""
    b, s, window = 2, 8704, 512
    g, rep, d, dv = (8, 9, 128, 128) if cell == "laguna" else (10, 2, 64, 128)
    f32, bf16 = jnp.float32, jnp.bfloat16
    S = jax.ShapeDtypeStruct
    operands = [S((b, s, g * rep * d), f32 if cell == "laguna" else bf16),
                S((b, s, g * d), f32 if cell == "laguna" else bf16), S((b, s, g * dv), bf16)]
    if cell == "laguna":  # the two tables and the gate a (token, head)
        operands += [S((b * s, d), f32), S((b * s, d), f32), S((b, s, g * rep), f32)]

    def fn(q, k, v, *turn_and_gate):
        extra = {}
        if turn_and_gate:
            extra = dict(turn=turn_and_gate[:2], turn_width=d, q_scale=d ** -0.5, out_gate=turn_and_gate[2])
        return sa.windowed_gqa_attention(q, k, v, window=window, num_kv_heads=g, block_q=1088, block_k=1088,
                                         interpret=False, **extra)

    call = _pallas_call(fn, *operands)
    tiles, steps, parts = sa.causal_steps(b, s, g, rep, d, dv, block_q=1088, block_k=1088, window=window,
                                          turned=cell == "laguna")
    grid = call["grid_mapping"].grid
    assert grid == (b, g, s // 256) and int(np.prod(grid)) == steps == tiles == {"laguna": 544, "phi4flash": 680}[cell]
    assert parts == steps * {"laguna": 3, "phi4flash": 1}[cell]
    # the key window: 768 rows where two tiles of 512 met 1,024, Element-addressed (no whole blocks)
    keys = call["grid_mapping"].block_mappings[1]
    assert keys.block_aval.shape == (768, d) and pl.Element(768) in keys.block_shape
    assert str(call["jaxpr"]).count("dot_general") == 2 * parts // steps  # ONE branch, two products a part
    assert call["name"] == "windowed_gqa_attention"


def _transposes(fn, *args):
    return sum(e.primitive.name == "transpose" for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns)


@pytest.mark.parametrize("kind", ["full", "windowed"])
@pytest.mark.parametrize("rep", [3, 9])
def test_grouped_heads_of_whole_lane_blocks_are_read_where_their_products_wrote_them(rep, kind):
    """Heads of 128, ``rep`` of them on each of 2 key heads, a batch of two
    (the cell's layers have 6 and 9 a group): k, v and o are column blocks of
    the token-major arrays, a group's ``rep`` output heads ONE block that the
    kernel writes head by head; q alone is handed over head-major with the
    batch in the rows (``[G, rep, B*S, d]``: ONE transpose traced, which
    compiled is a layout of the rotary's own fusion and no op:
    ``tests/test_chip_compile_layers.py``). The numbers are the dense reference's
    and those of the head-major addressing (taken where a head is no whole
    number of lane blocks: the same operands with eight zero columns a
    head); with a gate a (token, head) the output is that times the gate,
    to the bit, in both addressings."""
    b, s, g, d, window = 2, 64, 2, 128, 20
    q, k, v = qkv(rep + len(kind), b, s, g, rep, d)
    q = q * 0.1
    gate = jax.nn.sigmoid(qkv(rep, b, s, g, rep, 1)[0])  # [B, S, H]

    def attend(q, k, v, **gated):
        if kind == "windowed":  # 16 rows against a key window of 48, the sequence's first tiles at key 0
            return sa.windowed_gqa_attention(q, k, v, window=window, num_kv_heads=g, block_q=16,
                                             block_k=16, **gated)
        return sa.masked_gqa_attention(q, k, v, num_kv_heads=g, block_q=16, block_k=16, **gated)

    assert _transposes(attend, q, k, v) == 1
    got = attend(q, k, v)
    assert got.shape == (b, s, g * rep * d)
    want = band_softmax(q, k, v, g, window if kind == "windowed" else s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)
    assert _transposes(functools.partial(attend, out_gate=gate), q, k, v) == 1
    np.testing.assert_array_equal(
        np.asarray(attend(q, k, v, out_gate=gate)),
        np.asarray((got.reshape(b, s, g * rep, d) * gate[..., None]).reshape(b, s, -1)))

    def padded(x):
        return jnp.pad(x.reshape(b, s, -1, d), ((0, 0),) * 3 + ((0, 8),)).reshape(b, s, -1)

    assert _transposes(attend, padded(q), padded(k), padded(v)) == 4  # q, k, v in and o back
    major = attend(padded(q), padded(k), padded(v)).reshape(b, s, g * rep, d + 8)
    np.testing.assert_array_equal(np.asarray(major[..., d:]), 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(major[..., :d].reshape(b, s, -1)),
                               atol=3e-6)
    np.testing.assert_array_equal(
        np.asarray(attend(padded(q), padded(k), padded(v), out_gate=gate)),
        np.asarray((major * gate[..., None]).reshape(b, s, -1)))
    # in bfloat16 it is `decoder.gated`'s arithmetic: the rounded output, times the float32
    # scalar, rounded again
    q16, k16, v16 = (u.astype(jnp.bfloat16) for u in (q, k, v))
    rounded = attend(q16, k16, v16).reshape(b, s, g * rep, d)
    np.testing.assert_array_equal(
        np.asarray(attend(q16, k16, v16, out_gate=gate).astype(jnp.float32)),
        np.asarray((rounded * gate[..., None]).astype(jnp.bfloat16).reshape(b, s, -1)
                   .astype(jnp.float32)))


# the layer types' rotaries at heads of 128: (turned width, YaRN's factor on the turned part)
ROTARIES = {"full_rotary": (128, 1.0), "partial_rotary_64_of_128_yarn": (64, 1.4852030263919618)}
# heads a group -> (key heads, S, query tile, key tile): the looped reader's shape (16 heads on 16 key
# heads at S 2,304 in 768 x 768: as many key heads as heads, three square tiles a sequence), a full
# layer's (six a group in 512 x 1,088: the key tile twice the query tile and more) and a windowed
# layer's (nine a group in 256 x 512, the window the key tile). In every one a key tile past the
# first is FIRST met by a query tile that is not the sequence's first, and met again by later ones
GROUPS = {1: (4, 96, 32, 32), 6: (2, 96, 16, 32), 9: (2, 128, 16, 32)}


@pytest.mark.parametrize("rep", sorted(GROUPS))
@pytest.mark.parametrize("band", ["maskless", "window"])
@pytest.mark.parametrize("rotary", sorted(ROTARIES))
def test_the_kernel_turns_float32_heads_to_the_bit_as_the_projections_did(rotary, band, rep):
    """The kernel given q and k FLOAT32 and unturned, as ``W_q``'s and
    ``W_k``'s products wrote them, with the step's two tables
    (``decoder.turn_tables(angles, 128)``) against the kernel given what
    ``_projections`` made of them until PR 63 (``_turn_leading``, the
    softmax scale on q, ONE rounding to bf16), over a batch of two, with the
    gate of a (token, head) where a group has more than one head (laguna's
    layers; the looped reader has none). In two steps, because XLA's CPU
    backend contracts ``x*c + r*s`` inside the interpreted kernel's one
    compiled body and not op by op (the last float32 bit of one turned
    component in 30,000, which flips a bf16 rounding now and then: the TPU
    has no such instruction): (1) the tables' arithmetic on the whole
    arrays, op by op, IS ``_turn_leading``'s, to the last float32 bit, by the
    real tables; (2) where a contraction changes nothing — components of
    eight bits and tables rounded to eight — the kernel that turns equals
    the kernel given that arithmetic's result, rounded once, bit for bit; by
    the real tables it is within two roundings on a handful of rows. Two
    planted faults come out as OTHER results: the keys left unturned, and
    the sine's sign flipped."""
    width, factor = ROTARIES[rotary]
    g, s, bq, bk = GROUPS[rep]
    b, d, h, half = 2, 128, g * rep, width // 2
    window = bk if band == "window" else None
    q, k, v = (jnp.round(u * 32) / 32 for u in qkv(rep + width, b, s, g, rep, d))
    v = v.astype(jnp.bfloat16)
    gate = {"out_gate": jax.nn.sigmoid(qkv(rep, b, s, g, rep, 1)[0])} if rep > 1 else {}
    angles = jnp.tile(decoder.rotary_angles(np.arange(s), 10000.0, half), (b, 1))
    scale, lane = 0.1147, jnp.arange(d)

    def by_tables(x, heads, tables, by=1.0):  # `_turned_head`'s arithmetic, op by op
        x = x.reshape(b * s, heads, d)
        cos, sin = (t[:, None, :] for t in tables)
        rolled = jnp.where(lane < half, jnp.roll(x, d - half, -1), jnp.roll(x, half, -1))
        return ((x * cos + rolled * sin) * jnp.where(lane < width, factor, 1.0) * by
                ).reshape(b, s, -1)

    def attend(q, k, **turn):
        return sa._causal_attention(q, k, v, g, bq, bk, True, window=window, **gate, **turn)

    tables = decoder.turn_tables(angles, d)
    assert all(t.shape == (b * s, d) and t.dtype == jnp.float32 for t in tables)
    for x, heads in ((q, h), (k, g)):  # (1)
        np.testing.assert_array_equal(
            np.asarray(by_tables(x, heads, tables)),
            np.asarray(decoder._turn_leading(x.reshape(b * s, heads, d), angles, width, factor)
                       .reshape(b, s, -1)))
    coarse = tuple(jnp.round(t * 256) / 256 for t in tables)  # (2)
    kernel = dict(turn=coarse, turn_width=width, turn_scale=factor, q_scale=scale)
    got = attend(q, k, **kernel)
    assert got.dtype == jnp.bfloat16 and got.shape == (b, s, h * d)
    want = attend(by_tables(q, h, coarse, scale).astype(jnp.bfloat16),
                  by_tables(k, g, coarse).astype(jnp.bfloat16))
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    real = np.asarray(attend(q, k, **{**kernel, "turn": tables}), np.float32)
    by_xla = np.asarray(attend(by_tables(q, h, tables, scale).astype(jnp.bfloat16),
                               by_tables(k, g, tables).astype(jnp.bfloat16)), np.float32)
    np.testing.assert_allclose(real, by_xla, atol=2e-2)
    assert np.mean(real != by_xla) < 5e-3
    # a sequence's rows do not depend on its neighbour (the keys' scratch is a sequence's own)
    alone = sa._causal_attention(q[1:], k[1:], v[1:], g, bq, bk, True, window=window,
                                 **{n: u[1:] for n, u in gate.items()},
                                 **{**kernel, "turn": tuple(t[s:] for t in coarse)})
    np.testing.assert_array_equal(np.asarray(got[1:], np.float32), np.asarray(alone, np.float32))
    # planted faults: each is another result, far past a rounding
    unturned = attend(by_tables(q, h, coarse, scale).astype(jnp.bfloat16), k.astype(jnp.bfloat16))
    flipped = attend(q, k, **{**kernel, "turn": (coarse[0], -coarse[1])})
    for fault in (unturned, flipped):
        assert float(jnp.abs(fault.astype(jnp.float32) - got.astype(jnp.float32)).max()) > 0.05
    with pytest.raises(ValueError, match="float32 heads of whole lane blocks"):
        attend(q.astype(jnp.bfloat16), k, **kernel)


@pytest.mark.parametrize("kind", [FULL, SLIDING])
def test_a_layer_of_whole_lane_heads_hands_the_kernel_what_its_products_wrote(kind, monkeypatch):
    """``decoder._attention`` at heads of 128 (the rule's side the small
    trunks of this file, heads of 16, never take): ``_projections`` hands q
    and k on float32, unturned and unscaled, the kernel gets the layer
    type's tables, width and scales, and the layer is the layer that
    ``_projections`` turned (the rule switched off), within the
    contraction's rounding (the test above); at heads of 16 q and k leave
    turned and rounded, as ever."""
    cfg = small(mapping(head_dim=128))
    i = [FULL, SLIDING, SLIDING, FULL].index(kind)
    p = loud(decoder.init_params(cfg, jax.random.key(2)))["layers"][i]
    batch, s = 2, 64
    x = jnp.asarray(np.random.default_rng(3).standard_normal((batch * s, 64)), jnp.bfloat16)
    if kind == SLIDING:
        angles = decoder.rotary_angles(np.arange(s), cfg.sliding_rope_theta, cfg.head_dim // 2)
    else:
        angles = decoder.rotary_angles(np.arange(s), cfg.rope_theta, cfg.rope_dim // 2,
                                       yarn=cfg.rope_yarn)
    angles = jnp.tile(angles, (batch, 1))
    assert angles.shape[1] == (64 if kind == SLIDING else 32) and decoder._kernel_turns(cfg, angles)
    windowed = (True,) if kind == SLIDING else ()
    a, q, k, v, gate = decoder._projections(p, x, angles, cfg, *windowed)
    assert q.dtype == k.dtype == jnp.float32 and v.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(q), np.asarray(decoder._mm(a, p["wq"])))
    np.testing.assert_array_equal(np.asarray(k), np.asarray(decoder._mm(a, p["wk"])))
    window = cfg.sliding_window if kind == SLIDING else 0
    got = decoder._attention(p, x, angles, None, batch, cfg, window)[0]
    monkeypatch.setattr(decoder, "_kernel_turns", lambda cfg, angles: False)
    jax.clear_caches()  # `_attention` calls `_projections` jitted: traced under the rule above
    a, q, k, v, gate = decoder._projections(p, x, angles, cfg, *windowed)
    assert q.dtype == k.dtype == jnp.bfloat16
    want = decoder._attention(p, x, angles, None, batch, cfg, window)[0]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=2e-2)
    assert np.mean(got != want) < 5e-3 and float(np.abs(want - np.asarray(x, np.float32)).max()) > 0.5
    jax.clear_caches()
    narrow = small(mapping())
    assert not decoder._kernel_turns(narrow, angles) and not decoder._kernel_turns(cfg, None)


def test_the_kernel_s_tiles_are_the_band_s_alone_and_the_statistics_count_them():
    # 8,704 tokens in 512 x 512 tiles: 153 at or below the diagonal, 33 that meet a band of 512
    assert len(sa._band_tiles(8704, 512, 512)) == 153 == sa.causal_tile_count(8704)
    assert len(sa._band_tiles(8704, 512, 512, 512)) == 33 == sa.band_tile_count(8704, 512)
    assert sa._band_tiles(2048, 512, 512, 512) == [(0, 0), (1, 0), (1, 1), (2, 1), (2, 2), (3, 2), (3, 3)]
    assert sa._band_tiles(2048, 512, 512, 514)[:4] == [(0, 0), (1, 0), (1, 1), (2, 0)]  # two keys more
    assert sa._band_tiles(1024, 512, 256, 1) == [(0, 0), (0, 1), (1, 2), (1, 3)]
    assert sa.band_tile_count(8704, 10 ** 6) == 153 and sa.band_tile_count(64, 16, 16) == 7
    with pytest.raises(ValueError, match="window"):
        sa._causal_attention(*qkv(0, 1, 16, 1, 1, 16), 1, 8, 8, True, window=0)


def test_the_tiles_follow_from_the_group_s_rows_and_the_window():
    # the steps measured before the rule keep their tiles
    assert sa.causal_tiles(8704, 4, 1088, 1088) == (1088, 1088)  # lfm2: 4 heads of 64 a group
    assert sa.causal_tiles(8704, 1, 1088, 1088) == (1088, 1088)  # kimi, ling3: a head alone
    # six heads of 128 a group: the stacked score tile within its bytes
    bq, bk = sa.causal_tiles(8704, 6, 1088, 1088)
    assert 6 * bq * bk * 4 <= sa.SCORE_TILE_BYTES < 6 * 1088 * 1088 * 4 and 8704 % bq == 0
    # nine under a window of 512: a query tile of its share of the window, and as the "key tile" the
    # rows of the ONE key window a query tile is run against (`band_keys`: the window and the tile)
    assert sa.causal_tiles(8704, 9, 1088, 1088, 512) == (256, 768) and sa.BAND_QUERY_TILE == 0.5
    assert not hasattr(sa, "BAND_TILES")  # (the key extent is no share of the window: it IS window + bq)
    assert sa.causal_tiles(64, 3, 32, 32, 16) == (8, 24) == sa.causal_tiles(64, 3, 8, 8, 16)
    assert sa.causal_tiles(8704, 2, 1088, 1088, 512) == (256, 768)  # phi4flash's two half-heads a group
    assert [sa.band_keys(8704, bq, 512) for bq in (128, 256, 512)] == [640, 768, 1024]
    assert sa.band_keys(8704, 128, 300) == 512 and sa.band_keys(64, 16, 100) == 64  # ceil(299 / 128) + 1 tiles
    # where the kernel turns heads of 128 the float32 query block, the stacked scratch and the
    # tables' rows are counted in the same bytes: the served tiles stand (laguna's two, ouro's)
    assert sa.causal_tiles(8704, 6, 1088, 1088, None, 128) == (512, 1088)
    assert sa.causal_tiles(8704, 9, 1088, 1088, 512, 128) == (256, 768)
    assert sa.causal_tiles(2304, 1, 768, 768, None, 128) == (768, 768)
    assert sa.causal_tiles(8704, 9, 1088, 1088, None, 128) == (256, 1088)  # and there they bind


# ---------------------------------------------------------------------------
# the trunk against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", ["all_16", "experts_0_to_3_of_16"])
def test_the_windowed_trunk_matches_the_reference_at_all_positions_of_a_batch_of_two(held):
    m = mapping()
    cfg = small(m)
    params = loud(decoder.init_params(cfg, jax.random.key(3), jnp.float32))
    if held == "experts_0_to_3_of_16":  # a share: a quarter, as the cell's 64 of 256
        m.update(num_experts=4, router_experts=16, experts_held=[0, 4])
        cfg, params = small(m), share_of(params, 0, 4)
    patches, ids = inputs(3, batch=2)
    sizes = ref.sizes(m)
    with jax.default_matmul_precision("highest"):
        x, got, stats = KIT.trunk_of(params, patches, ids, cfg)
        want_x, want = KIT.reference_of(params, patches, ids, sizes)
    for a, b in ((x, want_x), (got, want)):
        scale = float(jnp.sqrt(jnp.mean(b ** 2)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4 * scale, rtol=0)
    # a windowed step counts the band's pairs (ten statistics), a holder of a quarter all thirteen
    names = decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS
    assert cfg.rows_go_ahead == (held != "all_16") and cfg.has_window
    names += (decoder.LINEAR_STATS + decoder.AHEAD_STATS) if cfg.rows_go_ahead else ()
    assert len(stats) == len(names) == (13 if cfg.rows_go_ahead else 10)
    got_stats = dict(zip(names, (float(v) for v in stats)))
    # 64 tokens in one statistics tile: every layer meets it
    assert got_stats["attn_tiles_causal_total"] == got_stats["attn_tiles_live_total"] == 2 * 4
    # two windowed layers: sum_t min(t + 1, 16) of 64 x 65 / 2 a sequence
    assert got_stats["attn_pairs_selected_total"] == 2 * 2 * (16 * 17 // 2 + 48 * 16)
    assert got_stats["attn_pairs_causal_total"] == 2 * 2 * (64 * 65 // 2)
    assert (got_stats["decoder_tokens_total"], got_stats["decoder_sequences_total"]) == (128, 2)
    assert got_stats["expert_tokens_mean_total"] == 3 * 128 * 5 / 16
    assert got_stats["expert_rows_routed_total"] == 3 * 128 * 5
    assert (got_stats["expert_rows_held_total"] == 3 * 128 * 5) == (held == "all_16")
    if cfg.rows_go_ahead:
        assert got_stats["linear_attn_tokens_total"] == got_stats["linear_attn_chunks_total"] == 0
        assert 0 < got_stats["expert_rows_ahead_total"] <= got_stats["expert_rows_held_total"]


def test_the_step_counts_the_tiles_the_band_meets_at_the_cell_s_shapes():
    """The counters' constants, from the shapes alone (nothing runs): a
    windowed layer's live tiles and pairs at 2 x 8,704 tokens."""
    with open(CONFIG) as f:
        file = json.load(f)
    cfg = decoder.DecoderConfig.from_mapping(file)
    s, b, w = file["sequence_tokens"], file["batch_size"], cfg.sliding_window
    kinds = [cfg.layer_kind(i)[0] for i in range(cfg.num_layers)]
    live = sum(b * (sa.band_tile_count(s, w) if op == SLIDING else sa.causal_tile_count(s))
               for op in kinds)
    causal = len(kinds) * b * sa.causal_tile_count(s)
    assert (live, causal) == (1314, 2754) and round(100 * live / causal, 1) == 47.7
    band, pairs = 512 * 513 // 2 + (s - 512) * 512, s * (s + 1) // 2
    assert (band, pairs) == (4325632, 37884160) and round(100 * band / pairs, 2) == 11.42
    assert kinds.count(SLIDING) * b * band == 51907584 and cfg.layer_stats + 2 == 13


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_with_a_control_s_fault_in_it_is_another_trunk(fault):
    x, same = KIT.trunk(5, jit=False)[0], KIT.reference(5)[0]  # made once for the nine cases
    want = KIT.reference(5, **FAULTS[fault])[0]
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    assert float(jnp.abs(x - same).max()) < 1e-3 * scale
    assert float(jnp.abs(x - want).max()) > 1e-2 * scale  # what a control puts in is seen


def test_a_sequence_of_the_batch_does_not_read_its_neighbour_s_keys():
    cfg = small(mapping(), causal_q_tile=16, causal_kv_tile=16)
    params = KIT.params(7)
    patches, ids = inputs(7, batch=2)
    run = jax.jit(lambda p, x: decoder.trunk(p, x, np.arange(64), cfg, 2)[0])
    x = run(params, embedded(params, patches, ids))
    moved = run(params, embedded(params, patches[::-1], ids))
    np.testing.assert_array_equal(np.asarray(x[:64]), np.asarray(moved[64:]))
    np.testing.assert_array_equal(np.asarray(x[64:]), np.asarray(moved[:64]))


# ---------------------------------------------------------------------------
# a rotary a layer type, a head count a layer, the gate
# ---------------------------------------------------------------------------

def test_a_full_layer_s_partial_yarn_rotary_is_the_reference_s_written_out_form():
    cfg = small(mapping())
    t = 64
    for op, pairs, width in ((FULL, 4, 8), (SLIDING, 8, 16)):
        want, turned, factor = ref.rotary(t, ROPE[op], 16)
        if op == FULL:
            got = decoder.rotary_angles(np.arange(t), cfg.rope_theta, cfg.rope_dim // 2,
                                        yarn=cfg.rope_yarn)
            assert factor == pytest.approx(cfg.rope_yarn.rotary_scale, abs=1e-12) and factor > 1.4
            assert cfg.rope_yarn.softmax_scale == 1.0  # mscale_all_dim 0: the score's scale is 16^-0.5
        else:
            got = decoder.rotary_angles(np.arange(t), cfg.sliding_rope_theta, cfg.head_dim // 2)
            assert factor == 1.0
        assert got.shape == (t, pairs) and turned == width
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
        # the leading `width` of a head turn, times the factor; the rest passes as it was
        x = jnp.asarray(np.random.default_rng(1).standard_normal((t, 3, 16)), jnp.float32)
        out = decoder._turn_leading(x, got, 2 * got.shape[-1], factor)
        np.testing.assert_array_equal(np.asarray(out[..., width:]), np.asarray(x[..., width:]))
        np.testing.assert_allclose(np.asarray(out[..., :width]),
                                   np.asarray(ref.rotate(x[..., :width], want) * factor), atol=1e-5)
    # YaRN blends: at 64 positions over an original 16 some pair is slowed, and none is sped up
    plain = 500000.0 ** (-np.arange(4) / 4)
    blended = cfg.rope_yarn.inv_freq(500000.0, 4)
    assert np.all(blended <= plain * (1 + 1e-12)) and np.any(blended < plain * 0.5)
    with pytest.raises(ValueError, match="attention_factor"):
        decoder.DecoderConfig.from_mapping(mapping(rope_parameters={
            **ROPE, FULL: {**ROPE[FULL], "attention_factor": 1.2}}))
    with pytest.raises(ValueError, match="sliding layer's rotary"):
        decoder.DecoderConfig.from_mapping(mapping(rope_parameters={
            **ROPE, SLIDING: {**ROPE[SLIDING], "partial_rotary_factor": 0.5}}))


def test_wq_wo_and_the_gate_are_a_layer_s_own_heads_wide_and_the_gate_is_one_scalar_a_head():
    cfg = small(mapping())
    assert [cfg.heads(i) for i in range(4)] == [4, 6, 6, 4] and cfg.attn_gate == "head_wise"
    assert not cfg.qk_norm and cfg.rope_partial_dim == 8 and cfg.rope_dim == 8
    params = decoder.init_params(cfg, jax.random.key(0), jnp.float32)
    for p, heads in zip(params["layers"], (4, 6, 6, 4)):
        assert p["wq"].shape == (64, heads * 16) and p["wo"].shape == (heads * 16, 64)
        assert p["w_attn_gate"].shape == (64, heads) and p["wk"].shape == p["wv"].shape == (64, 32)
        assert "q_norm" not in p and "k_norm" not in p
    assert "router" not in params["layers"][0] and "shared_up" in params["layers"][1]
    # one layer alone is the reference's, full and windowed, and the gate is in it
    params = loud(params)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((64, 64)), jnp.float32)
    sizes = ref.sizes(mapping())
    with jax.default_matmul_precision("highest"):
        for i, op in ((0, FULL), (1, SLIDING)):
            p = params["layers"][i]
            table = (decoder.rotary_angles(np.arange(64), cfg.sliding_rope_theta, 8) if op == SLIDING
                     else decoder.rotary_angles(np.arange(64), cfg.rope_theta, 4, yarn=cfg.rope_yarn))
            got, live, causal = decoder._attention(p, x, table, None, 1, cfg,
                                                   16 if op == SLIDING else 0)
            a = ref.rms(x, p["norm1"], 1e-6)
            want = x + ref.attention(p, a, op, cfg.heads(i), sizes, jnp.float32, 16)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
            ungated = x + ref.attention(p, a, op, cfg.heads(i), {**sizes, "attn_gate": False},
                                        jnp.float32, 16)
            assert float(jnp.abs(got - ungated).max()) > 1e-2
            assert (live, causal) == (1, 1)
    with pytest.raises(ValueError, match="num_attention_heads_per_layer"):
        decoder.DecoderConfig.from_mapping(mapping(num_attention_heads_per_layer=[4, 6]))
    with pytest.raises(ValueError, match="not built"):
        decoder.DecoderConfig.from_mapping(mapping(gating="per-element"))
    with pytest.raises(ValueError, match="sliding_window"):
        decoder.DecoderConfig.from_mapping(mapping(sliding_window=0))


# ---------------------------------------------------------------------------
# ten a token: the first k that is no power of two
# ---------------------------------------------------------------------------

def test_ten_of_256_are_lax_top_k_s_ten_and_their_gates_sum_to_the_scale():
    rng = np.random.default_rng(11)
    probs = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((96, 256)), jnp.float32))
    probs = probs.at[:, 17].set(probs[:, 3])  # a tie a row: the lower index first
    ids, gates = moe.route_top_k(probs, 10, True, gate_eps=1e-20, gate_scale=2.5)
    want_p, want_ids = jax.lax.top_k(probs, 10)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(
        want_p / jnp.sum(want_p, axis=-1, keepdims=True) * 2.5), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(gates.sum(axis=-1)), 2.5, rtol=1e-6)
    # ten terms summed by halves: (t0 + t8) + t4 ... every term once
    terms = [jnp.float32(2 ** i) for i in range(10)]
    assert float(moe._sum_by_halves(terms)) == 1023.0


@pytest.mark.parametrize("case", ["within_the_pass", "overflowing_into_the_loop"])
def test_a_quarter_s_holder_takes_ten_a_token_through_the_pass_and_the_counted_rows_back(case):
    """``_held_rows_ahead`` and ``sum_counted_rows`` at k = 10 against a
    plain gather-sum: 64 tokens x 10 slots of 256-wide rows (bf16: whole
    words), 4 of 16 experts held."""
    rng = np.random.default_rng(13)
    t, d, width, experts, k = 64, 256, 32, 16, 10

    def w(*shape, by=0.2):
        return jnp.asarray(rng.standard_normal(shape) * by, jnp.float32)

    router, x = w(d, experts, by=0.1), w(t, d, by=1.0)
    if case == "overflowing_into_the_loop":  # the held four are among everybody's ten: 256 rows
        router, x = router.at[0, :4].set(2.0), x.at[:, 0].set(4.0)
    x = x.astype(jnp.bfloat16)
    w_gate, w_up, w_down = (w(experts, d, width), w(experts, d, width), w(experts, width, d))
    held = slice(0, 4)
    y, tokens = moe.dropless_moe(
        x, router, *(u[held].astype(jnp.bfloat16) for u in (w_gate, w_up, w_down)), k=k,
        num_experts=experts, experts_held=(0, 4), scoring="sigmoid", gate_eps=1e-20, gate_scale=2.5)
    ahead = moe.rows_ahead(t * k, 4, experts)
    assert ahead == 240 and (int(tokens.sum()) > ahead) == (case == "overflowing_into_the_loop")
    # the plain way: every token's held choices, gathered and summed in float32
    logits = jnp.dot(x, router.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    probs = jax.nn.sigmoid(logits)
    top_p, ids = jax.lax.top_k(probs, k)
    gates = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20) * 2.5
    want = np.zeros((t, d), np.float64)
    xf = np.asarray(x, np.float64)
    for e in range(4):
        wg, wu, wd = (np.asarray(u[e].astype(jnp.bfloat16), np.float64) for u in (w_gate, w_up, w_down))
        h = xf @ wg
        h = h / (1 + np.exp(-h)) * (xf @ wu)
        gate_e = np.asarray(jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1), np.float64)
        want += gate_e[:, None] * (np.asarray(jnp.asarray(h, jnp.bfloat16), np.float64) @ wd)
    assert int(tokens.sum()) == int((np.asarray(ids) < 4).sum())
    np.testing.assert_allclose(np.asarray(y, np.float64), want, atol=0.03 * np.abs(want).max())


def test_the_counted_rows_of_ten_slots_a_token_are_a_plain_gather_sum():
    rng = np.random.default_rng(17)
    n, t, k, d = 96, 40, 10, 768  # 3,072's lane chunks in small: 6 of them, three words a row
    out = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
    back = jnp.asarray(rng.permutation(t * k).reshape(t, k) % 160, jnp.int32)  # some past n, some past the limit
    gates = jnp.asarray(rng.random((t, k)), jnp.float32)
    limit = 70
    got = row_gather.sum_counted_rows(out, back, limit, gates, interpret=True)
    counts = np.asarray(back) < limit
    rows = np.asarray(out.astype(jnp.float32))[np.minimum(np.asarray(back), n - 1)]
    want = np.zeros((t, d), np.float32)
    for j in range(k):  # ascending, in float32, as the kernel adds them
        want = want + np.where(counts[:, j, None], rows[:, j] * np.asarray(gates)[:, j, None], 0.0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    assert counts.sum() > 0 and (~counts).sum() > 0


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _expert_layer(seed, t=64, d=32, width=16, experts=32, k=10):
    rng = np.random.default_rng(seed)

    def w(*shape, by=0.2):
        return jnp.asarray(rng.standard_normal(shape) * by, jnp.float32)

    p = {"router": w(d, experts, by=0.5), "w_gate": w(experts, d, width),
         "w_up": w(experts, d, width), "w_down": w(experts, width, d),
         "shared_gate": w(d, width), "shared_up": w(d, width), "shared_down": w(width, d)}
    m = ref.sizes(mapping(num_experts=experts, num_experts_per_tok=k))
    return p, w(t, d, by=1.0), m


def test_four_quarters_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    p, b, m = _expert_layer(9)
    with jax.default_matmul_precision("highest"):
        parts, served = [], []
        for first in (0, 8, 16, 24):  # a share is a quarter of the experts, as the cell's 64 of 256
            y, tokens = moe.dropless_moe(
                b, p["router"], p["w_gate"][first:first + 8], p["w_up"][first:first + 8],
                p["w_down"][first:first + 8], k=10, num_experts=32, experts_held=(first, 8),
                scoring="sigmoid", gate_eps=1e-20, gate_scale=2.5)
            parts.append(np.asarray(y, np.float64))
            served.append(int(np.asarray(tokens).sum()))
        shared = np.asarray(decoder._dense_mlp(
            {"w_gate": p["shared_gate"], "w_up": p["shared_up"], "w_down": p["shared_down"]}, b))
        routed, chosen = ref.experts(p, b, m, jnp.float32)
        want = np.asarray(routed + ref.shared_expert(p, b, jnp.float32))
    assert sum(served) == 64 * 10 and np.asarray(chosen).sum() == 64 * 10  # every slot, once
    assert min(np.abs(part).max() for part in parts) > 0 and np.abs(shared).max() > 0
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    assert np.abs(sum(part + shared for part in parts) - want).max() > 1e-2  # counted four times: no
    held = {k: (v[8:16] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p.items()}
    one, _ = ref.experts(held, b, {**m, "experts_held": (8, 8)}, jnp.float32)
    np.testing.assert_allclose(parts[1], np.asarray(one), atol=2e-5)


# ---------------------------------------------------------------------------
# the configuration file, the catalog, the other readers
# ---------------------------------------------------------------------------

def _catalog_row(name="Laguna-S-2.1"):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == name]
    return row


def _file(name=None):
    with open(os.path.join(CONFIGS, name + ".json") if name else CONFIG) as f:
        return json.load(f)


def test_from_mapping_reads_the_published_keys():
    published = _catalog_row()["config"]
    got = decoder.DecoderConfig.from_mapping({**published, "router_scoring": "sigmoid"})
    assert (got.hidden_size, got.num_layers, got.num_heads, got.num_kv_heads, got.head_dim) == (
        3072, 48, 48, 8, 128)
    assert (got.sliding_window, got.rope_partial_dim, got.rope_dim, got.rope_theta,
            got.sliding_rope_theta, got.qk_norm, got.attn_gate) == (
        512, 64, 64, 500000.0, 10000.0, False, "head_wise")
    assert got.rope_yarn == decoder.Yarn(128.0, 8192, 32.0, 1.0, 1.0, 0.0)
    assert got.rope_yarn.rotary_scale == pytest.approx(1.4852030263919618, abs=1e-9)
    assert (got.num_experts, got.experts_held, got.experts_per_token, got.expert_width,
            got.shared_experts, got.num_dense_layers, got.intermediate_size) == (
        256, (0, 256), 10, 1024, 1, 1, 12288)
    assert (got.router_scoring, got.expert_bias, got.gate_eps, got.routed_scaling_factor,
            got.router_groups, got.norm_topk_prob) == ("sigmoid", False, 1e-20, 2.5, 1, True)
    kinds = [got.layer_kind(i)[0] for i in range(48)]
    assert kinds.count(FULL) == 12 and kinds.count(SLIDING) == 36 and kinds[:5] == [
        FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert {got.heads(i) for i, op in enumerate(kinds) if op == FULL} == {48}
    assert {got.heads(i) for i, op in enumerate(kinds) if op == SLIDING} == {72}
    assert got.has_window and not got.holds_a_share and got.layer_stats == 8
    # the other reading of the router is one key apart
    other = decoder.DecoderConfig.from_mapping({**published, "router_scoring": "softmax"})
    assert (other.router_scoring, other.gate_eps) == ("softmax", 0.0)
    with pytest.raises(ValueError, match="layer_types"):
        decoder.DecoderConfig.from_mapping({**published, "layer_types": published["layer_types"][:9]})
    with pytest.raises(ValueError, match="soft cap"):
        decoder.DecoderConfig.from_mapping({**published, "moe_router_logit_softcapping": 30})
    with pytest.raises(ValueError, match="mlp_only_layers"):
        decoder.DecoderConfig.from_mapping({**published, "mlp_only_layers": [1]})


def test_the_file_holds_the_catalog_s_numbers_unchanged_and_names_its_cuts():
    row, cfg = _catalog_row(), _file()
    assert cfg["source"] == row["source_url"] and len(cfg["source"]) <= 200
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types", "mlp_layer_types",
        "gating_types", "num_attention_heads_per_layer"}
    for k in ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer"):
        assert cfg[k] == row["config"][k][:9]  # the per-layer lists, cut with the depth
    for k in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert cfg["published"][k] == row["config"][k]
    assert "4 chips of a v5e host" in cfg["deployment"] and "NO cited deployment" in cfg["deployment"]
    said = " ".join(cfg["assumed"])
    for reading in ("sigmoid affinities", "softmax over the 256", "shared_expert_gate",
                    "no RMS norm on a head's query and key", "turned half only",
                    "the whole score's scale", "linear patch embedding", "[0, 25,088)"):
        assert reading in said, reading
    got = decoder.DecoderConfig.from_mapping(cfg)
    assert (got.num_layers, got.num_experts, got.experts_held, got.vocab_size) == (9, 256, (0, 64), 25088)
    assert got.holds_a_share and got.rows_go_ahead and got.layer_stats + 2 == 13  # NO new length
    assert cfg["num_experts"] == cfg["experts_held"][1] == 64 and cfg["vocab_size"] % 128 == 0
    assert cfg["step_tokens"] == cfg["batch_size"] * cfg["sequence_tokens"] == 17408
    # the floors: a whole period, at least four layers after the dense one, 8 experts, 1/8 of the vocabulary
    assert cfg["layer_types"][1:5] == [SLIDING] * 3 + [FULL] and len(cfg["layer_types"]) - 1 >= 4
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]
    # weights, recounted: 5.69 G parameters
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert round(count / 1e9, 3) == 5.693
    small_cfg = decoder.DecoderConfig.from_mapping({**cfg, **cfg["rehearse"]})
    assert small_cfg.has_window and small_cfg.rows_go_ahead and small_cfg.experts_per_token == 5


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_five_readers_have_nothing_of_what_this_one_brought(name):
    cfg = _file(name)
    got = decoder.DecoderConfig.from_mapping(cfg)
    assert (got.sliding_window, got.heads_per_layer, got.rope_partial_dim, got.sliding_rope_theta,
            got.qk_norm) == (0, (), 0, 0.0, True)
    assert not got.has_window and SLIDING not in got.layer_types
    assert got.attn_gate == ("head_wise" if OTHERS[name] == "ling3" else "")
    assert {got.heads(i) for i in range(got.num_layers)} == {got.num_heads}
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    for i, layer in enumerate(shapes["layers"]):
        if got.layer_kind(i)[0] == FULL:  # grouped-query attention keeps its per-head norms, ungated
            assert "q_norm" in layer and "k_norm" in layer and "w_attn_gate" not in layer


# ---------------------------------------------------------------------------
# the cell and its counts (its manifest entries: tests/test_manifest_entries.py)
# ---------------------------------------------------------------------------

def test_the_laguna_cell_follows_ling3_s_and_reports_the_host_path_as_the_decoders_do():
    cell = BENCH.cell(CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (1, "saturated", "laguna_s21_prefill_epix10k2m")
    config = BENCH.config(cell["config"])
    assert config["file"] == os.path.relpath(CONFIG, REPO)
    assert max(len(cell["why"]), len(config["why"])) <= 200
    cfg = _file()
    assert cfg["transport"]["slots"] == 16 and cfg["batch_size"] == 2
    assert cfg["trace_names"]["window_kernel"] == "windowed_gqa_attention"  # the pallas_call's own name
    assert cfg["trace_names"]["attention_kernel"] == "masked_gqa_attention"
    assert cfg["trace_names"]["step"] == "jit_laguna_step"


def test_laguna_roofline_counts_at_the_published_sizes():
    def count(function, **more):  # as the cell's file asks for it
        fn, [shapes] = need(CELL, function)
        return fn(**shapes, **more)

    full = count("laguna.full_attention")
    assert full["flops"] == 2 * 37884160 * 48 * 512 and round(full["flops"] / 1e12, 3) == 1.862
    band = count("laguna.windowed_attention")
    assert band["flops"] == 2 * 4325632 * 72 * 512 and round(band["flops"] / 1e12, 3) == 0.319
    assert band["bytes"] == 2 * 17408 * 128 * (2 * 72 + 2 * 8)  # q, o, k, v once
    # the yardstick does not know the kernel's tiles: a tile as wide as the window meets twice the band
    visited = 33 * 512 * 512
    assert 1.9 < visited / 4325632 < 2.1
    held = count("laguna.held_products", held_share=0.25)
    assert held["call_sites"] == 24 and held["flops"] == 24 * 2 * 43520 * 3072 * 1024
    step = count("laguna.step")
    assert round(step["flops"] / 1e12, 1) == 38.7
    rows, d = 17408, 3072
    by_hand = (2 * 8448 * 2 * 256 * d + 2 * 2 * d * 25088
               + 3 * 2 * rows * d * (2 * 48 * 128 + 2 * 8 * 128 + 48) + 3 * full["flops"]
               + 6 * 2 * rows * d * (2 * 72 * 128 + 2 * 8 * 128 + 72) + 6 * band["flops"]
               + 6 * rows * d * 12288
               + 8 * (6 * rows * d * (1024 + 1024 * 10 * 64 / 256) + 2 * rows * d * 256))
    assert step["flops"] == pytest.approx(by_hand, rel=1e-12)
    # attention with its projections is most of the step
    attention = by_hand - 6 * rows * d * 12288 - 8 * (
        6 * rows * d * (1024 + 1024 * 10 * 64 / 256) + 2 * rows * d * 256)
    assert 0.64 < attention / by_hand < 0.67


@pytest.mark.parametrize("lacks", ["sliding_window", "heads_per_layer"])
def test_the_adapter_ends_the_run_where_the_package_lacks_the_mechanism(monkeypatch, lacks):
    from benchmark.programs import prefill_windowed

    fields = [f for f in dataclasses.fields(decoder.DecoderConfig) if f.name != lacks]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: fields)
    cfg = _file()
    cfg.update(cfg["rehearse"])
    with pytest.raises(SystemExit, match=lacks):
        prefill_windowed.Program(cfg, 1, "", None)


def test_the_adapter_ends_the_run_where_the_file_counts_other_experts_than_it_holds():
    from benchmark.programs import prefill_windowed

    cfg = _file()
    cfg.update(cfg["rehearse"], num_experts=8)
    with pytest.raises(SystemExit, match="experts_held"):
        prefill_windowed.Program(cfg, 1, "", None)


def test_the_cell_s_rehearsal_runs_the_served_path_is_correct_and_reports_its_counters():
    line, _ = rehearse(CELL, seed=2)
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0 and line["cell"] == CELL
    counted = (("attn_tiles_live_total", "attn_tiles_causal_total"),
               ("attn_pairs_selected_total", "attn_pairs_causal_total"),
               ("expert_rows_held_total", "expert_rows_routed_total"),
               ("expert_rows_ahead_total", "expert_rows_held_total"),
               ("expert_tokens_max_total", "expert_tokens_mean_total"))
    for name in (*(ratio_of(CELL, *counters) for counters in counted), "ring_depth.hit",
                 # the start's own account (PR 55), in every cell as setup_s is
                 "startup_trace_s", "startup_lower_s", "startup_cache_load_s", "startup_compile_s",
                 "startup_cache_misses", "startup_rest_s"):
        assert name in line["would_report"], name
