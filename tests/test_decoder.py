"""The config-driven decoder (``models/decoder.py``), its sparse attention
and dropless experts, against the benchmark's plain reference
(``benchmark/reference/keye_decoder.py``) at small sizes on the CPU, and
the benchmark's reader for the counters it feeds."""

import functools
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
import decoder_kit
from benchmark.reference import keye_decoder as ref
from decoder_kit import PROMPT, Kit, streamed
from psana_ray_tpu.models import decoder
from psana_ray_tpu.ops import row_gather
from psana_ray_tpu.parallel import moe
from psana_ray_tpu.parallel import sparse_attention as sa
from psana_ray_tpu.parallel.moe import dropless_moe
from test_manifest_entries import need

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PANELS, ROWS, COLS = 2, 2, 14  # 56 patches + 8 prompt ids = 64 tokens


def mapping(**over):
    """The Hugging Face keys of a small decoder with every mechanism on."""
    m = dict(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, vocab_size=256, rms_norm_eps=1e-6, rope_theta=1e7,
        rope_scaling={"mrope_section": [4, 6, 6]},
        sa_config=dict(indexer_num_heads=4, indexer_head_dim=16, indexer_num_kv_heads=1,
                       topk=16, q_chunk_size=16, kv_chunk_size=32),
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, norm_topk_prob=True,
        intermediate_size=96,
    )
    m.update(over)
    return m


small = Kit(mapping, tiles=dict(q_tile=16, attn_q_tile=32)).small  # tiles that cut 64 tokens into several


def frame_of(seed):
    """The seed's one frame of patches, its prompt ids and the tokens' places."""
    (patches,), ids = decoder_kit.inputs(seed, patches=PANELS * ROWS * COLS)
    return patches, ids, decoder.frame_positions(PANELS, ROWS, COLS, PROMPT)


def all_logits(cfg, params, patches, ids, pos):
    x, stats = decoder.trunk(params, decoder.embed(params, patches, ids), pos, cfg)
    return decoder.logits_of(params, x, cfg), stats


# ---------------------------------------------------------------------------
# (a) the package's trunk against the reference, float32, all positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["indexer_experts", "dense_attention", "dense_mlp"])
def test_trunk_matches_reference_at_all_positions(variant):
    m = mapping()
    if variant == "dense_attention":
        m.pop("sa_config")
    if variant == "dense_mlp":
        m.update(num_experts=0, num_experts_per_tok=0)
    cfg = small(m)
    params = decoder.init_params(cfg, jax.random.key(3), jnp.float32)
    patches, ids, pos = frame_of(3)
    with jax.default_matmul_precision("highest"):
        got, stats = jax.jit(lambda p: all_logits(cfg, p, patches, ids, pos))(params)
        want = ref.forward(params, patches, ids, pos, m, block=16)
    assert got.shape == (64, 256)
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4 * scale, rtol=0)
    live, causal = float(stats[2]), float(stats[3])
    assert causal == 2 * 1 and 0 < live <= causal  # one 64 x 64 statistics tile a layer


# ---------------------------------------------------------------------------
# (b) Sel
# ---------------------------------------------------------------------------

def _selection(q_idx, k_idx, w_idx, topk, block_q=16, block_k=32):
    mask, live = sa.select_keys(jnp.transpose(q_idx, (1, 0, 2)), k_idx, w_idx, topk=topk,
                                block_q=block_q, block_k=block_k)
    np.testing.assert_array_equal(np.asarray(live) != 0, np.asarray(mask).any(axis=(2, 3)))
    s, d = k_idx.shape
    dots = jnp.einsum("thd,sd->ths", q_idx, k_idx, precision="highest")
    scores = jnp.sum(w_idx[:, :, None] * jax.nn.relu(dots), axis=1) / np.sqrt(d)
    return np.asarray(sa.mask_to_dense(mask)), np.asarray(ref.select(scores, jnp.arange(s), topk))


@pytest.mark.parametrize("case", ["longer_than_topk", "within_topk_is_dense", "tied_scores"])
def test_selection_is_sel(case):
    rng = np.random.default_rng(11)
    s, heads, d = 96, 4, 16
    q = jnp.asarray(rng.standard_normal((s, heads, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((s, heads)), jnp.float32)
    topk = {"longer_than_topk": 16, "within_topk_is_dense": 128, "tied_scores": 8}[case]
    if case == "tied_scores":
        # every key one of three vectors: each query's scores tie in runs
        # of 32, and the 8 selected must be the EARLIEST of the best run
        k = k[jnp.arange(s) % 3]
        w = jnp.abs(w)
    got, want = _selection(q, k, w, topk)
    np.testing.assert_array_equal(got, want)
    counts = got.sum(axis=1)
    np.testing.assert_array_equal(counts, np.minimum(np.arange(s) + 1, topk))
    if case == "within_topk_is_dense":
        np.testing.assert_array_equal(got, np.tril(np.ones((s, s), bool)))
    if case == "tied_scores":
        t = s - 1
        best = np.flatnonzero(got[t])
        assert len(set(best % 3)) == 1 and (best == best[0] + 3 * np.arange(8)).all()


# ---------------------------------------------------------------------------
# (b') the attention under Sel: grouped heads of whole lane blocks, a key tile that need not divide
# ---------------------------------------------------------------------------

def _masked_grouped_call(seed, s=640, g=2, rep=4, d=128, topk=48):
    rng = np.random.default_rng(seed)
    h = g * rep
    q = jnp.asarray(rng.standard_normal((1, s, h * d)), jnp.float32) * 0.1
    k, v = (jnp.asarray(rng.standard_normal((1, s, g * d)), jnp.float32) for _ in range(2))
    index = (jnp.asarray(rng.standard_normal((4, s, 32)), jnp.float32),
             jnp.asarray(rng.standard_normal((s, 32)), jnp.float32),
             jnp.asarray(rng.standard_normal((s, 4)), jnp.float32))
    return q, k, v, functools.partial(sa.select_keys, *index, topk=topk, block_q=64, block_k=128)


@pytest.mark.parametrize("block_q", [64, 128, 320])
def test_a_selection_s_attention_runs_in_a_key_tile_that_does_not_divide_the_keys(block_q):
    """Keye's call in small (PR 68): 640 keys, four heads of 128 a group,
    the mask written in tiles of 256 — three of them, 768 keys, the last
    128 columns keys that do not exist. The batched causal body under that
    mask (k and v padded with zeros to the whole tiles, a group's query
    tile read as ONE token-major block and stacked in the kernel) is the
    dense softmax over the selected pairs, and the same call at a tile that
    divides (128) to float32 rounding: the running softmax meets a row's
    keys in other tiles, nothing else differs."""
    q, k, v, select = _masked_grouped_call(68)
    s, g, rep, d = 640, 2, 4, 128
    mask, flags = select(mask_k=256)
    assert mask.shape == (10, 3, 64, 256) and flags.shape == (10, 5)
    sel = np.asarray(sa.mask_to_dense(mask))
    assert not sel[:, s:].any()
    sel = sel[:, :s]
    np.testing.assert_array_equal(sel.sum(axis=1), np.minimum(np.arange(s) + 1, 48))
    got = sa.masked_gqa_attention(q, k, v, mask, num_kv_heads=g, block_q=block_q)
    kh, vh = (jnp.repeat(x.reshape(1, s, g, d), rep, axis=2) for x in (k, v))
    score = jnp.einsum("bthd,bshd->bhts", q.reshape(1, s, g * rep, d), kh, precision="highest")
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(jnp.where(sel, score, -jnp.inf), -1), vh,
                      precision="highest").reshape(1, s, -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    dividing = sa.masked_gqa_attention(q, k, v, select(mask_k=128)[0], num_kv_heads=g, block_q=block_q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dividing), atol=2e-6)


def test_a_group_s_query_tile_is_one_token_major_block_stacked_in_the_kernel():
    """What the call traces to where a group's heads are whole lane blocks
    under a mask: no transpose of q (XLA's head-major copy: 0.28 GB and 1.6
    ms a layer at keye's sizes), two pads (k and v, to the mask's whole key
    tiles), and a kernel whose scratch holds the stacked query tile beside
    the running maximum, sum and accumulator. Heads of 16 (no lane block)
    keep their head-major operands and a tile that divides pads nothing."""
    q, k, v, select = _masked_grouped_call(7)

    def ops(fn, *args):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        call = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
        names = [str(e.params.get("name", e.primitive.name)) for e in jaxpr.eqns]  # (`jnp.pad` is a jit)
        scratch = call.params["jaxpr"].invars[-call.params["grid_mapping"].num_scratch_operands:]
        return names.count("transpose"), names.count("_pad"), [tuple(v.aval.shape) for v in scratch]

    attend = functools.partial(sa.masked_gqa_attention, num_kv_heads=2, block_q=128)
    assert ops(attend, q, k, v, select(mask_k=256)[0]) == (
        0, 2, [(512, 1), (512, 1), (512, 128), (512, 128)])
    assert ops(attend, q, k, v, select(mask_k=128)[0])[:2] == (0, 0)
    narrow = (q[..., :128], k[..., :32], v[..., :32])  # eight heads of 16 on two
    transposes, pads, scratch = ops(attend, *narrow, select(mask_k=256)[0])
    assert transposes == 4 and pads == 2 and len(scratch) == 3


def test_keye_s_call_takes_the_pair_table_s_grid_steps():
    """``causal_steps`` at keye's shapes: 34,304 queries in tiles of 256 (eight
    stacked heads of 2,176 float32 scores a row: 17.8 MB of the 20 a score
    tile may take; 512 rows would be 35.6) against sixteen key tiles of
    2,176 — 1,128 pairs at or below the diagonal a group, 4,512 grid steps a
    layer where the rectangular grid took 4 x 134 x 67 = 35,912."""
    s = 34304
    tiles = (sa.pick_tile(s, 128), sa.mask_tile(s, sa.pick_tile(s, 512)))
    assert tiles == (128, 2176)
    pairs = len(sa._band_tiles(s, 256, 2176))
    assert pairs == 1128 == sum(-(-(i + 1) * 256 // 2176) for i in range(s // 256))
    # (and since PR 75 a grid step's eight stacked heads are eight parts: `parts_a_step`)
    assert sa.causal_steps(1, s, 4, 8, 128, 128, block_q=256, mask_tiles=tiles) == (4 * pairs, 4 * pairs, 32 * pairs)
    assert sa.causal_steps(1, s, 4, 8, 128, 128, block_q=512, mask_tiles=tiles) == (4 * pairs, 4 * pairs, 32 * pairs)
    assert sa._masked_query_tile(s, 512, 128, 8 * 2176) == 256  # what fits, not what was asked
    assert sa._masked_query_tile(s, 512, 128, 8 * 1088) == 512
    assert sa._masked_query_tile(8704, 1088, 128, 2176) == 512  # dsv32's, a head alone: as it was


# ---------------------------------------------------------------------------
# (c) multimodal rotary
# ---------------------------------------------------------------------------

def test_mrope_is_three_section_rotation():
    rng = np.random.default_rng(5)
    sections, theta, s = (4, 6, 6), 1e7, 12
    pos = rng.integers(0, 50, (s, 3))
    x = rng.standard_normal((s, 2, 32))
    got = decoder.rotate(jnp.asarray(x, jnp.float32),
                         decoder.rotary_angles(pos, theta, 16, sections))
    want = np.empty_like(x)
    for t in range(s):
        for i in range(16):
            comp = 0 if i < 4 else (1 if i < 10 else 2)  # t, h, w
            ang = pos[t, comp] * theta ** (-i / 16)
            c, sn = np.cos(ang), np.sin(ang)
            want[t, :, i] = x[t, :, i] * c - x[t, :, i + 16] * sn
            want[t, :, i + 16] = x[t, :, i + 16] * c + x[t, :, i] * sn
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # equal components (a text token): plain rotary over that one position
    same = np.repeat(pos[:, :1], 3, axis=1)
    np.testing.assert_allclose(
        np.asarray(decoder.rotary_angles(same, theta, 16, sections)),
        np.asarray(decoder.rotary_angles(same[:, 0], theta, 16)), rtol=1e-6)


# ---------------------------------------------------------------------------
# (d), (e) dropless experts, and the shares of a divided layer
# ---------------------------------------------------------------------------

def _expert_layer(seed, t=64, d=32, width=16, experts=128, k=8, hot=None):
    rng = np.random.default_rng(seed)
    p = {
        "router": jnp.asarray(rng.standard_normal((d, experts)) * 0.5, jnp.float32),
        "w_gate": jnp.asarray(rng.standard_normal((experts, d, width)) * 0.2, jnp.float32),
        "w_up": jnp.asarray(rng.standard_normal((experts, d, width)) * 0.2, jnp.float32),
        "w_down": jnp.asarray(rng.standard_normal((experts, width, d)) * 0.2, jnp.float32),
    }
    b = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    if hot is not None:  # an expert every token chooses
        b = b.at[:, 0].set(4.0)
        p["router"] = p["router"].at[0, hot].set(5.0)
    m = {"E": experts, "k_e": k, "experts_held": (0, experts), "norm_topk_prob": True}
    return p, b, m


def _held(p, first, count):
    return {k: (v if k.startswith("router") else v[first:first + count]) for k, v in p.items()}


def test_dropless_routing_loses_no_token_at_four_times_the_mean_load():
    p, b, m = _expert_layer(7, experts=16, k=4, hot=5)
    with jax.default_matmul_precision("highest"):
        y, tokens = dropless_moe(b, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                                 k=4, num_experts=16)
        want, chosen = ref.experts(p, b, m, jnp.float32)
    tokens = np.asarray(tokens)
    assert tokens.sum() == 64 * 4  # every slot of every token served
    assert tokens[5] == 64 and tokens[5] >= 4 * tokens.mean()  # 4x the even share of 16
    np.testing.assert_array_equal(tokens, np.asarray(chosen).sum(axis=0))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid_with_selection_bias"])
def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_reference(scoring):
    from benchmark.reference import lfm2_decoder as lfm2_ref

    p, b, m = _expert_layer(9)
    router, experts_of = {}, ref.experts
    if scoring != "softmax":  # LFM2's router over the same layer: its reference, its arguments
        p["router_bias"] = jnp.asarray(np.random.default_rng(9).standard_normal(128) * 0.3, jnp.float32)
        router = dict(scoring="sigmoid", select_bias=p["router_bias"], gate_eps=1e-6)
        m = {**m, "scoring": "sigmoid", "select_bias": True, "scale": 1.0}
        experts_of = lfm2_ref.experts
    with jax.default_matmul_precision("highest"):
        parts, served = [], 0
        for first in (0, 32, 64, 96):
            h = _held(p, first, 32)
            y, tokens = dropless_moe(b, h["router"], h["w_gate"], h["w_up"], h["w_down"],
                                     k=8, num_experts=128, experts_held=(first, 32), **router)
            parts.append(np.asarray(y, np.float64))
            served += int(np.asarray(tokens).sum())
        want, _ = experts_of(p, b, m, jnp.float32)
    assert served == 64 * 8
    assert max(np.abs(part).max() for part in parts) > 0
    np.testing.assert_allclose(sum(parts), np.asarray(want), atol=1e-5)
    # and the reference, given one share, gives that share
    one, _ = experts_of(_held(p, 32, 32), b, {**m, "experts_held": (32, 32)}, jnp.float32)
    np.testing.assert_allclose(parts[1], np.asarray(one), atol=1e-5)


# ---------------------------------------------------------------------------
# (e') each row moves once each way: the dispatch and the combine against the
# formulation they replaced (PR 38's, written out)
# ---------------------------------------------------------------------------

BF16_ULP = 2.0 ** -7  # a bfloat16 keeps 8 significant bits: neighbours lie at most 2**-7 of the value apart


def _layer_as_it_was(p, x, k, experts, held):
    """PR 38's ``dropless_moe`` in plain steps: a stable argsort of the
    token slots, ``take`` (its default mode), the three grouped products as
    one ``dot`` an expert, ``take`` back by the inverse permutation, the
    gated float32 sum over ``k``. -> the sorted rows, the experts' rows,
    the order, the gates, ``y``, ``tokens``."""
    first, count = held
    t, d = x.shape
    logits = jnp.dot(x, p["router"].astype(x.dtype), preferred_element_type=jnp.float32)
    ids, gates = moe.route_top_k(jax.nn.softmax(logits, axis=-1), k)
    gates = jnp.where((ids >= first) & (ids < first + count), gates, 0.0)
    flat = np.asarray(ids).reshape(-1)
    order = np.argsort(flat, kind="stable")
    rows = jnp.take(x, jnp.asarray(order // k), axis=0)
    out = np.zeros((t * k, d), np.float32)
    for e in range(first, first + count):
        mine = np.flatnonzero(flat[order] == e)
        if mine.size:
            r = rows[mine]
            h = jax.nn.silu(jnp.dot(r, p["w_gate"][e - first], preferred_element_type=jnp.float32))
            h = (h * jnp.dot(r, p["w_up"][e - first], preferred_element_type=jnp.float32)).astype(x.dtype)
            out[mine] = jnp.dot(h, p["w_down"][e - first], preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.asarray(out, x.dtype)
    back = jnp.argsort(jnp.asarray(order))
    y = jnp.take(out, back, axis=0).reshape(t, k, d)
    y = jnp.sum(y.astype(jnp.float32) * gates[..., None], axis=1).astype(x.dtype)
    tokens = np.bincount(flat, minlength=experts)[first:first + count]
    return rows, out, jnp.asarray(order, jnp.int32), gates, y, tokens


def _loaded_layer(t, k, experts, load, share, d=2048, width=16):
    """An expert layer over bfloat16 rows of 2,048 (the width the row
    gather's kernel takes: it runs here, interpreted) whose router gives
    the ``load``; ``share``: the middle half of the experts is held."""
    rng = np.random.default_rng(t + k + experts)
    held = (experts // 4, experts // 2) if share else (0, experts)
    router = rng.standard_normal((d, experts)) * 0.02
    x = rng.standard_normal((t, d))
    if load == "hot":  # an expert EVERY token chooses: experts / k times the even share
        x[:, 0], router[0, held[0]] = 4.0, 5.0
    elif load == "cold":  # an expert NO token chooses
        x[:, 0], router[0, held[0]] = 4.0, -5.0
    p = {"router": jnp.asarray(router, jnp.float32)}
    for name, shape in (("w_gate", (d, width)), ("w_up", (d, width)), ("w_down", (width, d))):
        scale = shape[0] ** -0.5
        p[name] = jnp.asarray(rng.standard_normal((held[1],) + shape) * scale, jnp.bfloat16)
    return p, jnp.asarray(x, jnp.bfloat16), held


def _within_ulps(got, want, ulps, slack=0.0):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.abs(got - want) <= ulps * BF16_ULP * np.maximum(np.abs(got), np.abs(want)) + slack


@pytest.mark.parametrize("share", [False, True], ids=["all_held", "a_share_held"])
@pytest.mark.parametrize("load", ["even", "hot", "cold"])
@pytest.mark.parametrize("t,k,experts", [(64, 4, 8), (96, 8, 16), (40, 2, 4), (64, 2, 16)])
def test_rows_go_out_and_come_back_as_they_did_before(t, k, experts, load, share):
    p, x, held = _loaded_layer(t, k, experts, load, share)
    rows, out, order, gates, y_was, tokens_was = _layer_as_it_was(p, x, k, experts, held)
    mean = t * k / experts
    if load == "hot":  # (64, 2, 16): 8 x the even share; where experts / k is 2, twice: all a token can give
        assert tokens_was[0] == t == (experts // k) * mean
    elif load == "cold":
        assert tokens_was[0] == 0
    # out: the same rows, bit for bit (a copy), through the kernel or through XLA
    assert row_gather.tile_rows(t, t * k, x.shape[1], x.dtype) == t * k  # one tile, through the kernel
    got_rows = row_gather.gather_rows(x, order // k)
    assert got_rows.dtype == rows.dtype and bool(jnp.array_equal(got_rows, rows))
    # back, from the SAME experts' rows (a share's slots that are not held: a gate of 0 on a
    # row of zeros here; the layer itself never makes such a row, `_held_rows_moe`)
    got = moe.gated_row_sum(out, order, gates)
    # the float32 sum in the order j = 0 .. k-1, rounded once: what the new pass
    # states. WITHIN ONE bfloat16 step of it, and of the old reduce, not bit-equal:
    # XLA may keep a product unrounded into the add (one rounding fewer) and the
    # old reduce fixed no order, so a float32 sum can differ by the float32
    # roundings of its k terms (all that is left where they cancel), and a few per
    # 100,000 results then round to the neighbouring bfloat16
    chosen = np.asarray(jnp.take(out, jnp.argsort(order), axis=0), np.float32).reshape(t, k, -1)
    terms = chosen * np.asarray(gates)[..., None]
    want = np.zeros((t, chosen.shape[-1]), np.float32)
    for j in range(k):
        want = want + terms[:, j]
    want = np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32)
    roundings = k * 2.0 ** -23 * np.abs(terms).max(axis=1)
    assert not np.isnan(np.asarray(got, np.float32)).any()
    assert _within_ulps(got, want, 1, roundings).all() and _within_ulps(got, y_was, 1, roundings).all()
    assert (np.asarray(got, np.float32) == want).mean() > 0.999
    # the layer whole: the same tokens a held expert, and y within the roundings
    # of two bfloat16 intermediates (h and the experts' rows: the grouped product
    # and a plain dot add in different orders, so one in some thousand of those
    # rounds the other way)
    y, tokens = moe.dropless_moe(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], k=k,
                                 num_experts=experts, experts_held=held)
    np.testing.assert_array_equal(np.asarray(tokens), tokens_was)
    assert y.dtype == x.dtype and not np.isnan(np.asarray(y, np.float32)).any()
    scale = float(np.abs(np.asarray(y_was, np.float32)).max())
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_was, np.float32),
                               atol=2 * BF16_ULP * scale)


def test_a_sequence_reads_the_same_bits_at_another_place_of_the_batch():
    """Sequence 1 of three, then the batch moved one place on (it sits at
    place 2): its rows of the expert layer are the same BITS. The sum over a
    token's k rows depends on that token's rows only, in a fixed order."""
    p, x, held = _loaded_layer(96, 4, 8, "even", False)
    seqs = [x[i * 32:(i + 1) * 32] for i in range(3)]
    args = (p["router"], p["w_gate"], p["w_up"], p["w_down"])
    at_1, _ = moe.dropless_moe(jnp.concatenate(seqs), *args, k=4, num_experts=8)
    at_2, _ = moe.dropless_moe(jnp.concatenate([seqs[2], seqs[0], seqs[1]]), *args, k=4, num_experts=8)
    assert float(jnp.abs(at_1[32:64].astype(jnp.float32)).max()) > 0
    assert bool(jnp.array_equal(at_1[32:64], at_2[64:96]))
    assert bool(jnp.array_equal(at_1[:32], at_2[32:64]))


@pytest.mark.parametrize("m", [2048, 1024 + 256], ids=["whole_tiles", "ragged"])
@pytest.mark.parametrize("d", [2048, 2560, 2688, 3072, 768])
def test_row_gather_kernel_over_several_tiles_is_a_copy(d, m):
    """Two grid steps, so the second tile's copies are issued under the first
    one's unpacking and land in the other buffer; ``m`` in whole tiles of 1,024
    and ragged (the index padded to whole tiles, the last output block a partial
    one). Widths: whole ``[8, 128]`` word tiles (2,048: ``x`` read through XLA's
    reshape) and any other whole number of 128-column chunks, read through
    ``_rows_as_words``' view (2,560 and 3,072: 24 chunks' room; 2,688: 21
    chunks, an odd last one, the low halves of a last word; 768: six chunks in
    a view of eight). The kernel is driven directly: at these sizes the rule
    leaves every width but 2,048 to XLA (an ``x`` it keeps in vector memory)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((200, d)), jnp.bfloat16)
    idx = jnp.asarray(rng.integers(0, 200, m), jnp.int32)
    want = jnp.take(x, idx, axis=0)
    got = jax.jit(row_gather._kernel_rows, static_argnums=(2, 3))(x, idx, 1024, True)
    assert got.shape == (m, d) and got.dtype == x.dtype and bool(jnp.array_equal(got, want))
    assert row_gather.tile_rows(200, m, d, x.dtype) == (1024 if d == 2048 else 0)
    assert bool(jnp.array_equal(row_gather.gather_rows(x, idx), want))  # whoever moves them


@pytest.mark.parametrize("n,m,d,dtype", [
    (200, 2048, 2048, jnp.float32),  # 32-bit rows
    (200, 2048, 96, jnp.float32),
    (200, 2048, 2000, jnp.bfloat16),  # no whole number of 128-column chunks
    (200, 2048, 768, jnp.bfloat16),  # a view width, and an x XLA's own gather keeps in vector memory
    (200, 1000, 2048, jnp.bfloat16),  # under a tile, and no whole bursts of 16 copies
], ids=["float32", "float32_narrow", "no_whole_chunks", "a_small_x_at_a_view_width", "no_whole_bursts"])
def test_what_the_row_gather_kernel_does_not_take_goes_to_xla_s_in_bounds_gather(n, m, d, dtype):
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype)
    idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    assert row_gather.tile_rows(n, m, d, dtype) == 0
    assert "pallas_call" not in str(jax.make_jaxpr(row_gather.gather_rows)(x, idx))
    assert bool(jnp.array_equal(row_gather.gather_rows(x, idx), jnp.take(x, idx, axis=0)))


def test_the_row_gather_s_rule_reads_shapes_and_a_dtype_alone():
    """ONE function says who moves a call's rows: bfloat16 rows of whole
    128-column chunks go through the kernel; where a row is no whole ``[8,
    128]`` word tiles the kernel reads ``x`` through a view that is a pass
    over ALL of it, so it takes the call only where that pass is the smaller
    part, ``m >= n`` (a pass ahead of the held rows' loop: 3 to 4.5 rows out a
    row of ``x``; a turn of the loop, 2,048 rows of 8,704 to 34,816, stays
    XLA's), and where ``x`` is past what XLA's own gather keeps in vector
    memory, 112 MiB (laguna's 102 MiB ``x`` goes out at 10 ns a row by XLA,
    ling3's and nemotron3's 170 and 178.5 MiB at 41-42: PR 70's chip runs)."""
    bf16 = jnp.bfloat16
    assert row_gather.tile_rows(34816, 139264, 2048, bf16) == 1024  # lfm2's, keye's: as before
    assert row_gather.tile_rows(34816, 104448, 2560, bf16) == 1024  # ling3's pass ahead
    assert row_gather.tile_rows(34816, 156672, 2688, bf16) == 1024  # nemotron3's
    assert row_gather.tile_rows(17408, 65280, 3072, bf16) == 0  # laguna's: an x of 102 MiB
    assert row_gather.tile_rows(18944, 65280, 3072, bf16) == 0 < row_gather.tile_rows(19200, 65280, 3072, bf16)
    for n, d in [(17408, 7168), (8704, 7168), (34816, 2560)]:
        assert row_gather.tile_rows(n, 2048, d, bf16) == 0  # a turn of the loop: kimi's, dsv32's, ling3's
    assert row_gather.tile_rows(34816, 34816, 2560, bf16) == 1024  # the threshold: m = n
    assert row_gather.tile_rows(34816, 34815, 2560, bf16) == 0
    assert row_gather.tile_rows(4096, 2048, 2048, bf16) == 1024  # whole word tiles need no view: any m, any x
    assert row_gather.tile_rows(64, 256, 2048, bf16) == 256 and row_gather.tile_rows(64, 250, 2048, bf16) == 0


# ---------------------------------------------------------------------------
# (f) the precision the configuration states, and one below it
# ---------------------------------------------------------------------------

def _reference_rows(params, patches, ids, pos, m, compute):
    sizes = ref.sizes(m)
    x = ref.embed(params, patches, ids, compute)
    for p in params["layers"]:
        x = ref.layer(p, x, pos, sizes, compute, 16, False)[0]
    return x


def test_bf16_passes_the_rows_verdict_and_float8_fails_it_at_every_seed():
    from benchmark.programs.prefill import rows_verdict

    m = mapping()
    cfg = small(m)
    init = jax.jit(lambda key: decoder.init_params(cfg, key, jnp.bfloat16))
    _, ids, pos = frame_of(0)
    served = jax.jit(lambda p, f: decoder.trunk(p, decoder.embed(p, f, ids), pos, cfg)[0])
    plain = {c: jax.jit(lambda p, f, c=c: _reference_rows(p, f, ids, pos, m, c))
             for c in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn)}
    for seed in range(8):
        params = init(harness.make_key(seed))
        frame = frame_of(seed)[0]
        with jax.default_matmul_precision("highest"):
            want, stated, below = (np.asarray(plain[c](params, frame)) for c in plain)
        got = np.asarray(served(params, frame.astype(jnp.bfloat16)), np.float32)
        verdict = rows_verdict(got, want, stated)
        assert verdict["ok"] and verdict["yardsticks"] < 3.0, (seed, verdict)
        lower = rows_verdict(below, want, stated)
        assert not lower["ok"] and lower["yardsticks"] > 10.0, (seed, lower)


def test_rows_verdict_ignores_a_minority_of_tossed_rows():
    from benchmark.programs.prefill import rows_verdict

    rng = np.random.default_rng(0)
    want = rng.standard_normal((64, 32))
    stated = want * (1 + 1e-3 * rng.standard_normal(want.shape))
    got = want * (1 + 2e-3 * rng.standard_normal(want.shape))
    got[::7] += 0.2 * rng.standard_normal((10, 32))  # one row in seven off by a fifth
    few = rows_verdict(got, want, stated)
    assert few["ok"] and 0.1 < few["rows_over_limit"] < 0.2
    got[1::3] += 0.2 * rng.standard_normal((21, 32))  # and a further third: the median still holds
    many = rows_verdict(got, want, stated)
    assert not many["ok"] and many["rows_relative_rms_median"] <= many["limit"]
    assert not rows_verdict(want * (1 + 2e-2 * rng.standard_normal(want.shape)), want, stated)["ok"]
    got[0, 0] = np.nan
    assert not rows_verdict(got, want, stated)["ok"]


def _rehearsal_program(seed):
    from benchmark.programs import prefill

    with open(os.path.join(REPO, "benchmark", "configs", "keye_vl2_prefill_epix10k2m.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    det = cfg["detector"]
    frames = np.random.default_rng(seed).integers(
        90, 140, (1, det["panels"], det["height"], det["width"])).astype(np.uint16)
    return prefill.Program(cfg, seed, "", None), frames


def test_the_check_holds_the_served_logits_and_both_parts_of_the_sequence(monkeypatch):
    """What the chip's ``correct`` reads, at the rehearsal's size: the
    patches' rows, the prompt's rows, the head and the served logits. A
    head computed from float8 operands, served logits that are not the
    checked program's, or a prompt one position out of place, is not
    correct, each by the comparison that is there for it."""
    program, frames = _rehearsal_program(1)
    plain, memo = program.reference_hidden, {}
    program.reference_hidden = lambda batch, c: (  # the faults below leave the reference alone
        memo[c] if c in memo else memo.setdefault(c, plain(batch, c)))
    verdict = program.check(frames)
    assert verdict["ok"], verdict
    assert verdict["patch_rows"]["rows"] == 64 and verdict["prompt_rows"]["rows"] == 8
    parts = ("patch_rows", "prompt_rows", "head", "served")
    assert all(verdict[k]["ok"] for k in parts)

    def failing():
        program._step = jax.jit(program._step.__wrapped__)  # the package is traced in: trace anew
        verdict = program.check(frames)
        assert not verdict["ok"], verdict
        return {k for k in parts if not verdict[k]["ok"]}

    with monkeypatch.context() as patch:
        exact = decoder.logits_of

        def float8_head(params, x, cfg):
            rounded = dict(params, head=params["head"].astype(jnp.float8_e4m3fn).astype(x.dtype))
            return exact(rounded, x, cfg)

        patch.setattr(decoder, "logits_of", float8_head)
        assert failing() == {"head"}

    with monkeypatch.context() as patch:
        serve = program._serve
        patch.setattr(program, "_serve", lambda batch: (jnp.roll(serve(batch)[0], 1, axis=1), None))
        program._step = jax.jit(program._step.__wrapped__)
        assert failing() == {"served"}

    with monkeypatch.context() as patch:
        pos = decoder.frame_positions

        def prompt_one_late(panels, rows, cols, prompt_len):
            out = pos(panels, rows, cols, prompt_len).copy()
            out[panels * rows * cols:] += 1
            return out

        patch.setattr(decoder, "frame_positions", prompt_one_late)
        assert "prompt_rows" in failing()


def _verdicts(m, seeds=range(8)):
    """Per seed, ``precision_verdict`` of the LAST token's logits (what
    the chip's check compares) for the package's bf16 trunk and for the
    reference with float8-rounded operands."""
    cfg = small(m)
    init = jax.jit(lambda key: decoder.init_params(cfg, key, jnp.bfloat16))
    _, ids, pos = frame_of(0)

    def served(p, f):
        x, _ = decoder.trunk(p, decoder.embed(p, f, ids), pos, cfg)
        return decoder.logits_of(p, x[-1:], cfg)

    served = jax.jit(served)
    plain = {c: jax.jit(lambda p, f, c=c: ref.forward(p, f, ids, pos, m, c, 16)[-1:])
             for c in (jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn)}
    out = []
    for seed in seeds:
        params = init(harness.make_key(seed))
        frame = frame_of(seed)[0]
        with jax.default_matmul_precision("highest"):
            want, stated, below = (np.asarray(plain[c](params, frame)) for c in plain)
        got = np.asarray(served(params, frame.astype(jnp.bfloat16)))
        out.append((harness.precision_verdict(got, want, stated),
                    harness.precision_verdict(below, want, stated)))
    return out


def test_bf16_passes_precision_verdict_and_float8_fails_it_at_every_seed():
    """Where nothing is DECIDED (plain causal attention, a dense gated
    MLP) the verdict reads the precision alone, at every seed."""
    m = mapping(num_experts=0, num_experts_per_tok=0)
    m.pop("sa_config")
    for seed, (program, lower) in enumerate(_verdicts(m)):
        assert program["ok"], (seed, program)
        assert not lower["ok"], (seed, lower)
        assert lower["logits_relative_rms"] > 10 * lower["yardstick_relative_rms"], (seed, lower)


def test_with_selection_and_routing_the_verdict_holds_but_for_a_tossed_yardstick():
    """Top-2 of 8 experts and top-16 keys are decisions: one that lies
    inside the rounding noise goes either way, in the program and in the
    yardstick independently, and at this size moves a token's logits by
    tens of yardsticks. The program passes at every seed all the same;
    float8 operands fail wherever the yardstick itself was not tossed
    (there it is tens of times its usual self, and so is the limit)."""
    verdicts = _verdicts(mapping())
    usual = float(np.median([lower["yardstick_relative_rms"] for _, lower in verdicts]))
    passed = 0
    for seed, (program, lower) in enumerate(verdicts):
        assert program["ok"], (seed, program)
        if lower["ok"]:
            passed += 1
            assert lower["yardstick_relative_rms"] > 10 * usual, (seed, lower, usual)
    assert passed <= 1


@pytest.mark.parametrize("panels,rows,cols,prompt", [(2, 2, 14, 8), (16, 44, 48, 512)])
def test_the_reference_places_the_tokens_where_the_package_does(panels, rows, cols, prompt):
    want = ref.positions(panels, rows, cols, prompt)  # plain loops, the check's own
    np.testing.assert_array_equal(decoder.frame_positions(panels, rows, cols, prompt), want)
    assert want.shape == (panels * rows * cols + prompt, 3)
    assert tuple(want[0]) == (0, 0, 0) and tuple(want[cols]) == (0, 1, 0)
    assert tuple(want[panels * rows * cols - 1]) == (panels - 1, rows - 1, cols - 1)
    first = max(panels, rows, cols)  # 48 at the published size: the configuration's `assumed`
    np.testing.assert_array_equal(want[-prompt:], np.repeat(first + np.arange(prompt), 3).reshape(-1, 3))


# ---------------------------------------------------------------------------
# (g) the counters of a stream
# ---------------------------------------------------------------------------

def test_counters_of_a_two_frame_stream_through_infeed_pipeline():
    outs, snap, _ = streamed(small(mapping()), frames=2, batch=1)
    assert all(len(out) == 2 for out in outs)  # logits and statistics: nothing for a check rides on a served frame
    frames, layers, tokens = 2, 2, 64
    assert snap["expert_tokens_mean_total"] == frames * layers * tokens * 2 / 8
    assert snap["attn_tiles_causal_total"] == frames * layers * 1
    assert snap["expert_tokens_mean_total"] <= snap["expert_tokens_max_total"] <= frames * layers * tokens
    assert 0 < snap["attn_tiles_live_total"] <= snap["attn_tiles_causal_total"]
    assert set(decoder.STEP_STATS) <= set(snap)


def test_the_package_does_not_import_the_decoder():
    import subprocess
    import sys

    code = ("import sys, psana_ray_tpu, psana_ray_tpu.models; "
            "assert 'psana_ray_tpu.models.decoder' not in sys.modules; "
            "assert 'psana_ray_tpu.parallel.sparse_attention' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)


# ---------------------------------------------------------------------------
# the benchmark's reader of two counters (the manifest's entries: tests/test_manifest_entries.py)
# ---------------------------------------------------------------------------

def _ctx(snapshot):
    metrics = None if snapshot is None else types.SimpleNamespace(snapshot=lambda: snapshot)
    return types.SimpleNamespace(metrics=metrics)


@pytest.mark.parametrize("snapshot,args,want", [
    ({"a_total": 6.0, "b_total": 4.0}, {}, 1.5),
    ({"a_total": 6.0, "b_total": 4.0}, {"scale": 100.0}, 150.0),
    ({"a_total": 6.0}, {}, None),                    # the parent: no such counter
    ({"b_total": 4.0}, {}, None),
    ({"a_total": 6.0, "b_total": 0.0}, {}, None),    # nothing counted yet
    (None, {}, None),                                # a program without metrics
])
def test_program_counter_ratio(snapshot, args, want):
    from benchmark.readers import program_counter_ratio

    got = program_counter_ratio.read(_ctx(snapshot), "a_total", "b_total", **args)
    assert got == want


def test_roofline_counts_at_the_published_sizes():
    from benchmark.roofline import decoder as counts

    s = 34304
    assert counts.causal_pairs(s) == 588_399_360
    assert counts.selected_pairs(s, 2048) == 2048 * 2049 // 2 + (s - 2048) * 2048
    assert counts.selected_attention(s, 32, 4, 128, 2048)["flops"] == counts.selected_pairs(s, 2048) * 16384
    asked, [shapes] = need("keye_epix_saturated", "decoder.selected_attention")  # the cell's file
    assert asked(**shapes) == counts.selected_attention(s, 32, 4, 128, 2048)
    assert counts.grouped_product(s, 8, 2048, 768, 128)["flops"] == 2 * s * 8 * 2048 * 768
