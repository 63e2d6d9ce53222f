"""Latent attention, YaRN, the shared expert and a holder's SHARE of the
routed experts (``models/decoder.py`` reading Kimi-K2-Instruct's keys:
DeepSeek-V3's block) against the benchmark's plain reference
(``benchmark/reference/kimi_k2_decoder.py``) at small sizes on the CPU;
the causal kernel at a key width that is not its value width and with a
key part shared by all heads; the new cell's counters, counts and readers."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_k2_decoder as ref
from decoder_kit import PROMPT, Kit, inputs, share_of, streamed
from psana_ray_tpu.models import decoder
from psana_ray_tpu.parallel import moe
from psana_ray_tpu.parallel import sparse_attention as sa
from test_manifest_entries import BENCH, asked, need
from xla_turn import TURNS, assert_the_kernel_s_turn_is_xla_s, turned_by_xla

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "kimi_k2_prefill_epix10k2m.json")
CELL = "kimi_k2_epix_saturated"
YARN = {"type": "yarn", "factor": 32, "original_max_position_embeddings": 16, "beta_fast": 1,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def mapping(**over):
    """Kimi-K2's Hugging Face keys at a small size: 16 routed experts, all held."""
    m = dict(
        hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        vocab_size=256, rms_norm_eps=1e-6, rope_theta=50000, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1,
        n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=32,
        intermediate_size=96, norm_topk_prob=True, scoring_func="sigmoid",
        topk_method="noaux_tc", routed_scaling_factor=2.827, tie_word_embeddings=False,
        rope_scaling=dict(YARN), patch=8,
    )
    m.update(over)
    return m


def _kv_norm_times_3(params):
    return {**params, "layers": [{**p, "kv_a_norm": p["kv_a_norm"] * 3.0} for p in params["layers"]]}


# loud weights: at a hidden size of 64, normal(0, 0.02) makes every operator's output a hundredth of
# the residual stream's, and a test would not see a fault in one; tiles that cut 64 tokens into several
KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=16, causal_kv_tile=32))
LOUD_KV_NORM = Kit(mapping, ref, tiles=KIT.tiles, loud=lambda params: _kv_norm_times_3(KIT.loud(params)))
small = KIT.small


# ---------------------------------------------------------------------------
# the trunk against the reference, float32, all positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", ["all_16", "experts_4_to_7_of_16", "no_dense_layer"])
def test_trunk_with_latent_attention_matches_reference_at_all_positions(held):
    m = mapping()
    if held == "no_dense_layer":
        m.update(first_k_dense_replace=0)
    cfg = small(m)
    params = KIT.loud(decoder.init_params(cfg, jax.random.key(3), jnp.float32))
    if held == "experts_4_to_7_of_16":  # a share: the file's key counts the experts held
        m.update(n_routed_experts=4, router_experts=16, experts_held=[4, 4])
        cfg, params = small(m), share_of(params, 4, 4)
    patches, ids = inputs(3)
    sizes = ref.sizes(m)
    with jax.default_matmul_precision("highest"):
        x, got, stats = KIT.trunk_of(params, patches, ids, cfg)
        want_x, want = KIT.reference_of(params, patches, ids, sizes)
    assert got.shape == (64, 256) and "head" in params  # untied
    for a, b in ((x, want_x), (got, want)):
        scale = float(jnp.sqrt(jnp.mean(b ** 2)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4 * scale, rtol=0)
    n_moe = 3 if held == "no_dense_layer" else 2
    assert float(stats[1]) == n_moe * 64 * 4 / 16  # the even share of an expert, over the expert layers
    assert float(stats[2]) == float(stats[3]) == 3  # one 64 x 64 tile a latent-attention layer
    assert [float(v) for v in stats[4:6]] == [64.0, 1.0]
    if held == "experts_4_to_7_of_16":  # a holder of a share counts what it held, and what was routed
        assert float(stats[7]) == n_moe * 64 * 4 and 0 < float(stats[6]) < float(stats[7])
        # and a holder of a QUARTER (this one, not the cell's 12 of 384, whose vector is eight long)
        # takes a pass ahead of its loop: every group's places, then the rows that pass took
        assert len(stats) == 13 and cfg.rows_go_ahead and not any(float(v) for v in stats[8:12])
        assert float(stats[12]) == float(stats[6])  # under 1.5 even shares a layer: all of them
    else:
        assert len(stats) == 6


@pytest.mark.parametrize("fault", ["turn_key", "mscale", "yarn", "kv_norm", "shared", "select_bias",
                                   "softmax"])
def test_the_reference_with_a_fault_in_it_is_another_trunk(fault):
    faults = {"softmax": {"scoring": "softmax"}}.get(fault, {fault: False})
    x = LOUD_KV_NORM.trunk(5, jit=False)[0]  # made once for the seven cases
    want = LOUD_KV_NORM.reference(5, **faults)[0]
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    assert float(jnp.abs(x - want).max()) > 1e-2 * scale  # what a control puts in is seen


# ---------------------------------------------------------------------------
# the latent-attention layer alone, and its kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("widths", [
    {},  # heads of 16: head-major operands, the one keys-and-values array cut in two
    {"qk_nope_head_dim": 128, "v_head_dim": 128, "num_attention_heads": 2},  # read where written
    {"v_head_dim": 24},  # values of another width than the keys: two arrays from the projections
], ids=["heads_of_16", "heads_of_128", "values_of_24"])
def test_latent_attention_layer_is_the_reference_operator_for_a_batch_of_two(widths):
    m = mapping(**widths)
    cfg = small(m)
    p = KIT.params(7, over=widths)["layers"][0]
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2 * 64, 64)), jnp.float32)
    angles = jnp.tile(decoder.rotary_angles(np.arange(64), cfg.rope_theta, 4, None, cfg.rope_yarn),
                      (2, 1))
    sizes = ref.sizes(m)
    with jax.default_matmul_precision("highest"):
        out, live, causal = decoder.latent_attention(p, x, angles, 2, cfg)
        got = out - x
        want = jnp.concatenate([
            ref.latent_attention(p, ref.rms(x[i * 64:(i + 1) * 64], p["norm1"], 1e-6), sizes,
                                 jnp.float32, 16) for i in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert "q_norm" not in p and "k_norm" not in p  # no per-head norm in this operator
    assert live == causal == 2  # no selection: every tile of both sequences is attended


def _plain_attention(q, k, v, qs, ks, g, allowed):
    """Softmax attention over the ``allowed [S, S]`` pairs, a score being ``q
    . k + qs . ks`` with the ONE shared key; ``H/G`` query heads read a key head."""
    b, s, _ = q.shape
    h = qs.shape[2] // ks.shape[2]
    kh, vh = (jnp.repeat(x.reshape(b, s, g, -1), h // g, axis=2) for x in (k, v))
    score = (jnp.einsum("bthd,bshd->bhts", q.reshape(b, s, h, -1), kh, precision="highest")
             + jnp.einsum("bthd,bsd->bhts", qs.reshape(b, s, h, -1), ks, precision="highest"))
    score = jnp.where(allowed, score, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(score, -1), vh,
                      precision="highest").reshape(b, s, -1)


@TURNS
@pytest.mark.parametrize("bq,bk", [(16, 32), (32, 16), (48, 32), (96, 96)])
@pytest.mark.parametrize("rep", [1, 2])
def test_causal_kernel_with_a_shared_key_part_and_values_of_another_width(bq, bk, rep, turn):
    rng = np.random.default_rng(bq + rep)
    b, s, g, d, ds, dv = 2, 96, 2, 16, 8, 24
    h = g * rep
    q = jnp.asarray(rng.standard_normal((b, s, h * d)), jnp.float32) * 0.3
    qs = jnp.asarray(rng.standard_normal((b, s, h * ds)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((b, s, g * d)), jnp.float32)
    ks = jnp.asarray(rng.standard_normal((b, s, ds)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, g * dv)), jnp.float32)
    raw = qs
    if turn is not None:
        qs, tables = turned_by_xla(raw, turn, h)
    got = sa.masked_gqa_attention(q, k, v, num_kv_heads=g, block_q=bq, block_k=bk,
                                  q_shared=qs, k_shared=ks)
    if turn is not None:
        by_xla, got = got, sa.masked_gqa_attention(
            q, k, v, num_kv_heads=g, block_q=bq, block_k=bk, q_shared=raw, k_shared=ks,
            shared_turn=tables, shared_scale=turn)
        assert_the_kernel_s_turn_is_xla_s(got, by_xla, raw, qs, tables, turn, h, 3e-6)
    want = _plain_attention(q, k, v, qs, ks, g, np.tril(np.ones((s, s), bool)))
    assert got.shape == (b, s, h * dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)
    # the plain way to the same numbers: the shared key written once a head beside the head's own
    wide_q = jnp.concatenate([q.reshape(b, s, h, d), qs.reshape(b, s, h, ds)], -1).reshape(b, s, -1)
    wide_k = jnp.concatenate([k.reshape(b, s, g, d), jnp.broadcast_to(ks[:, :, None], (b, s, g, ds))],
                             -1).reshape(b, s, -1)
    plain = sa.masked_gqa_attention(wide_q, wide_k, v, num_kv_heads=g, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), atol=3e-6)


def _latent_operands(seed, b, s, g, d, ds):
    """Latent attention's operands at ``rep`` 1 and values as wide as the
    keys: ``q, k, v [B, S, G*d]``, the shared parts, and the ONE array that
    holds head ``h``'s keys at column block ``2h`` and its values at ``2h + 1``."""
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, g * d)), jnp.float32) for _ in range(3))
    qs = jnp.asarray(rng.standard_normal((b, s, g * ds)), jnp.float32) * 0.3
    ks = jnp.asarray(rng.standard_normal((b, s, ds)), jnp.float32)
    kv = jnp.concatenate([k.reshape(b, s, g, d), v.reshape(b, s, g, d)], -1).reshape(b, s, -1)
    return q * 0.1, k, v, qs, ks, kv


def _transposes(fn, *args):
    return sum(e.primitive.name == "transpose" for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns)


@pytest.mark.parametrize("kv", ["one_array", "two_arrays"])
@pytest.mark.parametrize("case", ["maskless_batch_of_two", "masked_one_sequence"])
def test_causal_kernel_reads_heads_of_whole_lane_blocks_where_their_products_wrote_them(case, kv):
    """Heads of 128 with values of 128, alone in their groups (latent
    attention's case): q, k, v and o are column blocks of the token-major
    arrays, k and v of ONE array or of two; the numbers are the reference's
    and those of the head-major addressing (taken where a head is no whole
    number of lane blocks: the same operands with eight zero columns a head)."""
    b, s, g, d, ds = (2 if case == "maskless_batch_of_two" else 1), 64, 3, 128, 8
    q, k, v, qs, ks, one = _latent_operands(len(case), b, s, g, d, ds)
    allowed, selection = np.tril(np.ones((s, s), bool)), ()
    if case == "masked_one_sequence":  # a selection: half of the earlier keys, and the query's own
        allowed &= np.random.default_rng(5).random((s, s)) < 0.5
        allowed |= np.eye(s, dtype=bool)
        selection = (jnp.asarray(allowed.reshape(s // 16, 16, s // 32, 32).transpose(0, 2, 1, 3),
                                 jnp.int8),)
    keys_values = (one, None) if kv == "one_array" else (k, v)

    def attend(q, k, v, qs):
        return sa.masked_gqa_attention(q, k, v, *selection, num_kv_heads=g, block_q=32,
                                       block_k=32, q_shared=qs, k_shared=ks)

    got = attend(q, *keys_values, qs)
    assert got.shape == (b, s, g * d)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain_attention(q, k, v, qs, ks, g, allowed)), atol=3e-6)
    # one transpose, the narrow shared query's; the head-major addressing makes five (q, k, v,
    # the shared query, o back)
    assert _transposes(attend, q, *keys_values, qs) == 1

    def padded(x):
        return jnp.pad(x.reshape(b, s, g, d), ((0, 0),) * 3 + ((0, 8),)).reshape(b, s, -1)

    assert _transposes(attend, padded(q), padded(k), padded(v), qs) == 5
    major = attend(padded(q), padded(k), padded(v), qs).reshape(b, s, g, d + 8)
    np.testing.assert_array_equal(np.asarray(major[..., d:]), 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(major[..., :d].reshape(b, s, -1)),
                               atol=3e-6)


@pytest.mark.parametrize("rep", [1, 2])
@pytest.mark.parametrize("case", ["maskless_batch_of_two", "masked_one_sequence"])
def test_the_kernel_turns_its_bf16_tile_to_the_bit_as_the_projections_did(case, rep):
    """Kimi's widths (heads of 128 + 64, values of 128) in bf16, the cells'
    type: the kernel given the float32 product and the tables against the
    kernel given the query :func:`decoder.rotate` turned and rounded, in
    tiles of 128 over sequences of 256, heads alone in their groups and two
    a group, maskless over a batch and under a selection's mask: the turned
    tiles are equal to the bit, and so is every output."""
    b, s, g, d, ds = (2 if case == "maskless_batch_of_two" else 1), 256, 2, 128, 64
    h, dt = g * rep, jnp.bfloat16
    _, k, v, _, ks, _ = _latent_operands(61, b, s, g, d, ds)
    rng = np.random.default_rng(rep)
    q, raw = (jnp.asarray(rng.standard_normal((b, s, h * width)), jnp.float32) * scale
              for width, scale in ((d, 0.1), (ds, 0.3)))
    turned, tables = turned_by_xla(raw, 0.1147, h)
    selection = ()
    if case == "masked_one_sequence":  # half of the earlier keys, and the query's own
        allowed = np.tril(np.random.default_rng(5).random((s, s)) < 0.5) | np.eye(s, dtype=bool)
        selection = (jnp.asarray(allowed.reshape(s // 32, 32, s // 128, 128).transpose(0, 2, 1, 3),
                                 jnp.int8),)

    def attend(qs, **turn):
        return sa.masked_gqa_attention(q.astype(dt), k.astype(dt), v.astype(dt), *selection,
                                       num_kv_heads=g, block_q=128, block_k=128, q_shared=qs,
                                       k_shared=ks.astype(dt), **turn)

    in_kernel = attend(raw, shared_turn=tables, shared_scale=0.1147)
    assert in_kernel.dtype == dt and in_kernel.shape == (b, s, h * d)
    assert assert_the_kernel_s_turn_is_xla_s(in_kernel, attend(turned.astype(dt)), raw,
                                             turned.astype(dt), tables, 0.1147, h, 2e-2)
    with pytest.raises(ValueError, match="there is none"):  # tables with no shared part to turn
        sa.masked_gqa_attention(q, k, v, num_kv_heads=g, shared_turn=tables)


def test_a_call_without_the_tables_traces_the_kernel_it_traced():
    """LFM2's call (no shared part, heads of 64, four a group) and kimi's
    as it was until PR 61 (the shared query turned by XLA, bf16): their
    jaxprs, the kernel's body in them, hashed on PR 60's tree and on PR
    61's — equal. The turn is a branch taken in Python by the operands
    given; :data:`tests.test_chip_compile.PINNED_STEPS` blanks a kernel's
    body, this does not."""
    import hashlib

    S = jax.ShapeDtypeStruct
    wide, narrow = S((4, 8704, 32 * 64), jnp.bfloat16), S((4, 8704, 8 * 64), jnp.bfloat16)
    # (as ONE stacked product, the form hashed then: since PR 75 the rule cuts this shape's four stacked
    # heads into four parts, a body four times as long, and `cut=1` asks for the old one)
    assert sa.causal_tiles(8704, 4, 1088, 1088) == (1088, 1088)
    lfm2 = jax.make_jaxpr(lambda q, k, v: sa._causal_attention(
        q, k, v, 8, 1088, 1088, False, cut=1))(wide, narrow, narrow)
    heads = S((2, 8704, 64 * 128), jnp.bfloat16)
    # (at ONE head a grid step, the form hashed then: since PR 66 the rule gives heads alone in their
    # groups a block of two at this shape, a body twice as long, and `heads=1` asks for the old one)
    kimi = jax.make_jaxpr(lambda q, k, v, qs, ks: sa._causal_attention(
        q, k, v, 64, 1088, 1088, False, qs, ks, heads=1))(
            heads, heads, heads, S((2, 8704, 64 * 64), jnp.bfloat16), S((2, 8704, 64), jnp.bfloat16))
    assert [hashlib.sha256(str(j).encode()).hexdigest()[:16] for j in (lfm2, kimi)] == [
        "f3a740c1d0e1fb7b", "7a235d7ede244698"]


@pytest.mark.parametrize("rep", [1, 4])
def test_heads_of_64_keep_the_head_major_addressing(rep):
    """A 64-lane block of ``[S, G*64]`` Mosaic does not take: heads of 64
    (alone in their groups, and four a group: LFM2's and granite's) are
    transposed to head-major and back as they were, whatever the heads a
    group (the width alone decides: PR 58 took ``rep`` out of the rule), and
    ONE array of keys and values is cut in two first."""
    b, s, g, d, ds = 2, 64, 2, 64, 8
    _, k, v, _, ks, one = _latent_operands(64, b, s, g, d, ds)
    rng = np.random.default_rng(rep)
    q, qs = (jnp.asarray(rng.standard_normal((b, s, g * rep * width)), jnp.float32) * scale
             for width, scale in ((d, 0.1), (ds, 0.3)))

    def attend(q, k, v):
        return sa.masked_gqa_attention(q, k, v, num_kv_heads=g, block_q=32, block_k=32,
                                       q_shared=qs, k_shared=ks)

    want = _plain_attention(q, k, v, qs, ks, g, np.tril(np.ones((s, s), bool)))
    for keys_values in ((k, v), (one, None)):
        assert _transposes(attend, q, *keys_values) == 5
        np.testing.assert_allclose(np.asarray(attend(q, *keys_values)), np.asarray(want), atol=3e-6)
    assert _transposes(lambda q, k, v: sa.masked_gqa_attention(  # and without a shared part: four
        q, k, v, num_kv_heads=g, block_q=32, block_k=32), q, k, v) == 4


# (a mask WITH a shared key part and a value width of its own is the selection over latent
# attention: tests/test_decoder_dsv32.py)


# ---------------------------------------------------------------------------
# YaRN, against the formula written out
# ---------------------------------------------------------------------------

def test_yarn_angles_at_the_published_sizes_are_the_formula_written_out():
    with open(CONFIG) as f:
        cfg = decoder.DecoderConfig.from_mapping(json.load(f))
    yarn = cfg.rope_yarn
    assert yarn == decoder.Yarn(32.0, 4096, 1.0, 1.0, 1.0, 1.0) and cfg.rope_dim == 64
    correction = 64 * np.log(4096 / (2 * np.pi)) / (2 * np.log(50000))
    assert 19.1 < correction < 19.2  # pairs 0..19 keep their frequency, 20..31 are divided by 32
    want = np.asarray([50000.0 ** (-i / 32) / (1.0 if i <= 19 else 32.0) for i in range(32)])
    np.testing.assert_allclose(yarn.inv_freq(50000.0, 32), want, rtol=1e-12)
    np.testing.assert_allclose(ref.yarn_inv_freq(ref.sizes(json.load(open(CONFIG)))), want, rtol=1e-12)
    pos = np.asarray([0, 1, 4095, 8703])
    got = decoder.rotary_angles(pos, 50000.0, 32, None, yarn)
    np.testing.assert_allclose(np.asarray(got), pos[:, None] * want[None, :], rtol=1e-6)
    assert yarn.rotary_scale == 1.0  # mscale / mscale_all_dim
    assert yarn.softmax_scale == pytest.approx((0.1 * np.log(32) + 1) ** 2) == pytest.approx(1.8133, abs=1e-4)
    # a ramp that spans several pairs blends; no scaling under a factor of 1
    wide = decoder.Yarn(4.0, 64, 8.0, 1.0, 1.0, 0.0).inv_freq(10000.0, 16)
    plain = 10000.0 ** (-np.arange(16) / 16)
    assert wide[0] == plain[0] and wide[-1] == plain[-1] / 4 and np.all(np.diff(wide / plain) <= 0)
    assert decoder.Yarn(1.0, 64).softmax_scale == 1.0
    # and plain rotary is what it was
    np.testing.assert_allclose(np.asarray(decoder.rotary_angles(pos, 1e6, 32)),
                               pos[:, None] * (1e6 ** (-np.arange(32) / 32))[None, :], rtol=1e-6)


# ---------------------------------------------------------------------------
# the shares of a divided layer, the shared expert counted once
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


def _routed(p, b, held, **kw):
    first, count = held
    return moe.dropless_moe(b, p["router"], p["w_gate"][first:first + count],
                            p["w_up"][first:first + count], p["w_down"][first:first + count],
                            k=4, num_experts=16, experts_held=held, scoring="sigmoid",
                            select_bias=p["router_bias"], gate_eps=1e-20, gate_scale=2.827, **kw)


def test_four_shares_of_four_experts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    p, b, m = _expert_layer(9)
    with jax.default_matmul_precision("highest"):
        parts, served = [], 0
        for first in (0, 4, 8, 12):
            y, tokens = _routed(p, b, (first, 4))
            parts.append(np.asarray(y, np.float64))
            served += int(np.asarray(tokens).sum())
        shared = np.asarray(decoder._dense_mlp(
            {"w_gate": p["shared_gate"], "w_up": p["shared_up"], "w_down": p["shared_down"]}, b))
        routed, chosen = ref.experts(p, b, m, jnp.float32)
        want = np.asarray(routed + ref.shared_expert(p, b, jnp.float32))
    assert served == 64 * 4 and np.asarray(chosen).sum() == 64 * 4  # every slot, once
    assert min(np.abs(part).max() for part in parts) > 0 and np.abs(shared).max() > 0
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    # every share WITH the shared expert would count it four times: not the layer
    assert np.abs(sum(part + shared for part in parts) - want).max() > 1e-2
    # and the reference, given one share, gives that share
    held = {k: (v[4:8] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p.items()}
    one, _ = ref.experts(held, b, {**m, "experts_held": (4, 4)}, jnp.float32)
    np.testing.assert_allclose(parts[1], np.asarray(one), atol=2e-5)


@pytest.mark.parametrize("chunk", [16, 64, 4096])
def test_when_every_token_chooses_held_experts_no_row_is_dropped(chunk, monkeypatch):
    """The adversarial routing: the selection bias lifts experts 4..7 over
    all others for every token, so all T * k slots are held rows, several
    turns of the loop at a chunk of 16 or 64."""
    p, b, m = _expert_layer(11)
    p["router_bias"] = jnp.zeros(16).at[4:8].set(10.0)
    real = moe._held_rows_moe
    monkeypatch.setattr(moe, "_held_rows_moe", lambda *a: real(*a, chunk=chunk))
    with jax.default_matmul_precision("highest"):
        y, tokens = _routed(p, b, (4, 4))
        want, chosen = ref.experts(p, b, m, jnp.float32)  # the uncut layer: nothing else was chosen
    assert np.asarray(tokens).tolist() == [64] * 4  # exact: every slot of every token, here
    assert np.asarray(chosen)[:, 4:8].all() and np.asarray(chosen).sum() == 64 * 4
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    # and a holder nobody chose computes nothing, in no turn of the loop
    y0, tokens0 = _routed(p, b, (12, 4))
    assert int(np.asarray(tokens0).sum()) == 0 and float(jnp.abs(y0).max()) == 0.0


def test_a_share_moves_its_held_rows_only_and_the_whole_layer_is_the_program_it_was(monkeypatch):
    p, b, m = _expert_layer(13)
    real = moe._held_rows_moe
    monkeypatch.setattr(moe, "_held_rows_moe", lambda *a: real(*a, chunk=64))
    share = jax.make_jaxpr(lambda b: _routed(p, b, (0, 4)))(b)
    whole = jax.make_jaxpr(lambda b: _routed(p, b, (0, 16)))(b)
    slots = 64 * 4

    def shapes(jaxpr, out=None):
        out = set() if out is None else out
        for eqn in jaxpr.eqns:
            out.update(tuple(v.aval.shape) for v in eqn.outvars if hasattr(v.aval, "shape"))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                shapes(sub, out)
        return out

    assert (slots, 32) in shapes(whole.jaxpr)  # all T * k rows gathered where every expert is held
    assert (slots, 32) not in shapes(share.jaxpr)  # and none in a share: 64 rows a turn
    assert "while" in str(share) and "while" not in str(whole)


# ---------------------------------------------------------------------------
# the configuration's third spelling
# ---------------------------------------------------------------------------

def test_kimi_configuration_reads_the_published_keys():
    with open(CONFIG) as f:
        cfg = json.load(f)
    got = decoder.DecoderConfig.from_mapping(cfg)
    assert (got.hidden_size, got.num_heads, got.head_dim, got.rope_dim) == (7168, 64, 192, 64)
    assert (got.q_lora_rank, got.kv_lora_rank, got.qk_nope_head_dim, got.qk_rope_head_dim,
            got.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (got.num_experts, got.experts_held, got.experts_per_token, got.expert_width,
            got.shared_experts, got.num_dense_layers) == (384, (0, 12), 8, 2048, 1, 1)
    assert (got.router_scoring, got.expert_bias, got.gate_eps, got.routed_scaling_factor,
            got.norm_topk_prob, got.tie_embedding) == ("sigmoid", True, 1e-20, 2.827, True, False)
    assert got.holds_a_share and got.vocab_size == 20480 == cfg["published"]["vocab_size"] // 8
    kinds = [got.layer_kind(i) for i in range(got.num_layers)]
    assert kinds == [(decoder.LATENT, False)] + [(decoder.LATENT, True)] * 6
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 4.84e9 < n < 4.86e9  # the file's 4.85 G parameters, 9.70 GB in bf16
    layer = shapes["layers"][1]
    assert layer["router"].shape == (7168, 384) and layer["w_gate"].shape == (12, 7168, 2048)
    assert layer["wkv_a"].shape == (7168, 576) and layer["wq_b"].shape == (1536, 64 * 192)
    assert cfg["sequence_tokens"] == 16 * (352 // 16) * (384 // 16) + cfg["prompt_tokens"] == 8704
    assert cfg["step_tokens"] == cfg["batch_size"] * cfg["sequence_tokens"] == 17408
    assert cfg["n_routed_experts"] == cfg["experts_held"][1] == cfg["published"]["n_routed_experts"] // 32
    assert (BENCH.cell(CELL)["traffic"], BENCH.file(CELL)) == ("saturated", cfg)  # the cell runs THIS file
    # a null query rank was refused until PR 50; it is a FULL-RANK query (W_q in W_dq and W_uq's place)
    full = decoder.DecoderConfig.from_mapping({**cfg, "q_lora_rank": None})
    assert (full.q_lora_rank, full.kv_lora_rank, full.attn_gate) == (0, 512, "")
    theirs = jax.eval_shape(lambda k: decoder.init_params(full, k), jax.random.key(0))["layers"][1]
    assert theirs["wq"].shape == (7168, 64 * 192) and "wq_b" not in theirs


@pytest.mark.parametrize("name", ["keye_vl2_prefill_epix10k2m", "lfm2_8b_a1b_prefill_epix10k2m"])
def test_the_other_two_readers_have_nothing_of_what_kimi_brought(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        got = decoder.DecoderConfig.from_mapping(json.load(f))
    assert (got.rope_yarn, got.q_lora_rank, got.kv_lora_rank, got.qk_nope_head_dim,
            got.qk_rope_head_dim, got.v_head_dim, got.shared_experts) == (None, 0, 0, 0, 0, 0, 0)
    assert not got.holds_a_share and got.rope_dim == got.head_dim
    assert decoder.LATENT not in {got.layer_kind(i)[0] for i in range(got.num_layers)}
    assert got.gate_eps == (1e-6 if "lfm2" in name else 0.0)


def test_catalog_numbers_are_in_the_file_unchanged():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "Kimi-K2-Instruct"]
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["source"] == row["source_url"] and len(cfg["source"]) <= 200
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: row["config"][k] for k in differs} == {k: cfg["published"][k] for k in differs}
    assert "32 chips that share each layer" in cfg["deployment"] and len(cfg["assumed"]) >= 8


# ---------------------------------------------------------------------------
# the counters of a share, in snapshot() and under /metrics
# ---------------------------------------------------------------------------

def test_counters_of_a_share_reach_the_snapshot_and_the_exposition():
    cfg = small(mapping(n_routed_experts=4, router_experts=16, experts_held=[0, 4]))
    _, snap, text = streamed(cfg)
    steps, tokens = 2, 2 * (2 * 2 * 14 + PROMPT)
    assert snap["decoder_tokens_total"] == steps * tokens
    assert snap["expert_rows_routed_total"] == steps * 2 * tokens * 4  # two expert layers, 4 a token
    assert 0 < snap["expert_rows_held_total"] < snap["expert_rows_routed_total"]
    assert snap["expert_tokens_mean_total"] == steps * 2 * tokens * 4 / 16
    for name in decoder.STEP_STATS + decoder.SHARE_STATS:
        assert f'psana_ray_{name}{{source="reader"}}' in text, name


# ---------------------------------------------------------------------------
# the cell's counts and its readers (its manifest entries: tests/test_manifest_entries.py)
# ---------------------------------------------------------------------------

def test_kimi_roofline_counts_at_the_published_sizes():
    from benchmark.roofline import kimi_k2 as roofline

    attn = roofline.latent_attention(2, 8704, 64, 128, 64, 128)
    assert attn["flops"] == 2 * (192 + 128) * 64 * 2 * (8704 * 8705 // 2)  # 3.10 T a layer
    assert 3.09e12 < attn["flops"] < 3.11e12
    assert attn["bytes"] == 2 * 17408 * (64 * (128 + 64 + 128 + 128 + 128) + 64)
    fn, [shapes] = need(CELL, "kimi_k2.step")  # as the cell's file asks for it
    step = fn(**shapes)
    assert 72.0e12 < step["flops"] < 72.5e12  # ISSUE 42's 72.2 T: 0.37 s at the peak
    latent = 7 * (3.52e12 + 3.10e12)
    assert 0.63 < latent / step["flops"] < 0.65  # latent attention and its projections
    fn, [shapes] = need(CELL, "kimi_k2.held_products")
    gmm = fn(held_share=12 / 384, **shapes)
    assert gmm["flops"] == 18 * 2 * 4352 * 7168 * 2048  # 0.38 T an expert layer
    assert gmm["bytes"] == 18 * 2 * (12 * 7168 * 2048 + 4352 * (7168 + 2048))


def _held_rows_loop_trace(tmp_path, monkeypatch, joined=()):
    """A reader's context over a hand-written trace of two runs of a step
    with ONE expert layer: a loop whose body calls three Pallas kernels (the
    grouped products) and a scatter-add, and whatever ``joined`` the scope
    (name -> last component of its name stack)."""
    import types

    from benchmark.readers import trace_scope_leaf_time

    ops = [("%while.5 = (s32[]) while(...)", 5e5, 2e6),  # spans its body
           ("%tpu_custom_call.1 = f32[8,8] custom-call(...)", 1e6, 3e5),
           ("%tpu_custom_call.2 = f32[8,8] custom-call(...)", 2e6, 1e5),
           ("%_lambda_.7 = f32[8,8] custom-call(...)", 2.1e6, 2e5),
           ("%fusion.3 = f32[8,8] fusion(...)", 2.3e6, 2e5),
           ("%fusion.9 = f32[8,8] fusion(...)", 3e6, 9e5),
           ("%while.5 = (s32[]) while(...)", 5.5e6, 2e6),
           ("%tpu_custom_call.1 = f32[8,8] custom-call(...)", 6e6, 2e5),
           ("%tpu_custom_call.2 = f32[8,8] custom-call(...)", 7e6, 2e5),
           ("%_lambda_.7 = f32[8,8] custom-call(...)", 7.2e6, 2e5),
           ("%fusion.3 = f32[8,8] fusion(...)", 7.4e6, 2e5)]
    stack = "jit(kimi_k2_step)/moe/jit(mlp)"  # a body's operations keep the LOOP's scopes only
    scopes = {"while.5": stack + "/while", "tpu_custom_call.1": stack + "/pallas_call",
              "tpu_custom_call.2": stack + "/pallas_call", "_lambda_.7": stack + "/pallas_call",
              "fusion.3": stack + "/scatter-add", "fusion.9": "jit(kimi_k2_step)/proj/dot_general"}
    for i, (name, leaf) in enumerate(joined):
        ops += [(f"%{name} = f32[8,8] custom-call(...)", 2.6e6 + i * 1e4, 1e3),
                (f"%{name} = f32[8,8] custom-call(...)", 7.7e6 + i * 1e4, 1e3)]
        scopes[name] = f"{stack}/{leaf}"
    device = {0: {"XLA Modules": [("jit_kimi_k2_step(1)", 0.0, 4e6), ("jit_kimi_k2_step(1)", 5e6, 4e6)],
                  "XLA Ops": sorted(ops, key=lambda e: e[1])}}
    trace = types.SimpleNamespace(device=device)
    cfg = {"trace_names": {"step": "jit_kimi_k2_step"},
           "t": 64, "k": 4, "d": 128, "f": 64, "n": 4, "l": 2, "dense": 1}
    counters = {"expert_rows_held_total": 50.0, "expert_rows_routed_total": 200.0}
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace" / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace_scope_leaf_time, "load_scopes", lambda path: scopes)
    ctx = types.SimpleNamespace(
        trace=trace, trace_window=(0.0, 1e9), cfg=cfg, spool_path=str(tmp_path / "spans" / "spool"),
        metrics=types.SimpleNamespace(snapshot=lambda: counters),
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e30})
    return ctx, scopes


def _gmm_share_args():
    args, = asked(CELL, function="kimi_k2.held_products").values()  # the grouped products' share
    assert (args["scope"], args["leaf"], args["within"]) == ("moe", "pallas_call", "@step")
    return {**args, "shape_from": {"tokens": "t", "per_token": "k", "hidden": "d", "width": "f",
                                   "held": "n", "layers": "l", "dense_layers": "dense"}}


def test_a_loops_body_is_counted_once_and_its_kernels_are_found_by_scope_and_primitive(tmp_path, monkeypatch):
    import types

    from benchmark.readers import roofline_share_per_run, trace_scope_leaf_time, trace_scope_time

    ctx, scopes = _held_rows_loop_trace(tmp_path, monkeypatch)
    trace, step = ctx.trace, "^%?jit_kimi_k2_step"
    # the whole scope: the loop's own event (2.0 ms, spanning its body) is left out
    assert trace_scope_time.scope_ms(trace, scopes, "moe", step, 0.0, 1e9) == pytest.approx(2.8)
    assert trace_scope_leaf_time.leaf_scope_ms(trace, scopes, "moe", step, 0.0, 1e9) == pytest.approx(0.8)
    ran = set()
    assert trace_scope_leaf_time.leaf_scope_ms(trace, scopes, "moe", step, 0.0, 1e9, "pallas_call",
                                               ran) == pytest.approx(0.6)
    assert ran == {"tpu_custom_call.1", "tpu_custom_call.2", "_lambda_.7"}
    assert trace_scope_leaf_time.leaf_scope_ms(trace, scopes, "no_such_scope", step, 0.0, 1e9) is None
    # the share: the least time of all the step's products over the products' time in a run
    args = _gmm_share_args()
    flops = 3 * 2 * (64 * 4 * 0.25) * 128 * 64
    assert roofline_share_per_run.read(ctx, **args) == pytest.approx(flops / 1e12 / 6e-4 * 100.0)
    ctx.metrics = types.SimpleNamespace(snapshot=lambda: {})  # the parent's program: no such counters
    assert roofline_share_per_run.read(ctx, **args) is None
    ctx.trace = None
    assert roofline_share_per_run.read(ctx, **args) is None


@pytest.mark.parametrize("joined,read", [
    ((("row_scatter.4", "pallas_call"),), False),  # a fourth kernel in the loop: not gmm's time
    ((("row_gather.2", "pallas_call"), ("row_scatter.4", "pallas_call")), False),
    ((("fusion.12", "gather"),), True),  # what is no Pallas call may come and go
], ids=["a_row_scatter_joins", "two_kernels_join", "an_xla_gather_joins"])
def test_the_grouped_products_share_reads_nothing_once_another_kernel_joins_the_scope(
        tmp_path, monkeypatch, capsys, joined, read):
    """``gmm_roofline_share.kimi`` reads every Pallas call under ``moe``:
    the roofline function counts three call sites an expert layer, and a
    trace in which another number ran is refused aloud, not read low."""
    from benchmark.readers import roofline_share_per_run

    ctx, _ = _held_rows_loop_trace(tmp_path, monkeypatch, joined)
    got = roofline_share_per_run.read(ctx, **_gmm_share_args())
    said = capsys.readouterr().err
    if read:
        assert got is not None and not said
    else:
        assert got is None and "call sites" in said and joined[-1][0] in said


def test_the_adapter_ends_the_run_where_the_package_has_no_latent_attention(monkeypatch):
    from benchmark.programs import prefill_latent

    @dataclasses.dataclass(frozen=True)
    class Older:  # the parent's DecoderConfig, as far as the adapter looks
        hidden_size: int = 0

    monkeypatch.setattr(decoder, "DecoderConfig", Older)
    with pytest.raises(SystemExit) as e:
        prefill_latent.Program({"name": "kimi_k2_prefill_epix10k2m"}, 1, "", None)
    assert e.value.code not in (0, None) and "latent attention" in str(e.value.code)


def test_the_adapter_ends_the_run_where_the_file_counts_another_share_than_it_holds():
    from benchmark.programs import prefill_latent

    with open(CONFIG) as f:
        cfg = json.load(f)
    with pytest.raises(SystemExit) as e:  # before anything is built
        prefill_latent.Program({**cfg, "experts_held": [0, 24]}, 1, "", None)
    assert e.value.code not in (0, None) and "experts_held" in str(e.value.code)
