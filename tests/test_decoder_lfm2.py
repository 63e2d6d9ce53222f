"""The decoder's schedule of layer kinds and its batch of sequences
(``models/decoder.py`` reading LFM2-8B-A1B's keys), the maskless causal
kernel and the sigmoid router with its selection bias, against the
benchmark's plain reference (``benchmark/reference/lfm2_decoder.py``) at
small sizes on the CPU; the new cell's counters and counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_decoder as ref
from decoder_kit import PROMPT, Kit, embedded, inputs, streamed
from psana_ray_tpu.models import decoder
from psana_ray_tpu.parallel import moe
from psana_ray_tpu.parallel import sparse_attention as sa
from test_manifest_entries import need

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "lfm2_8b_a1b_prefill_epix10k2m.json")


def mapping(**over):
    """LFM2's Hugging Face keys at a small size, every kind of layer in."""
    m = dict(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=256, norm_eps=1e-5, rope_theta=1e6, conv_L_cache=3, conv_bias=False,
        layer_types=["conv", "conv", "full_attention", "conv"], num_dense_layers=1,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, intermediate_size=96,
        norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1, tie_embedding=True,
        patch=8,
    )
    m.update(over)
    return m


def loud(params):
    """The same tree with the convolution's weights scaled up: at a hidden
    size of 64, normal(0, 0.02) makes its output a thousandth of the
    residual stream's, and a test would not see it (at 2,048 they are
    alike)."""
    def scale(p):
        return {k: v * {"w_in": 8.0, "conv_w": 25.0}.get(k, 1.0) for k, v in p.items()}

    return {**params, "layers": [scale(p) if "conv_w" in p else p for p in params["layers"]]}


KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=16, causal_kv_tile=32), loud=loud)  # 64 tokens in several tiles
small = KIT.small


# ---------------------------------------------------------------------------
# the trunk against the reference, float32, all positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", ["published_order", "attention_first", "no_dense_layer"])
def test_trunk_of_three_kinds_of_layer_matches_reference_at_all_positions(schedule):
    m = mapping()
    if schedule == "attention_first":
        m.update(layer_types=["full_attention", "conv", "conv", "full_attention"],
                 num_dense_layers=2)
    if schedule == "no_dense_layer":
        m.update(num_dense_layers=0)
    cfg = small(m)
    params = loud(decoder.init_params(cfg, jax.random.key(3), jnp.float32))
    patches, ids = inputs(3)
    sizes = ref.sizes(m)
    with jax.default_matmul_precision("highest"):
        x, got, stats = KIT.trunk_of(params, patches, ids, cfg)
        want_x, want = KIT.reference_of(params, patches, ids, sizes)
    assert got.shape == (64, 256) and "head" not in params  # the head is the embedding
    for a, b in ((x, want_x), (got, want)):
        scale = float(jnp.sqrt(jnp.mean(b ** 2)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4 * scale, rtol=0)
    kinds = [cfg.layer_kind(i) for i in range(4)]
    n_attn = sum(op == decoder.ATTENTION for op, _ in kinds)
    n_moe = sum(experts for _, experts in kinds)
    assert float(stats[1]) == n_moe * 64 * 2 / 8  # only the expert layers count tokens
    assert float(stats[2]) == float(stats[3]) == n_attn * 1  # one 64 x 64 tile an attention layer
    assert [float(v) for v in stats[4:]] == [64.0, 1.0]  # tokens and sequences served


# ---------------------------------------------------------------------------
# the gated short convolution, tap by tap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("taps", [(0,), (1,), (2,), (0, 1, 2)])
def test_the_convolution_is_causal_tap_by_tap_with_zeros_before_the_sequence(taps):
    cfg = small(mapping())
    rng = np.random.default_rng(5)
    d, t, batch = 64, 16, 2
    w = np.zeros((d, 3), np.float32)
    w[:, list(taps)] = rng.standard_normal((d, len(taps)))
    p = {"norm1": jnp.ones((d,)), "w_in": jnp.asarray(rng.standard_normal((d, 3 * d)) * 0.2, jnp.float32),
         "conv_w": jnp.asarray(w), "w_out": jnp.asarray(rng.standard_normal((d, d)) * 0.2, jnp.float32)}
    x = jnp.asarray(rng.standard_normal((batch * t, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decoder.gated_short_conv(p, x, batch, cfg) - x, np.float64)
    xs = np.asarray(x, np.float64)
    a = xs / np.sqrt(np.mean(xs ** 2, axis=1, keepdims=True) + 1e-5)
    b, c, z = np.split(a @ np.asarray(p["w_in"], np.float64), 3, axis=1)
    u = (b * z).reshape(batch, t, d)
    conv = np.zeros_like(u)
    for j in taps:  # tap j meets u[t - 2 + j]; nothing lies before a sequence's first token
        back = 2 - j
        conv[:, back:] += w[:, j] * u[:, :t - back]
    want = (c * conv.reshape(batch * t, d)) @ np.asarray(p["w_out"], np.float64)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    if taps == (0,):  # the earliest tap alone: a sequence's first two tokens get nothing
        assert np.abs(got.reshape(batch, t, d)[:, :2]).max() == 0.0


@pytest.mark.parametrize("seq_len,block_rows", [(8, 512), (24, 512), (64, 16), (64, 8)])
def test_taps_kernel_carries_rows_across_tiles_and_stops_at_a_sequence_start(seq_len, block_rows):
    from psana_ray_tpu.ops.short_conv import gated_conv_taps

    rng = np.random.default_rng(seq_len)
    d, batch = 128, 3
    bcz = rng.standard_normal((batch * seq_len, 3 * d)).astype(np.float32)
    w = rng.standard_normal((d, 3)).astype(np.float32)
    got = np.asarray(gated_conv_taps(jnp.asarray(bcz), jnp.asarray(w), seq_len=seq_len,
                                     block_rows=block_rows))
    b, c, z = np.split(bcz.astype(np.float64), 3, axis=1)
    u = (b * z).reshape(batch, seq_len, d)
    padded = np.concatenate([np.zeros((batch, 2, d)), u], axis=1)
    conv = sum(w[:, j] * padded[:, j:j + seq_len] for j in range(3))
    np.testing.assert_allclose(got, c * conv.reshape(-1, d), atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="sequences"):
        gated_conv_taps(jnp.asarray(bcz), jnp.asarray(w), seq_len=seq_len + 4)


# ---------------------------------------------------------------------------
# a batch of sequences
# ---------------------------------------------------------------------------

def test_a_batch_of_two_equals_the_two_alone_and_the_expert_layer_sorts_once(monkeypatch):
    cfg = small(mapping())
    params = loud(decoder.init_params(cfg, jax.random.key(4), jnp.float32))
    patches, ids = inputs(4, batch=2)
    seen = []
    real = decoder.dropless_moe
    monkeypatch.setattr(decoder, "dropless_moe",
                        lambda x, *a, **k: seen.append(x.shape) or real(x, *a, **k))
    with jax.default_matmul_precision("highest"):
        both, stats = decoder.trunk(params, embedded(params, patches, ids), np.arange(64), cfg, 2)
        assert seen == [(128, 64)] * 3  # each expert layer saw ALL the rows: one sort a layer
        alone = [decoder.trunk(params, embedded(params, patches[i:i + 1], ids),
                               np.arange(64), cfg)[0] for i in range(2)]
    # no token of sequence 0 reaches sequence 1, through the convolution or the attention
    np.testing.assert_allclose(np.asarray(both), np.concatenate([np.asarray(a) for a in alone]),
                               atol=2e-6)
    assert [float(v) for v in stats[1:]] == [3 * 128 * 2 / 8, 2.0, 2.0, 128.0, 2.0]
    # and the comparison can tell a leak: the convolution over the batch's rows as ONE sequence
    conv = decoder.gated_short_conv
    monkeypatch.setattr(decoder, "gated_short_conv", lambda p, x, batch, cfg: conv(p, x, 1, cfg))
    with jax.default_matmul_precision("highest"):
        leaky, _ = decoder.trunk(params, embedded(params, patches, ids), np.arange(64), cfg, 2)
    np.testing.assert_allclose(np.asarray(leaky[:64]), np.asarray(alone[0]), atol=2e-6)
    assert float(jnp.abs(leaky[64:66] - alone[1][:2]).max()) > 1e-2  # its first two tokens


@pytest.mark.parametrize("bq,bk", [(16, 32), (32, 16), (48, 32), (96, 96)])
def test_causal_kernel_without_a_mask_is_causal_attention_of_each_sequence(bq, bk):
    rng = np.random.default_rng(0)
    b, s, h, g, d = 2, 96, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, h * d)), jnp.float32) * d ** -0.5
    k, v = (jnp.asarray(rng.standard_normal((b, s, g * d)), jnp.float32) for _ in range(2))
    got = sa.masked_gqa_attention(q, k, v, num_kv_heads=g, block_q=bq, block_k=bk)
    kh, vh = (jnp.repeat(x.reshape(b, s, g, d), h // g, axis=2) for x in (k, v))
    score = jnp.einsum("bthd,bshd->bhts", q.reshape(b, s, h, d), kh, precision="highest")
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(score, -1), vh, precision="highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want.reshape(b, s, h * d)), atol=2e-6)
    assert sa.causal_tile_count(8704) == 17 * 18 // 2 and sa.causal_tile_count(96) == 1


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def test_router_selects_by_score_plus_bias_and_weights_by_the_score_alone():
    s = jnp.asarray([[0.9, 0.8, 0.7, 0.6, 0.5, 0.1]], jnp.float32)
    ids, gates = moe.route_top_k(s, 4, True, select_bias=jnp.zeros(6), gate_eps=1e-6)
    assert ids.tolist() == [[0, 1, 2, 3]]
    np.testing.assert_allclose(np.asarray(gates), np.asarray(s[:, :4] / (3.0 + 1e-6)), rtol=1e-6)
    # a bias too small to swap a choice changes nothing, gates included
    small_bias = jnp.asarray([0, 0, 0, 0, 0.05, 0], jnp.float32)
    ids2, gates2 = moe.route_top_k(s, 4, True, select_bias=small_bias, gate_eps=1e-6)
    assert ids2.tolist() == [[0, 1, 2, 3]]
    np.testing.assert_array_equal(np.asarray(gates2), np.asarray(gates))
    # one large enough does swap it: expert 4 for expert 3, and the gate of 4
    # is its sigmoid score WITHOUT the bias, renormalised over the four chosen
    ids3, gates3 = moe.route_top_k(s, 4, True, select_bias=small_bias * 3, gate_eps=1e-6)
    assert sorted(ids3[0].tolist()) == [0, 1, 2, 4]
    want = {0: 0.9, 1: 0.8, 2: 0.7, 4: 0.5}
    for e, gate in zip(ids3[0].tolist(), np.asarray(gates3[0])):
        assert gate == pytest.approx(want[e] / (2.9 + 1e-6), rel=1e-6)
    # equal selection scores: the lower index; a scale multiplies every gate
    tied = jnp.asarray([[0.5, 0.5, 0.5, 0.2]], jnp.float32)
    ids4, gates4 = moe.route_top_k(tied, 2, False, select_bias=jnp.zeros(4), gate_scale=2.5)
    assert ids4.tolist() == [[0, 1]] and np.asarray(gates4).tolist() == [[1.25, 1.25]]


def _expert_layer(seed, t=64, d=32, width=16, experts=32, k=4):
    rng = np.random.default_rng(seed)
    p = {
        "router": jnp.asarray(rng.standard_normal((d, experts)) * 0.5, jnp.float32),
        "router_bias": jnp.asarray(rng.standard_normal(experts) * 0.3, jnp.float32),
        "w_gate": jnp.asarray(rng.standard_normal((experts, d, width)) * 0.2, jnp.float32),
        "w_up": jnp.asarray(rng.standard_normal((experts, d, width)) * 0.2, jnp.float32),
        "w_down": jnp.asarray(rng.standard_normal((experts, width, d)) * 0.2, jnp.float32),
    }
    m = ref.sizes(mapping(num_experts=experts, num_experts_per_tok=k))
    return p, jnp.asarray(rng.standard_normal((t, d)), jnp.float32), m


@pytest.mark.parametrize("fault", ["none", "softmax", "no_bias"])
def test_expert_layer_under_the_sigmoid_router_is_the_reference_and_not_its_faults(fault):
    p, b, m = _expert_layer(13)
    with jax.default_matmul_precision("highest"):
        y, tokens = moe.dropless_moe(
            b, p["router"], p["w_gate"], p["w_up"], p["w_down"], k=4, num_experts=32,
            scoring="sigmoid", select_bias=p["router_bias"], gate_eps=1e-6)
        faults = {"none": {}, "softmax": {"scoring": "softmax"}, "no_bias": {"select_bias": False}}
        want, chosen = ref.experts(p, b, {**m, **faults[fault]}, jnp.float32)
    if fault == "none":
        np.testing.assert_array_equal(np.asarray(tokens), np.asarray(chosen).sum(axis=0))
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    else:  # what the controls put in the reference's place is another layer
        assert float(jnp.abs(y - want).max()) > 1e-2


# ---------------------------------------------------------------------------
# the configuration's two spellings
# ---------------------------------------------------------------------------

def test_keye_configuration_reads_as_it_did():
    with open(os.path.join(REPO, "benchmark", "configs", "keye_vl2_prefill_epix10k2m.json")) as f:
        got = decoder.DecoderConfig.from_mapping(json.load(f))
    assert got == decoder.DecoderConfig(
        hidden_size=2048, num_layers=4, num_heads=32, num_kv_heads=4, head_dim=128,
        vocab_size=151936, rms_eps=1e-6, rope_theta=1e7, mrope_section=(16, 24, 24),
        indexer_heads=16, indexer_head_dim=64, topk=2048, q_tile=128, kv_tile=512,
        attn_q_tile=256, num_experts=128, experts_per_token=8, expert_width=768,
        experts_held=(0, 128), norm_topk_prob=True, intermediate_size=6144, patch=8)
    # what LFM2 brought is off: attention and experts in every layer, a softmax
    # router without bias, epsilon or scale, a head of its own
    assert [got.layer_kind(i) for i in range(4)] == [(decoder.ATTENTION, True)] * 4
    assert (got.router_scoring, got.expert_bias, got.gate_eps, got.routed_scaling_factor,
            got.tie_embedding) == ("softmax", False, 0.0, 1.0, False)


def test_lfm2_configuration_reads_the_published_keys():
    with open(CONFIG) as f:
        cfg = json.load(f)
    got = decoder.DecoderConfig.from_mapping(cfg)
    assert (got.head_dim, got.rms_eps, got.mrope_section, got.conv_taps) == (64, 1e-5, None, 3)
    assert (got.router_scoring, got.expert_bias, got.gate_eps) == ("sigmoid", True, 1e-6)
    kinds = [got.layer_kind(i) for i in range(got.num_layers)]
    assert kinds[:3] == [("conv", False), ("conv", False), ("full_attention", True)]
    assert sum(op == "full_attention" for op, _ in kinds) == 3 and sum(e for _, e in kinds) == 10
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 3.92e9 < n < 3.94e9  # the file's 3.93 G parameters
    # the cut is the published schedule's first half, and nothing else differs
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:12]
    assert cfg["sequence_tokens"] == 16 * (352 // 16) * (384 // 16) + cfg["prompt_tokens"] == 8704
    assert cfg["step_tokens"] == cfg["batch_size"] * cfg["sequence_tokens"]
    with pytest.raises(ValueError, match="layer_types"):
        decoder.DecoderConfig.from_mapping({**cfg, "num_hidden_layers": 24})


def test_catalog_numbers_are_in_the_file_unchanged():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B"]
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers", "layer_types"}


# ---------------------------------------------------------------------------
# the counters of a batched stream, in snapshot() and under /metrics
# ---------------------------------------------------------------------------

def test_counters_of_a_batched_stream_reach_the_snapshot_and_the_exposition():
    cfg = small(mapping())
    _, snap, text = streamed(cfg)
    steps, tokens = 2, 2 * (2 * 2 * 14 + PROMPT)
    assert snap["decoder_tokens_total"] == steps * tokens
    assert snap["decoder_sequences_total"] == steps * 2
    assert snap["expert_tokens_mean_total"] == steps * 3 * tokens * 2 / 8  # three expert layers
    assert snap["attn_tiles_live_total"] == snap["attn_tiles_causal_total"] == steps * 2 * 1
    for name in decoder.STEP_STATS:
        assert f'psana_ray_{name}{{source="reader"}}' in text, name


# ---------------------------------------------------------------------------
# the cell's counts (its manifest entries: tests/test_manifest_entries.py)
# ---------------------------------------------------------------------------

def test_lfm2_roofline_counts_at_the_published_sizes():
    from benchmark.roofline import decoder as shared
    from benchmark.roofline import lfm2 as roofline

    attn = roofline.causal_attention(4, 8704, 2048, 32, 8)
    assert attn["flops"] == 4 * 64 * 32 * 4 * (8704 * 8705 // 2)  # 1.24 T a layer
    assert attn["bytes"] == 2 * 4 * 8704 * 64 * (2 * 32 + 2 * 8)
    assert shared.grouped_product(34816, 4, 2048, 1792, 32)["flops"] == 2 * 34816 * 4 * 2048 * 1792
    assert roofline.gated_conv_taps(34816, 2048, 3)["bytes"] == 34816 * 2048 * 2 * 4  # 570 MB: 0.70 ms
    fn, [shapes] = need("lfm2_epix_saturated", "lfm2.step")  # as the cell's file asks for it
    step = fn(**shapes)
    assert 53.0e12 < step["flops"] < 53.6e12  # ISSUE 38's 53.2 T, 270 ms at the peak
    experts = 10 * 3 * 2 * 34816 * 4 * 2048 * 1792
    assert 0.55 < experts / step["flops"] < 0.60  # the ten expert layers' products: 30.7 T


def test_peak_flops_share_reads_the_step_and_gives_nothing_without_one():
    import types

    from benchmark.readers import peak_flops_share

    args = {"pattern": "@step", "function": "lfm2.causal_attention",
            "shape_from": {"batch": "b", "tokens": "s", "hidden": "d", "heads": "h", "kv_heads": "g"}}
    assert peak_flops_share.read(types.SimpleNamespace(trace=None), **args) is None
    device = {0: {"XLA Modules": [("jit_lfm2_step(1)", 0.0, 2e6), ("jit_lfm2_step(1)", 5e6, 2e6),
                                  ("jit_other(2)", 8e6, 1e6)]}}
    ctx = types.SimpleNamespace(
        trace=types.SimpleNamespace(device=device), trace_window=(0.0, 1e9),
        cfg={"trace_names": {"step": "jit_lfm2_step"}, "b": 1, "s": 128, "d": 256, "h": 4, "g": 2},
        peaks={"bf16_flops_per_s": 1e12})
    flops = 4 * 64 * 4 * (128 * 129 // 2)
    assert peak_flops_share.read(ctx, **args) == pytest.approx(flops / 1e12 / 2e-3 * 100.0)
