"""Layers that are ONE block each (``models/decoder.py`` reading
Nemotron-H's keys): the state-space scan in several groups of ``B`` and ``C``
with the gated norm by group (``ops/ssd.py``), position-free attention at
many heads a group, UNGATED ``relu^2`` experts through the expert layer's
three paths (``parallel/moe.py``) beside a shared one, against the benchmark's
plain reference (``benchmark/reference/nemotron3_decoder.py``: the recurrence
token by token, every held expert a dense pass) at small sizes on the CPU; the
new cell's configuration file, manifest entries, adapter, counters and counts."""

import dataclasses
import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_kit
from benchmark.reference import nemotron3_decoder as ref
from decoder_kit import F32_PRODUCTS, PROMPT, Kit, checked, embedded, inputs, rehearse, streamed
from psana_ray_tpu.models import decoder
from psana_ray_tpu.ops import ssd
from psana_ray_tpu.parallel import moe
from test_manifest_entries import BENCH

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmark", "configs")
NAME = "nemotron3_nano_prefill_epix10k2m"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "nemotron3_epix_saturated"
PATTERN = "ME*EM-E"  # every kind of block, the dense MLP alone too (no layer of the model: no cell runs it)
# the controls' faults (benchmark/tests/nemotron3_controls.py), at this size's chunk
FAULTS = {"relu": {"act": "relu"}, "gated_silu": {"act": "gated_silu"}, "one_bc": {"one_bc": True},
          "norm_all_channels": {"norm_groups": 1}, "norm_before_gate": {"gate_first": False},
          "state_not_carried": {"carry": 16}, "no_skip": {"skip": False},
          "no_dt_bias": {"dt_bias": False}, "no_conv_bias": {"conv_bias": False},
          "rotary": {"rotary": True}, "softmax_router": {"scoring": "softmax"},
          "no_select_bias": {"select_bias": False}, "scaling_one": {"scale": 1.0},
          "no_shared_expert": {"shared": False}}
# the block a fault sits in: the test's model is that block and one more after it
FAULT_IN = {**{k: "M" for k in ("one_bc", "norm_all_channels", "norm_before_gate", "state_not_carried",
                                "no_skip", "no_dt_bias", "no_conv_bias")}, "rotary": "*"}
# the decoder cells the benchmark had before this one
OTHERS = ("keye_vl2_prefill_epix10k2m", "lfm2_8b_a1b_prefill_epix10k2m", "kimi_k2_prefill_epix10k2m",
          "deepseek_v32_prefill_epix10k2m", "ling3_flash_prefill_epix10k2m",
          "laguna_s21_prefill_epix10k2m", "granite4_h_micro_prefill_epix10k2m",
          "ouro_2p6b_prefill_epix10k2m")


def mapping(**over):
    """Nemotron-H's Hugging Face keys at a small size: every kind of block,
    8 scan heads of 16 over a state of 16 in 2 groups of B and C, 16 experts
    of which 4 a token, all held."""
    m = dict(
        model_type="nemotron_h", hidden_size=64, num_hidden_layers=len(PATTERN),
        hybrid_override_pattern=PATTERN, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        vocab_size=256, layer_norm_epsilon=1e-5, norm_eps=1e-5, rope_theta=10000,
        mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16, n_groups=2, conv_kernel=4,
        use_conv_bias=True, expand=2, chunk_size=128, mamba_proj_bias=False, attention_bias=False,
        mlp_bias=False, use_bias=False, mlp_hidden_act="relu2", n_routed_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
        n_shared_experts=1, intermediate_size=48, n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, tie_word_embeddings=False, patch=8,
    )
    m.update(over)
    return m


loud = functools.partial(decoder_kit.loud, keep=("conv_w",))
# the scan's chunks at most 16 rows: the trunk's 64 tokens cross three chunk edges
PATCHES_OF = {"float32_products": lambda: decoder_kit.float32_products(ssd, ssd.ssd_scan),
              "chunks_of_16": lambda: decoder_kit.chunks_of_16(ssd, ssd.ssd_scan)}
KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=32, causal_kv_tile=32), loud=loud, patches=PATCHES_OF)
small, trunk_of = KIT.small, KIT.trunk_of


@pytest.fixture
def float32_products():
    with PATCHES_OF["float32_products"]():
        yield


def _close(got, want, atol=2e-4):
    scale = float(jnp.sqrt(jnp.mean(jnp.asarray(want) ** 2)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol * scale, rtol=0)


# ---------------------------------------------------------------------------
# the scan in groups of B and C against the recurrence, token by token
# ---------------------------------------------------------------------------

def _scan_case(groups, seed=0, heads=16, p=16, state=16, seq=48, batch=2):
    rng = np.random.default_rng(seed)
    t, wide = batch * seq, heads * p
    xbc = rng.standard_normal((t, wide + 2 * groups * state))
    args = dict(xbc=xbc, z=rng.standard_normal((t, wide)), dt=rng.standard_normal((t, heads)),
                dt_bias=rng.uniform(-4.0, -1.0, heads), a_log=np.log(rng.uniform(1.0, 16.0, heads)),
                skip=rng.uniform(0.5, 1.5, heads), gain=rng.uniform(0.5, 1.5, wide))
    return {k: jnp.asarray(v, jnp.float32) for k, v in args.items()}, dict(
        seq_len=seq, heads=heads, state=state)


def _recurrence(a, seq_len, heads, state, groups, norm_groups=None):
    """The reference's own pieces on the scan's operands: ``ref.scan`` a
    sequence, the skip, the gate and the norm by group."""
    t, wide = a["z"].shape
    p = wide // heads
    m = {"carry": 0, "one_bc": False}
    step = jax.nn.softplus(a["dt"] + a["dt_bias"])
    out = []
    for lo in range(0, t, seq_len):
        rows = slice(lo, lo + seq_len)
        x = a["xbc"][rows, :wide].reshape(seq_len, heads, p)
        b = a["xbc"][rows, wide:wide + groups * state].reshape(seq_len, groups, state)
        c = a["xbc"][rows, wide + groups * state:].reshape(seq_len, groups, state)
        y = ref.scan(x, b, c, step[rows], -jnp.exp(a["a_log"]), m, jnp.float32)
        y = (y + a["skip"][None, :, None] * x).reshape(seq_len, wide) * jax.nn.silu(a["z"][rows])
        y = y.reshape(seq_len, norm_groups or groups, -1)
        y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + 1e-5)
        out.append(y.reshape(seq_len, wide) * a["gain"])
    return jnp.concatenate(out)


@pytest.mark.parametrize("rows", [8, 16, 48])
@pytest.mark.parametrize("groups", [2, 8, 16])
def test_the_scan_in_groups_of_b_and_c_is_the_recurrence_and_the_norm_goes_by_group(
        groups, rows, float32_products):
    """16 heads in 2 groups (8 heads a group: a grid step IS a group, the
    norm closes inside it), in 8 (2 a group) and in 16 (a head alone), in
    chunks of 8, 16 and one of 48 rows, two sequences: float32 products leave
    the form alone, which is exact."""
    a, shape = _scan_case(groups, seed=groups + rows)
    with jax.default_matmul_precision("highest"):
        got = ssd.ssd_scan(a["xbc"], a["z"], a["dt"], a["dt_bias"], a["a_log"], a["skip"],
                           a["gain"], eps=1e-5, rows=rows, **shape)
        want = _recurrence(a, groups=groups, **shape)
        all_channels = _recurrence(a, groups=groups, norm_groups=1, **shape)
    _close(got, want)
    assert float(jnp.abs(all_channels - want).max()) > 1e-2  # the norm over all channels is another


def test_groups_wider_than_a_grid_step_carry_their_norm_across_its_steps(float32_products):
    """32 heads in 2 groups: a group is two grid steps of eight heads, so
    ``C B^T`` is made at the group's first step and the norm closes at its
    last (the carried path, as under ONE group, by group)."""
    a, shape = _scan_case(2, seed=7, heads=32, seq=32, batch=1)
    with jax.default_matmul_precision("highest"):
        got = ssd.ssd_scan(a["xbc"], a["z"], a["dt"], a["dt_bias"], a["a_log"], a["skip"],
                           a["gain"], eps=1e-5, rows=16, **shape)
        want = _recurrence(a, groups=2, **shape)
    _close(got, want)


def test_one_group_of_b_and_c_is_the_kernel_it_was_to_the_traced_equation():
    """Granite's call (64 heads of 64 over a state of 128, ONE B and C, 512
    rows a chunk): its jaxpr, the kernel's body and every index map in it,
    hashed on PR 63's tree and on PR 64's — equal. The groups are a branch
    taken in Python by the operands' shapes;
    :data:`tests.test_chip_compile.PINNED_STEPS` blanks a kernel's body, this
    does not. And a group of its own (Nemotron-H's 8 of 8 heads) is another
    kernel: no scratch but the states."""
    S = jax.ShapeDtypeStruct

    def traced(width):
        return jax.make_jaxpr(lambda xbc, z, dt, b, a, s, g: ssd.ssd_scan(
            xbc, z, dt, b, a, s, g, seq_len=8704, heads=64, state=128, eps=1e-5, interpret=False))(
            S((8704, width), jnp.bfloat16), S((8704, 4096), jnp.bfloat16), S((8704, 64), jnp.float32),
            S((64,), jnp.float32), S((64,), jnp.float32), S((64,), jnp.float32),
            S((4096,), jnp.bfloat16))

    one, eight = str(traced(4096 + 2 * 128)), str(traced(4096 + 2 * 8 * 128))
    assert hashlib.sha256(one.encode()).hexdigest()[:16] == "35edb407ad0065f3"
    assert eight != one and "ssd_scan" in eight


def test_shapes_that_are_no_whole_groups_are_refused():
    a, shape = _scan_case(2)
    rest = (a["z"], a["dt"], a["dt_bias"], a["a_log"], a["skip"], a["gain"])
    with pytest.raises(ValueError, match="whole groups of B and C"):
        ssd.ssd_scan(a["xbc"][:, :-8], *rest, eps=1e-5, **shape)
    three = jnp.concatenate([a["xbc"][:, :256]] + [a["xbc"][:, 256:272]] * 6, axis=1)  # 3 groups, 16 heads
    with pytest.raises(ValueError, match="whole groups of B and C"):
        ssd.ssd_scan(three, *rest, eps=1e-5, **shape)


# ---------------------------------------------------------------------------
# the ungated expert through the expert layer's three paths
# ---------------------------------------------------------------------------

def _expert_layer(seed, experts=32, tokens=64, d=64, width=32):
    """An ungated expert layer's weights (loud), its normed input and the
    reference's reading of it: 32 experts, 4 a token."""
    m = ref.sizes(mapping(n_routed_experts=experts, num_hidden_layers=1, hybrid_override_pattern="E"))
    rng = np.random.default_rng(seed)

    def w(*shape, scale=0.1):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    p = {"router": w(d, experts, scale=0.3), "router_bias": w(experts, scale=0.05),
         "w_up": w(experts, d, width), "w_down": w(experts, width, d),
         "shared_up": w(d, 2 * width), "shared_down": w(2 * width, d)}
    return p, w(tokens, d, scale=1.0), m


def _moe(p, b, held, bias=None, experts=32):
    first, count = held
    return moe.dropless_moe(
        b, p["router"], None, p["w_up"][first:first + count], p["w_down"][first:first + count], k=4,
        num_experts=experts, experts_held=held, scoring="sigmoid",
        select_bias=p["router_bias"] if bias is None else bias, gate_eps=1e-20, gate_scale=2.5)


PATHS = {"all_held": (0, 32), "held_rows_loop": (4, 2), "pass_ahead": (8, 8),
         "pass_ahead_overflowing": (8, 8)}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_ungated_expert_goes_through_each_of_the_layer_s_paths(path):
    """``relu(x W_up)^2 W_down`` where no gate's weights are given: through the
    all-held path, the held rows' loop (a share under 1/8) and the pass ahead
    of it (a quarter; with a bias that sends every choice to the held eight,
    the loop takes what overflows the pass), against the reference's dense
    loop over the held ids; the experts' counts are the reference's chosen
    sets bit for bit."""
    p, b, m = _expert_layer(len(path))
    held = PATHS[path]
    bias = p["router_bias"]
    if path == "pass_ahead_overflowing":
        bias = bias.at[8:16].add(5.0)
    assert (moe.rows_ahead(64 * 4, held[1], 32) > 0) == (path != "held_rows_loop")  # (all held: unused)
    with jax.default_matmul_precision("highest"):
        y, tokens = _moe(p, b, held, bias)
        mine = {k: (v[held[0]:held[0] + held[1]] if k in ("w_up", "w_down") else v)
                for k, v in p.items()}
        want, chosen = ref.experts({**mine, "router_bias": bias}, b, {**m, "experts_held": held},
                                   jnp.float32)
    np.testing.assert_array_equal(  # expert ids, bit for bit: each held expert's slots
        np.asarray(tokens), np.asarray(chosen).sum(0)[held[0]:held[0] + held[1]])
    if path == "pass_ahead_overflowing":
        assert int(np.asarray(tokens).sum()) == 256 > moe.rows_ahead(256, 8, 32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    # and it is no gated layer nor a plain relu
    for fault in ("relu", "gated_silu"):
        other, _ = ref.experts({**mine, "router_bias": bias}, b,
                               {**m, "experts_held": held, "act": fault}, jnp.float32)
        assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-2, fault


def test_the_two_holders_of_an_expert_block_and_the_shared_expert_once_add_up_to_the_uncut_block():
    """The share test: holders ``[0, 16]`` and ``[16, 16]`` of one ``E`` block
    (a half each, as the cell's 64 of 128), the shared expert counted ONCE,
    sum to the uncut reference's block; counted on both holders it does not."""
    p, b, m = _expert_layer(9)
    with jax.default_matmul_precision("highest"):
        parts, served = [], []
        for first in (0, 16):
            y, tokens = _moe(p, b, (first, 16))
            parts.append(np.asarray(y, np.float64))
            served.append(int(np.asarray(tokens).sum()))
        shared = np.asarray(decoder._dense_mlp({"w_up": p["shared_up"], "w_down": p["shared_down"]}, b))
        routed, chosen = ref.experts(p, b, m, jnp.float32)  # the uncut layer: all 32 held
        want = np.asarray(routed + ref.mlp(p["shared_up"], p["shared_down"], b, m, jnp.float32))
    assert sum(served) == 64 * 4 == np.asarray(chosen).sum()  # every slot, once
    assert min(np.abs(part).max() for part in parts) > 0 and np.abs(shared).max() > 0
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    assert np.abs(sum(part + shared for part in parts) - want).max() > 1e-2  # counted twice: no
    # through the block itself: two holders' layers, the shared expert in each, x counted once
    cfg = small(mapping(n_routed_experts=16, router_experts=32, num_hidden_layers=1,
                        hybrid_override_pattern="E", moe_shared_expert_intermediate_size=64))
    x = b * 0.5
    outs = []
    for first in (0, 16):
        held = dataclasses.replace(cfg, experts_held=(first, 16))
        layer = {"norm2": jnp.ones((64,)), "router": p["router"], "router_bias": p["router_bias"],
                 "w_up": p["w_up"][first:first + 16], "w_down": p["w_down"][first:first + 16],
                 "shared_up": p["shared_up"], "shared_down": p["shared_down"]}
        with jax.default_matmul_precision("highest"):
            outs.append(np.asarray(decoder.decoder_layer(layer, x, None, None, held, (None, True))[0]))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.layer({**p, "norm2": jnp.ones((64,))}, x, "E", m))
    beside = np.asarray(x) + np.asarray(ref.mlp(
        p["shared_up"], p["shared_down"], ref.rms(x, jnp.ones((64,)), 1e-5), m, jnp.float32))
    np.testing.assert_allclose(outs[0] + outs[1] - beside, whole, atol=5e-5)


def test_the_gated_experts_three_paths_trace_what_they_traced():
    """The all-held path, the held rows' loop and the pass ahead of it with a
    gate's weights GIVEN: their jaxprs, hashed on PR 63's tree and on PR 64's —
    equal (the expert's hidden activation is ONE function now,
    ``moe.hidden_rows``, and a gated layer's products and their order are
    what its three copies wrote) — and hashed anew in PR 65, knowingly: in all
    three the up product is ``moe.gmm``, which takes the gate's float32 product
    as an operand and ends in the activation, and the float32 up product, the
    ``logistic``, the two multiplies and the rounding between the products are
    gone from the layer's own equations."""
    S = jax.ShapeDtypeStruct

    def traced(held, gated=True, e=32, t=256, d=256, f=128, k=4):
        weights = [S((held[1], d, f), jnp.bfloat16)] * (2 if gated else 1)
        def layer(x, r, *w):
            return moe.dropless_moe(x, r, *((w[0], w[1]) if gated else (None, w[0])), w[-1], k=k,
                                    num_experts=e, experts_held=held, scoring="sigmoid",
                                    gate_eps=1e-20, gate_scale=2.5, interpret=False)
        return str(jax.make_jaxpr(layer)(S((t, d), jnp.bfloat16), S((d, e), jnp.bfloat16), *weights,
                                         S((held[1], f, d), jnp.bfloat16)))

    holders = ((0, 32), (0, 2), (0, 8))
    assert [hashlib.sha256(traced(h).encode()).hexdigest()[:16] for h in holders] == [
        "008f4b8085ef6677", "9b5224621a0d8b83", "8a432e3ee4d91474"]

    def products(text):  # megablox's is a `custom_vjp_call` around its jit, both named gmm
        return text.count("name=gmm") - len(re.findall(r"custom_vjp_call\[\s*name=gmm", text))

    # an ungated layer calls the grouped product twice where a gated one calls it three times
    for h in ((0, 32), (0, 2)):
        assert (products(traced(h)), products(traced(h, gated=False))) == (3, 2)


@pytest.mark.parametrize("gated", [True, False])
def test_the_dense_mlp_s_form_follows_from_the_weights_it_is_given(gated):
    rng = np.random.default_rng(4)
    b = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    p = {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
         for k, s in (("w_gate", (16, 24)), ("w_up", (16, 24)), ("w_down", (24, 16)))}
    if not gated:
        del p["w_gate"]
    with jax.default_matmul_precision("highest"):
        got = decoder._dense_mlp(p, b)
    hidden = jax.nn.silu(b @ p["w_gate"]) * (b @ p["w_up"]) if gated else jnp.maximum(b @ p["w_up"], 0) ** 2
    np.testing.assert_allclose(np.asarray(got), np.asarray(hidden @ p["w_down"]), rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("n,fits,want", [(1856, 780, 640), (1856, 2048, 1856), (2560, 2730, 2560),
                                         (1792, 1024, 896), (3072, 2048, 1536)])
def test_a_width_no_lane_tile_divides_goes_in_the_tile_that_computes_the_fewest_columns(n, fits, want):
    """``grouped_tiles``' output tile: the ungated experts' 1,856 = 14.5 lane
    tiles has no 128-multiple divisor: three tiles of 640 compute 1,920 columns
    (768 would compute 2,304, 512 2,048); the widths that have one are as they were."""
    k = 4 * 2 ** 20 // (2 * fits)  # the contraction at which a 4 MiB weight tile holds `fits` columns
    assert moe.grouped_tiles(4096, 8, k - k % 128, n, 2)[2] == want


def test_the_cell_s_grouped_products_tiles():
    assert moe.grouped_tiles(156672, 64, 2688, 1856, 2) == (128, 2688, 640)   # the pass's up product (no gate)
    assert moe.grouped_tiles(156672, 64, 1856, 2688, 2) == (256, 1856, 896)   # and its down product
    assert moe.rows_ahead(34816 * 6, 64, 128) == 156672  # 1.5 even shares of 104,448


# ---------------------------------------------------------------------------
# each block alone, and the trunk, against the reference: float32, a batch of two
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("letter", ["M", "*", "E", "-"])
def test_a_block_alone_is_the_reference_s_at_all_positions_of_a_batch_of_two(letter):
    """A model of ONE layer of each kind: ``x + Mixer(rms(x))`` and nothing
    else (an ``M`` or ``*`` block has no feed-forward and no second norm, an
    ``E`` or ``-`` block no operator and no first), two sequences."""
    one = dict(num_hidden_layers=1, hybrid_override_pattern=letter)
    layer, = KIT.params(2, over=one)["layers"]
    assert ("norm1" in layer, "norm2" in layer) == ((True, False) if letter in "M*" else (False, True))
    assert "w_gate" not in layer and "shared_gate" not in layer  # ungated: two matrices an MLP
    assert ("wq" in layer, "w_in" in layer, "router" in layer) == (
        letter == "*", letter == "M", letter == "E")
    x, got, stats = KIT.trunk(2, batch=2, over=one, under=(*F32_PRODUCTS, "chunks_of_16"))
    want_x, want = KIT.reference(2, batch=2, over=one)
    _close(x, want_x)
    _close(got, want)
    assert len(stats) == (12 if letter == "M" else 6)  # no share held: the lengths such steps had


def test_the_trunk_of_single_blocks_matches_the_reference_and_counts_by_block():
    cfg = small(mapping())
    x, got, stats = KIT.trunk(3, batch=2, under=(*F32_PRODUCTS, "chunks_of_16"))
    want_x, want = KIT.reference(3, batch=2)
    assert x.dtype == jnp.float32
    _close(x, want_x)
    _close(got, want)
    names = (decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS + decoder.LINEAR_STATS
             + decoder.AHEAD_STATS)
    assert len(stats) == 12 and cfg.layer_stats == 10 and not cfg.rows_go_ahead  # every expert held
    s = dict(zip(names, (float(v) for v in stats)))
    # a block without experts or without a scan contributes zeros: 2 M, 1 *, 3 E, 1 -
    assert s["linear_attn_tokens_total"] == 2 * 128 and s["linear_attn_chunks_total"] == 2 * 2 * 8 * 4
    assert s["attn_tiles_causal_total"] == s["attn_tiles_live_total"] == 2
    assert s["expert_rows_routed_total"] == s["expert_rows_held_total"] == 3 * 128 * 4
    assert s["expert_tokens_mean_total"] == 3 * 128 * 4 / 16 <= s["expert_tokens_max_total"]
    assert s["decoder_tokens_total"] == 128 and s["decoder_sequences_total"] == 2
    assert s["attn_pairs_causal_total"] == s["attn_pairs_selected_total"] == 0


def test_a_share_holder_s_trunk_is_the_reference_given_the_same_share():
    """8 of 16 experts held (the cell's half): the reference is given the held
    experts' weights and the router's whole width, as the adapter gives them."""
    share = dict(n_routed_experts=8, router_experts=16, experts_held=[0, 8])
    params = KIT.params(5, over=share)
    assert params["layers"][1]["w_up"].shape == (8, 64, 32) and params["layers"][1]["router"].shape == (64, 16)
    x, got, stats = KIT.trunk(5, batch=2, over=share, under=F32_PRODUCTS)
    want_x, want = KIT.reference(5, batch=2, over=share)
    _close(x, want_x)
    _close(got, want)
    held, routed, ahead = float(stats[6]), float(stats[7]), float(stats[12])
    assert 0 < ahead <= held < routed == 3 * 128 * 4


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_with_a_control_s_fault_in_it_is_another_trunk(fault):
    """Each of the controls' faults moves the reference's own output by far
    more than the program lies from it. What no limit on the chip catches
    under random weights (PERF.md section 4) is held HERE."""
    two = dict(num_hidden_layers=2, hybrid_override_pattern=FAULT_IN.get(fault, "E") + "*")
    x = KIT.trunk(5, over=two, under=F32_PRODUCTS)[0]  # made once a pattern (three of them), as the clean reference
    want, other = KIT.reference(5, over=two)[0], KIT.reference(5, over=two, **FAULTS[fault])[0]
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    near = float(jnp.sqrt(jnp.mean((x - want) ** 2))) / scale
    far = float(jnp.sqrt(jnp.mean((other - want) ** 2))) / scale
    assert near < 1e-5 and far > max(100 * near, 1e-3), (near, far)


def test_without_a_rotary_an_attention_block_reads_no_position(float32_products):
    cfg = small(mapping(num_hidden_layers=1, hybrid_override_pattern="*"))
    params = loud(decoder.init_params(cfg, jax.random.key(8), jnp.float32))
    patches, ids = inputs(8)
    x = embedded(params, patches, ids, cfg)
    here, _ = decoder.trunk(params, x, np.arange(64), cfg)
    there, _ = decoder.trunk(params, x, np.arange(64) + 1000, cfg)
    np.testing.assert_array_equal(np.asarray(here), np.asarray(there))
    assert not cfg.rotary and not cfg.qk_norm and cfg.softmax_scale == 16 ** -0.5


def test_a_sequence_of_the_batch_does_not_read_its_neighbour_s_state_or_taps():
    """Sequence 1 of a batch of two, alone and after another neighbour: the
    same rows (the state AND the convolution stop at a sequence's edge)."""
    cfg = small(mapping(num_hidden_layers=3, hybrid_override_pattern="M*M"))
    params = loud(decoder.init_params(cfg, jax.random.key(11), jnp.float32))
    patches, ids = inputs(11, batch=2)
    both, _, _ = trunk_of(params, patches, ids, cfg)
    alone, _, _ = trunk_of(params, patches[1:], ids, cfg)
    swapped, _, _ = trunk_of(params, patches[::-1], ids, cfg)
    np.testing.assert_allclose(np.asarray(both[64:]), np.asarray(alone), atol=1e-5)
    np.testing.assert_allclose(np.asarray(both[64:]), np.asarray(swapped[:64]), atol=1e-5)
    as_one, _ = jax.jit(lambda p: decoder.trunk(
        p, embedded(p, patches, ids, cfg), np.arange(128), cfg, 1))(params)
    assert float(jnp.abs(as_one[64:] - both[64:]).max()) > 1e-2


# ---------------------------------------------------------------------------
# the ninth spelling
# ---------------------------------------------------------------------------

def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        return next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


def _file(name=NAME):
    with open(os.path.join(CONFIGS, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def test_from_mapping_reads_the_catalog_row_s_keys():
    got = decoder.DecoderConfig.from_mapping(_catalog_row()["config"])
    kinds = [got.layer_kind(i) for i in range(got.num_layers)]
    assert got.num_layers == 52 and got.single_block
    assert (kinds.count((decoder.MAMBA, None)), kinds.count((None, True)),
            kinds.count((decoder.ATTENTION, None))) == (23, 23, 6)
    # n_groups 8 is the scan's, n_group 1 the router's: two keys one letter apart
    assert (got.ssm_groups, got.router_groups, got.router_groups_kept) == (8, 1, 1)
    assert (got.ssm_heads, got.ssm_head_dim, got.ssm_state, got.conv_taps, got.conv_bias) == (
        64, 64, 128, 4, True)
    assert (got.num_heads, got.num_kv_heads, got.head_dim, got.hidden_size) == (32, 2, 128, 2688)
    assert (got.num_experts, got.experts_per_token, got.expert_width, got.shared_experts) == (
        128, 6, 1856, 2)  # the shared expert 2 x 1,856 = 3,712 wide, whatever n_shared_experts counts
    assert (got.router_scoring, got.expert_bias, got.gate_eps, got.routed_scaling_factor,
            got.norm_topk_prob) == ("sigmoid", True, 1e-20, 2.5, True)
    assert (got.mlp_act, got.rotary, got.qk_norm, got.rms_eps, got.tie_embedding) == (
        "relu2", False, False, 1e-5, False)
    assert got.stream_dtype is None and got.passes == 1 and got.holds_a_share is False


def test_the_pattern_s_letters_each_name_one_block_and_an_unknown_one_is_refused():
    m = mapping()
    got = decoder.DecoderConfig.from_mapping(m)
    assert [got.layer_kind(i) for i in range(7)] == [
        ("mamba", None), (None, True), ("full_attention", None), (None, True), ("mamba", None),
        (None, False), (None, True)]
    # the first num_hidden_layers letters are read: a cut of the depth keeps the published string
    assert decoder.DecoderConfig.from_mapping({**m, "num_hidden_layers": 3}).layer_types == (
        "mamba", "moe", "full_attention")
    for pattern in ("MEXEM-E", "ME*E", "me*em-e"):
        with pytest.raises(ValueError, match="hybrid_override_pattern"):
            decoder.DecoderConfig.from_mapping({**m, "hybrid_override_pattern": pattern})
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        decoder.DecoderConfig.from_mapping({**m, "mlp_hidden_act": "gelu"})
    with pytest.raises(ValueError, match="groups of B and C"):
        decoder.DecoderConfig.from_mapping({**m, "n_groups": 3})
    # another model's file cannot name a block without an operator
    with pytest.raises(ValueError, match="layer_types"):
        decoder.DecoderConfig.from_mapping({**_file("lfm2_8b_a1b_prefill_epix10k2m"),
                                            "layer_types": ["moe"] * 12})


def test_the_file_holds_the_catalog_s_numbers_unchanged_and_names_its_cuts():
    row, cfg = _catalog_row(), _file()
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["hybrid_override_pattern"] == row["config"]["hybrid_override_pattern"]  # kept whole
    assert cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]] == "MEMEM*EMEMEM*E"
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["deployment"].startswith("2 chips share each layer")
    assert (cfg["n_routed_experts"], cfg["router_experts"], cfg["experts_held"]) == (64, 128, [0, 64])
    assert cfg["batch_size"] * cfg["sequence_tokens"] == cfg["step_tokens"] == 34816
    assert not [k for k in cfg["reduced"] if k.endswith(("_dim", "_rank", "hidden_size"))
                or "intermediate" in k]  # no width is cut
    got = decoder.DecoderConfig.from_mapping(cfg)
    assert got.holds_a_share and got.rows_go_ahead and got.layer_stats == 11
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    weights = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(weights - 4585.6e6) < 0.5e6  # 9.17 GB in bf16, as the file's `deployment` adds it up
    assert shapes["layers"][0]["w_in"].shape == (2688, 4096 + 6144 + 64)
    assert shapes["layers"][1]["w_up"].shape == (64, 2688, 1856) and "w_gate" not in shapes["layers"][1]
    assert shapes["layers"][1]["shared_up"].shape == (2688, 3712)
    assert shapes["layers"][5]["wq"].shape == (2688, 4096) and shapes["layers"][5]["wk"].shape == (2688, 256)
    assert shapes["head"].shape == (2688, 65536)


@pytest.mark.parametrize("name", OTHERS)
def test_the_other_eight_readers_have_nothing_of_what_this_one_brought(name):
    got = decoder.DecoderConfig.from_mapping(_file(name))
    assert (got.single_block, got.mlp_act, got.ssm_groups) == (False, "silu", 1)
    kinds = [got.layer_kind(i) for i in range(got.num_layers)]
    assert all(op is not None and experts is not None for op, experts in kinds)  # pairs, as they were
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    for layer in shapes["layers"]:
        assert {"norm1", "norm2", "w_gate", "w_up", "w_down"} <= set(layer)
        assert ("shared_up" in layer) == ("shared_gate" in layer)


def test_the_cell_runs_this_configuration_under_saturated_traffic_on_one_chip():
    cell = BENCH.cell(CELL)
    assert cell == {**cell, "config": NAME, "traffic": "saturated", "chips": 1}
    cfg = _file()
    assert cfg["transport"] == {"scheme": "shm", "slots": 16} and cfg["program"] == "prefill_blocks"
    assert cfg["reference"]["module"] == "nemotron3_decoder"


def test_nemotron3_roofline_counts_at_the_published_sizes():
    from benchmark.roofline import nemotron3

    cfg = _file()
    step = nemotron3.step(
        batch=cfg["batch_size"], tokens=cfg["sequence_tokens"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], pattern=cfg["hybrid_override_pattern"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        scan_heads=cfg["mamba_num_heads"], scan_head_dim=cfg["mamba_head_dim"],
        state=cfg["ssm_state_size"], groups=cfg["n_groups"], taps=cfg["conv_kernel"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"], dense_width=cfg["intermediate_size"],
        experts=cfg["router_experts"], held=cfg["n_routed_experts"], per_token=cfg["num_experts_per_tok"],
        vocab=cfg["vocab_size"], prompt=cfg["prompt_tokens"], patch=cfg["patch"])
    assert abs(step["flops"] - 45.99e12) < 0.02e12 and step["bytes"] == 0.0  # 233 ms at 197 TFLOP/s
    scan = nemotron3.ssd_scan(4, 8704, 64, 64, 128, 8)
    one = nemotron3.ssd_scan(4, 8704, 64, 64, 128, 1)
    assert scan["flops"] == one["flops"] == 5 * 64 * 128 * 64 * 34816
    assert scan["bytes"] - one["bytes"] == 34816 * 7 * 2 * 2 * 128  # B and C once a GROUP
    products = nemotron3.held_products(34816, 6, 2688, 1856, 64, 14, cfg["hybrid_override_pattern"], 0.5)
    assert products["call_sites"] == 12  # TWO an expert layer
    assert products["flops"] == 12 * 2 * 104448 * 2688 * 1856
    attention = nemotron3.causal_attention(4, 8704, 32, 2, 128)
    assert attention["flops"] == 4 * 128 * 32 * 4 * (8704 * 8705 // 2)


@pytest.mark.parametrize("lacks", ["single_block", "ssm_groups", "mlp_act"])
def test_the_adapter_ends_the_run_where_the_package_lacks_the_mechanism(monkeypatch, lacks):
    from benchmark.programs import prefill_blocks

    older = dataclasses.make_dataclass(
        "Older", [(f.name, f.type, dataclasses.field(default=None))
                  for f in dataclasses.fields(decoder.DecoderConfig) if f.name != lacks], frozen=True)
    monkeypatch.setattr(decoder, "DecoderConfig", older)
    with pytest.raises(SystemExit) as e:
        prefill_blocks.Program({"name": NAME}, 1, "", None)
    assert e.value.code not in (0, None) and lacks in str(e.value.code)


def test_the_adapter_ends_the_run_where_the_file_counts_other_experts_than_it_holds():
    from benchmark.programs import prefill_blocks

    with pytest.raises(SystemExit) as e:
        prefill_blocks.Program({**_file(), "n_routed_experts": 128}, 1, "", None)
    assert "is not the count of experts_held" in str(e.value.code)


def test_the_cell_s_share_of_rows_is_the_batched_adapter_s_own_with_nothing_laid_over():
    from benchmark.programs import prefill_batched, prefill_blocks

    # a holder of HALF the experts reads 0-45% of a part's rows over the limit (PERF.md section 4)
    assert prefill_blocks.TOSSED_ROWS_SHARE == prefill_batched.TOSSED_ROWS_SHARE == 0.7
    assert prefill_blocks.Program.check is prefill_batched.Program.check
    assert prefill_blocks.STEP_NAME == "nemotron3_step" == _file()["trace_names"]["step"][4:]


def test_single_block_counters_reach_the_snapshot_and_the_exposition():
    cfg = small(mapping(num_hidden_layers=4, hybrid_override_pattern="ME*E", n_routed_experts=8,
                        router_experts=16, experts_held=[0, 8]))
    _, snap, text = streamed(cfg)
    steps, s = 2, 2 * 2 * 14 + PROMPT  # 64 tokens a frame, two frames a step
    assert snap["decoder_tokens_total"] == steps * 2 * s
    assert snap["linear_attn_tokens_total"] == steps * 2 * s  # ONE block with a scan
    assert snap["linear_attn_chunks_total"] == steps * 2 * 8 * (s // ssd.scan_rows(s))
    assert snap["attn_tiles_causal_total"] == steps * 2  # one attention block, one tile a sequence
    assert 0 < snap["expert_rows_held_total"] < snap["expert_rows_routed_total"] == steps * 2 * 2 * s * 4
    assert 0 < snap["expert_rows_ahead_total"] <= snap["expert_rows_held_total"]
    for name in (decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS
                 + decoder.LINEAR_STATS + decoder.AHEAD_STATS):
        assert f'psana_ray_{name}{{source="reader"}}' in text, name


def test_the_cell_s_rehearsal_runs_the_served_path_and_is_correct():
    line, done = rehearse(CELL, seed=1)
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0 and line["cell"] == CELL
    for name in ("ring_depth.hit", "device_wait_ms.hit", "h2d_ms.hit", "startup_trace_s"):
        assert name in line["would_report"], name
    verdict = checked(done)
    assert verdict["isolated.0"]["ok"] and verdict["isolated.1"]["ok"]
    assert verdict["patch_rows.1"]["ok"] and verdict["first_rows.1"]["rows"] == 24  # for the record
