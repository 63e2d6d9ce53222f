"""The gated delta rule with ONE decay a head over a rectangular state
(``ops/delta_rule.gated_delta_net``), position-free multi-head attention
under a norm over the whole q / k projections, OLMo 2's reordered norm, and
the trunk that mixes them (``models/decoder.py`` reading Olmo-Hybrid's keys)
against the benchmark's plain reference
(``benchmark/reference/olmo_hybrid_decoder.py``: the recurrence token by
token) at small sizes on the CPU; the other reading of every assumption that
has one; the new cell's manifest entries, counters, counts and adapter; and
that Ling-3.0's kernel is the equation it was."""

import dataclasses
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_kit
from benchmark.reference import olmo_hybrid_decoder as ref
from benchmark.roofline import olmo_hybrid as roofline
from decoder_kit import F32_PRODUCTS, PROMPT, Kit, embedded, inputs, rehearse, streamed
from psana_ray_tpu.models import decoder
from psana_ray_tpu.ops import delta_rule as dr
from psana_ray_tpu.parallel import sparse_attention as sa
from test_manifest_entries import BENCH

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "olmo_hybrid_7b_prefill_epix10k2m.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "olmo_hybrid_epix_saturated"
GDN, MHA = decoder.LINEAR, decoder.ATTENTION
# the controls' faults (benchmark/tests/olmo_hybrid_controls.py), at this size's chunk
FAULTS = {"bf16_state": {"state": "bfloat16"}, "state_not_carried": {"carry": 16},
          "beta_without_its_2": {"beta_scale": 1.0}, "no_decay": {"decay": "none"},
          "first_head_s_decay": {"decay": "first"}, "sigmoid_gate": {"gate": "sigmoid"},
          "gate_before_norm": {"gate_first": True}, "no_l2_norm": {"l2": False},
          "latest_taps_only": {"taps_used": (2, 3)}, "norm_before_branch": {"norm_place": "before"},
          "qk_norm_a_head": {"qk_norm": "head"}, "rotary": {"rotary": True}}
# the assumptions of the file that have an OTHER reading, as the reference spells it
OTHER_READINGS = {"norm_before_the_branch": {"norm_place": "before"}, "a_rotary_at_500000": {"rotary": True},
                  "qk_norm_a_head": {"qk_norm": "head"}, "gate_before_the_norm": {"gate_first": True}}


def mapping(**over):
    """Olmo-Hybrid's Hugging Face keys at a small size: one period (three
    linear layers, one full), 6 heads with keys 24 and values 48 wide (``d_k !=
    d_v``, a head count no power of two), attention heads of 16."""
    m = dict(
        model_type="olmo_hybrid", hidden_size=64, num_hidden_layers=4,
        layer_types=[GDN, GDN, GDN, MHA], num_attention_heads=6, num_key_value_heads=6, head_dim=16,
        vocab_size=256, rms_norm_eps=1e-6, intermediate_size=96, hidden_act="silu",
        attention_bias=False, tie_word_embeddings=False, linear_num_key_heads=6,
        linear_num_value_heads=6, linear_key_head_dim=24, linear_value_head_dim=48,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
        patch=8,
    )
    m.update(over)
    return m


# loud: the taps are of order 1 as drawn; the gains and the gate's own vectors stay
loud = functools.partial(decoder_kit.loud, keep=("conv_",))
PATCHES_OF = {"float32_products": lambda: decoder_kit.float32_products(dr, dr.gated_delta_net)}
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

def _kernel_case(case, heads, d_k, d_v, seq, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    t = batch * seq
    q, k = (rng.standard_normal((t, heads * d_k)) for _ in range(2))
    v, z = (rng.standard_normal((t, heads * d_v)) for _ in range(2))
    a = 2.0 * rng.standard_normal((t, heads))
    beta = rng.uniform(0.05, 1.95, (t, heads))
    a_log, dt_bias = np.log(rng.uniform(1.0, 16.0, heads)), rng.uniform(-6.0, 1.0, heads)
    if case == "decay_at_minus_8_a_row":  # the unbounded gate: e^-1024 over a chunk of 128
        a_log, dt_bias, a = np.full(heads, np.log(8.0)), np.full(heads, 1.0), np.zeros((t, heads))
        dt_bias = np.full(heads, float(np.log(np.expm1(1.0))))  # softplus -> 1: g = -8 exactly
    elif case == "decay_near_none":  # -1e-3 a row and less: the plain delta rule
        a_log, dt_bias, a = np.zeros(heads), np.full(heads, float(np.log(np.expm1(1e-3)))), np.zeros((t, heads))
    elif case == "identical_keys":  # a detector's blank patches: one key again and again, kept
        q, k, v = (np.tile(u[:1], (t, 1)) for u in (q, k, v))
        a_log, dt_bias, a = np.zeros(heads), np.full(heads, -12.0), np.zeros((t, heads))
        beta = np.full((t, heads), 0.9)
    elif case == "beta_near_2":
        beta = np.full((t, heads), 2.0 - 1e-3)
    elif case == "beta_near_0":
        beta = np.full((t, heads), 1e-3)
    arrays = [jnp.asarray(u, jnp.float32) for u in (q, k, v, a, z, beta, a_log, dt_bias,
                                                    rng.uniform(0.5, 1.5, d_v))]
    return arrays, dict(seq_len=seq, heads=heads, key_dim=d_k, eps=1e-6)


def _recurrence(arrays, seq_len, heads, key_dim, eps, gate="silu", **fault):
    """The reference's own lines on the kernel's operands, sequence by sequence."""
    q, k, v, a, z, beta, a_log, dt_bias, gain = arrays
    t, d_v = q.shape[0], v.shape[1] // heads
    m = {"carry": 0, "state": "float32", **fault}
    q, k, v = q.reshape(t, heads, key_dim), k.reshape(t, heads, key_dim), v.reshape(t, heads, d_v)
    q, k = (u / jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True) + ref.L2_EPS) for u in (q, k))
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    out = [ref.delta_rule(q[lo:lo + seq_len] * key_dim ** -0.5, k[lo:lo + seq_len], v[lo:lo + seq_len],
                          g[lo:lo + seq_len], beta[lo:lo + seq_len], m, jnp.float32)
           for lo in range(0, t, seq_len)]
    act = jax.nn.silu if gate == "silu" else jax.nn.sigmoid
    o = ref.rms(jnp.concatenate(out), gain, eps) * act(z.reshape(t, heads, d_v))
    return o.reshape(t, heads * d_v), g


# heads 6 of 24 -> 48 and the published 30 of 96 -> 192; sequences of 48 rows in chunks of 8, 16 and
# 48 (one chunk), and of 256 in the SERVED chunk of 128 (seven levels of the inverse)
CASES = ("spread_decay", "decay_at_minus_8_a_row", "decay_near_none", "beta_near_2", "beta_near_0",
         "identical_keys")
CHUNKED = [(case, 6, 24, 48, 16, 48) for case in CASES]
CHUNKED += [(case, 6, 24, 48, chunk, 48) for chunk in (8, 48) for case in ("spread_decay", "identical_keys")]
CHUNKED += [(case, 30, 96, 192, 16, 32) for case in ("spread_decay", "decay_at_minus_8_a_row")]
CHUNKED += [(case, 6, 96, 192, 128, 256) for case in ("spread_decay", "decay_at_minus_8_a_row")]


@pytest.mark.parametrize("case,heads,d_k,d_v,chunk,seq", CHUNKED,
                         ids=[f"{c}-{h}x{k}x{v}-{n}" for c, h, k, v, n, _ in CHUNKED])
def test_the_chunked_form_is_the_recurrence_and_not_an_approximation(case, heads, d_k, d_v, chunk,
                                                                     seq, float32_products):
    """Two sequences in one array (a boundary inside it): with float32
    products the kernel IS the token-by-token recurrence to float32's own
    rounding over ``d_k != d_v``, head counts 6 and 30, step sizes up to 2
    and log-decays from -1e-3 to -8 a row — nothing overflows under the
    unbounded gate, and what underflows is the true value's own underflow."""
    arrays, sizes = _kernel_case(case, heads, d_k, d_v, seq)
    with jax.default_matmul_precision("highest"):
        got = dr.gated_delta_net(*arrays, chunk=chunk, group=2, **sizes)
        want, g = _recurrence(arrays, **sizes)
    if case == "decay_at_minus_8_a_row":
        assert float(g.max()) < -7.999
    if case in ("decay_near_none", "identical_keys"):
        assert float(g.min()) > -1.01e-3
    assert dr.chunk_rows(seq, chunk) == chunk and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=0)
    # a state that crossed the sequences' boundary, or stopped at a chunk's, is another result
    seen = {"decay_at_minus_8_a_row": 1e-5, "identical_keys": 1e-3}.get(case, 1e-2)
    if (d_k, chunk) == (24, 16):
        leaked, _ = _recurrence(arrays, **{**sizes, "seq_len": 2 * seq})
        assert float(jnp.abs(leaked[seq:] - want[seq:]).max()) > seen
        if seen == 1e-2:
            dropped, _ = _recurrence(arrays, carry=chunk, **sizes)
            assert float(jnp.abs(dropped - want).max()) > 1e-2
    # the state resets: the second sequence ALONE is what it was in the pair, exactly
    alone = dr.gated_delta_net(*(u[seq:] if u.ndim == 2 and u.shape[0] == 2 * seq else u
                                 for u in arrays), chunk=chunk, group=2, **sizes)
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(got[seq:]))


@pytest.mark.parametrize("group", [2, 3])
def test_bf16_products_keep_the_kernel_within_their_rounding_of_the_recurrence(group):
    """At the served products' precision, whatever the heads a grid step; the
    gate is the head form's SiLU (a sigmoid there is another result)."""
    arrays, sizes = _kernel_case("spread_decay", 6, 24, 48, 48)
    got = dr.gated_delta_net(*arrays, chunk=16, group=group, **sizes)
    want, _ = _recurrence(arrays, **sizes)
    assert float(jnp.sqrt(jnp.mean((got - want) ** 2)) / jnp.sqrt(jnp.mean(want ** 2))) < 2e-2
    other, _ = _recurrence(arrays, gate="sigmoid", **sizes)
    assert float(jnp.abs(other - want).max()) > 0.1


def test_heads_at_whole_lane_tiles_are_the_heads_they_were_to_the_bit():
    """``lanes_a_head``: 96-wide heads at 128 columns each, zeros after them,
    change no L2 norm, no product and no row of a state; and the rule for the
    heads a grid step takes whole tiles only: 30 heads of 128 + 192 -> 6."""
    arrays, sizes = _kernel_case("spread_decay", 6, 24, 48, 48)
    q, k = (dr.lanes_a_head(u, 6, lanes=32) for u in arrays[:2])
    assert q.shape == (96, 6 * 32) and float(jnp.abs(q.reshape(96, 6, 32)[..., 24:]).max()) == 0.0
    np.testing.assert_array_equal(
        np.asarray(dr.gated_delta_net(q, k, *arrays[2:], chunk=16, **sizes)),
        np.asarray(dr.gated_delta_net(*arrays, chunk=16, **sizes)))
    assert dr.lanes_a_head(arrays[2], 3, lanes=32) is arrays[2]  # 96 wide: whole tiles already
    assert (dr.head_group(30, 128, 192), dr.head_group(30, 128, 192, 4), dr.head_group(30, 96, 192),
            dr.head_group(30, 96, 192, tiles=False)) == (6, 2, 30, 6)
    with pytest.raises(ValueError, match="delta rule"):
        dr.gated_delta_net(*arrays, chunk=16, **{**sizes, "key_dim": 32})  # wider than its columns


def test_ling3_s_kernel_is_the_equation_it_was():
    """Ling-3.0's call (a decay per channel, 32 heads of 128, four a grid
    step, chunks of 128 in blocks of 16): its jaxpr, the kernel's body in it,
    hashed on PR 66's tree and on PR 67's — equal. The two forms share the
    inverse by halves, the constants, the running sums and ``_chunks``; which
    products feed them is a branch taken in Python by the function called."""
    S = jax.ShapeDtypeStruct
    rows, heads, d = 4 * 8704, 32, 128
    wide = heads * d
    jaxpr = jax.make_jaxpr(lambda qkv, f, z, beta, la, b, g: dr.gated_delta_rule(
        qkv, f, z, beta, la, b, g, seq_len=8704, heads=heads, lower=-5.0, eps=1e-6,
        interpret=False))(
            S((rows, 3 * wide), jnp.bfloat16), S((rows, wide), jnp.float32), S((rows, wide), jnp.bfloat16),
            S((rows, heads), jnp.float32), S((heads,), jnp.float32), S((wide,), jnp.float32),
            S((d,), jnp.float32))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == "1b47e3732029a690"


# ---------------------------------------------------------------------------
# the trunk against the reference, float32, all positions, a batch of two
# ---------------------------------------------------------------------------

def test_the_hybrid_trunk_matches_the_reference_at_all_positions_of_a_batch_of_two():
    cfg = small(mapping())
    x, got, stats = KIT.trunk(3, batch=2, under=F32_PRODUCTS)
    want_x, want = KIT.reference(3, batch=2)
    for a, b in ((x, want_x), (got, want)):
        scale = float(jnp.sqrt(jnp.mean(b ** 2)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4 * scale, rtol=0)
    # twelve statistics, granite's groups (no experts, nothing selected): at heads of 16 no causal call
    # takes a block of heads (the counters' test below runs heads of 128, and counts seventeen)
    names = decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS + decoder.LINEAR_STATS
    assert len(stats) == len(names) == 12 and cfg.layer_stats == 10
    got_stats = dict(zip(names, (float(v) for v in stats)))
    assert (got_stats["decoder_tokens_total"], got_stats["decoder_sequences_total"]) == (128, 2)
    assert got_stats["linear_attn_tokens_total"] == 3 * 128  # three linear layers
    assert got_stats["linear_attn_chunks_total"] == 3 * 2 * 6 * (64 // 16)
    assert got_stats["attn_tiles_causal_total"] == got_stats["attn_tiles_live_total"] == 2
    assert got_stats["expert_tokens_max_total"] == got_stats["expert_rows_routed_total"] == 0


def test_a_sequence_of_the_batch_does_not_read_its_neighbour_s_state_or_taps():
    cfg = small(mapping())
    params = loud(decoder.init_params(cfg, jax.random.key(7), jnp.float32))
    patches, ids = inputs(7, batch=2)
    run = jax.jit(lambda p, x: decoder.trunk(p, x, np.arange(64), cfg, 2)[0])
    x = run(params, embedded(params, patches, ids))
    moved = run(params, embedded(params, patches[::-1], ids))
    np.testing.assert_array_equal(np.asarray(x[:64]), np.asarray(moved[64:]))
    np.testing.assert_array_equal(np.asarray(x[64:]), np.asarray(moved[:64]))


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_with_a_control_s_fault_in_it_is_another_trunk(fault):
    x, want = KIT.trunk(5, under=F32_PRODUCTS)[0], KIT.reference(5, **FAULTS[fault])[0]
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    # what a control puts in is seen; a bf16 state's rounding is small and still no float32 state
    seen = 1e-3 if fault == "bf16_state" else 1e-2
    assert float(jnp.abs(x - want).max()) > seen * scale


@pytest.mark.parametrize("reading", sorted(OTHER_READINGS))
def test_an_assumption_s_other_reading_is_another_model(reading):
    """Each ``assumed`` entry of the file that names another reading: the
    reference computes both, the program is the first, and the two differ."""
    x, same = KIT.trunk(5, under=F32_PRODUCTS)[0], KIT.reference(5)[0]
    other = KIT.reference(5, **OTHER_READINGS[reading])[0]
    scale = float(jnp.sqrt(jnp.mean(same ** 2)))
    assert float(jnp.abs(x - same).max()) < 1e-3 * scale
    assert float(jnp.abs(other - same).max()) > 3e-2 * scale


def test_the_full_layers_reads_no_position(float32_products):
    """No rotary: the trunk built with one planted in its full layers (the
    other reading of a null ``rope_theta``) is not the reference, and is the
    reference with the same rotary planted."""
    m = mapping()
    cfg = small(m)
    assert not cfg.rotary and cfg.rope_theta == 0.0
    turned = dataclasses.replace(cfg, rotary=True, rope_theta=ref.OTHER_THETA)
    params = loud(decoder.init_params(cfg, jax.random.key(11), jnp.float32))
    patches, ids = inputs(11)
    with jax.default_matmul_precision("highest"):
        x = embedded(params, patches, ids)
        plain, planted = (decoder.trunk(params, x, np.arange(64), c)[0] for c in (cfg, turned))
        want, want_turned = (ref.hidden(params, patches[0], ids, ref.sizes(m, rotary=r), block=16)
                             for r in (False, True))
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    assert float(jnp.abs(plain - want).max()) < 1e-3 * scale
    assert float(jnp.abs(planted - want_turned).max()) < 1e-3 * scale
    assert float(jnp.abs(planted - want).max()) > 3e-2 * scale


# ---------------------------------------------------------------------------
# the configuration's keys, the block's norms, what is drawn
# ---------------------------------------------------------------------------

def _file():
    with open(CONFIG) as f:
        return json.load(f)


def test_from_mapping_reads_the_published_keys():
    got = decoder.DecoderConfig.from_mapping(_file())
    assert (got.hidden_size, got.num_layers, got.num_heads, got.num_kv_heads, got.head_dim,
            got.vocab_size, got.intermediate_size) == (3840, 16, 30, 30, 128, 100352, 11008)
    assert got.layer_types == (GDN, GDN, GDN, MHA) * 4 and got.num_experts == 0
    assert (got.linear_head_dim, got.linear_value_dim, got.linear_decay, got.linear_beta_scale,
            got.conv_taps, got.linear_chunk) == (96, 192, "head", 2.0, 4, dr.HEAD_CHUNK)
    assert (got.pre_norm, got.sandwich, got.qk_norm, got.qk_norm_span) == (False, True, True, "projection")
    assert not got.rotary and not got.tie_embedding and got.rms_eps == 1e-6 and got.passes == 1
    assert got.stream_dtype is None  # bf16, the weights': the file's last `assumed` entry has both readings
    # a step size in (0, 1) where eigenvalues stay positive
    assert decoder.DecoderConfig.from_mapping(
        {**_file(), "linear_allow_neg_eigval": False}).linear_beta_scale == 1.0


@pytest.mark.parametrize("other, says", [
    ({"linear_num_value_heads": 60}, "one count for all three"),
    ({"linear_num_key_heads": 15, "linear_num_value_heads": 15}, "one count for all three"),
    ({"rope_parameters": {"rope_theta": 500000.0, "rope_type": "default"}}, "beside a rotary"),
    ({"rope_parameters": None, "rope_theta": 10000000.0}, "beside a rotary"),
])
def test_a_file_that_spells_its_linear_layers_so_and_is_not_of_the_family_is_refused(other, says):
    """The ``linear_*`` keys are read as the mark of the reordered, position-free
    block (no key of the file says either): another model that sizes Gated
    DeltaNet layers by the same keys (heads of the three kinds that differ, a
    rotary in its full layers) is refused, never built as this one."""
    m = {k: v for k, v in {**_file(), **other}.items() if v is not None}
    with pytest.raises(ValueError, match=says):
        decoder.DecoderConfig.from_mapping(m)


def test_the_file_holds_the_catalog_s_numbers_unchanged_and_names_its_cuts():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    cfg, reduced = _file(), ["num_hidden_layers"]
    assert cfg["source"] == row["source_url"] and cfg["reduced"] == reduced
    for key, value in row["config"].items():
        if key == "layer_types":
            assert cfg[key] == value[:16] and value[:4] * 8 == value
        elif key not in reduced:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 16 and cfg["published"]["num_hidden_layers"] == 32
    assert "two pipeline stages" in cfg["deployment"] and len(cfg["assumed"]) >= 10
    for said in ("reordered", "rotary", "whole", "A_log", "dt_bias", "stream"):
        assert any(said.lower() in line.lower() for line in cfg["assumed"]), said


def test_only_what_a_block_has_is_drawn():
    """No ``norm1`` and no ``norm2`` under the reordered norm; a gain a column
    of the WHOLE q and k projections; the linear layers' matrices at the
    published shapes (96-wide heads are laid at lane tiles where they are
    READ, not where they are held)."""
    cfg = decoder.DecoderConfig.from_mapping(mapping())
    params = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))
    linear, full = params["layers"][0], params["layers"][3]
    assert sorted(linear) == sorted([
        "w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f", "dt_bias", "a_log", "w_beta", "w_z",
        "o_norm", "wo", "norm1_post", "norm2_post", "w_gate", "w_up", "w_down"])
    assert sorted(full) == sorted(["wq", "wk", "wv", "wo", "q_norm", "k_norm", "norm1_post",
                                   "norm2_post", "w_gate", "w_up", "w_down"])
    assert (linear["w_q"].shape, linear["w_v"].shape, linear["conv_k"].shape, linear["w_f"].shape,
            linear["o_norm"].shape, linear["wo"].shape) == (
                (64, 144), (64, 288), (144, 4), (64, 6), (48,), (288, 64))
    assert full["q_norm"].shape == full["k_norm"].shape == (96,) and "head" in params
    # the gate's draw: A in (1, 16), a step of 0.001 to 0.1 where the pre-activation is 0
    drawn = decoder.init_params(cfg, jax.random.key(1))["layers"][0]
    assert 0.0 <= float(drawn["a_log"].min()) and float(drawn["a_log"].max()) <= float(np.log(16.0))
    step = jax.nn.softplus(drawn["dt_bias"])
    assert 1e-3 * 0.99 <= float(step.min()) and float(step.max()) <= 0.1 * 1.01


def test_a_norm_after_a_branch_is_refused_on_what_it_is_not_built_on():
    cfg = small(mapping())
    params = decoder.init_params(cfg, jax.random.key(0), jnp.float32)
    x = jnp.zeros((64, 64), jnp.float32)
    gated = dataclasses.replace(cfg, attn_gate="head_wise")
    with pytest.raises(ValueError, match="sandwich"):
        decoder.decoder_layer(params["layers"][3], x, None, None, gated, gated.layer_kind(3))
    bare = dataclasses.replace(cfg, sandwich=False)
    with pytest.raises(ValueError, match="no norm"):
        decoder.decoder_layer(params["layers"][3], x, None, None, bare, bare.layer_kind(3))


# ---------------------------------------------------------------------------
# counters, the manifest's entries, the counts
# ---------------------------------------------------------------------------

def test_linear_and_block_counters_reach_the_snapshot_and_the_exposition():
    # heads of 128, alone in their groups and unturned: two a grid step, and seventeen statistics
    cfg = small(mapping(head_dim=128), linear_chunk=8)
    _, snap, text = streamed(cfg)
    steps, s = 2, 2 * 2 * 14 + PROMPT  # 64 tokens a frame, two frames a step
    assert snap["decoder_tokens_total"] == steps * 2 * s
    assert snap["linear_attn_tokens_total"] == steps * 3 * (2 * s)  # three linear layers
    assert snap["linear_attn_chunks_total"] == steps * 3 * (2 * 6 * (s // 8))
    assert snap["linear_attn_tokens_total"] * 6 / snap["linear_attn_chunks_total"] == 8  # the chunk
    assert snap["attn_head_tiles_total"] == 2 * snap["attn_grid_steps_total"] == steps * 2 * 6 * 3
    assert snap["loop_passes_total"] == snap["expert_rows_ahead_total"] == 0  # the groups it has not
    for name in decoder.LINEAR_STATS + decoder.BLOCK_STATS:
        assert f'psana_ray_{name}{{source="reader"}}' in text, name


def test_the_cell_follows_granite_s_and_reports_the_host_path_as_the_decoders_do():
    cell = BENCH.cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "saturated") and BENCH.file(CELL) == _file()
    cfg = _file()
    assert cfg["program"] == "prefill_reordered" and cfg["transport"]["slots"] == 4
    assert (cfg["patch"], cfg["prompt_tokens"], cfg["sequence_tokens"], cfg["batch_size"]) == (16, 256, 8704, 1)
    assert cfg["trace_names"] == {"step": "jit_olmo_hybrid_step", "calib_kernel": "fused_calibrate",
                                  "gdn_kernel": "gated_delta_net",
                                  "attention_kernel": "masked_gqa_attention"}


def test_olmo_hybrid_roofline_counts_at_the_published_sizes():
    """Worked by hand: a linear layer 2 x 11.0592 M + 3 x 22.1184 M + 2 x 0.1152
    M = 88.704 M products' parameters, a full layer 4 x 14.7456 M, the MLP
    126.812 M; 8,704 tokens; 37,884,160 causal pairs at 4 x 128 operations a
    head; the recurrence 7 x 96 x 192 a head and token."""
    cfg = _file()
    rule = roofline.delta_rule(1, 8704, 30, 96, 192)
    assert rule["flops"] == 7 * 96 * 192 * 30 * 8704 == 33_690_746_880
    assert rule["bytes"] == 8704 * 30 * (2 * 2 * 96 + 3 * 2 * 192 + 8) == 403_169_280
    assert rule["bytes"] / 819e9 > rule["flops"] / 197e12  # bytes bound it: 0.49 ms a layer
    attention = roofline.causal_attention(1, 8704, 3840, 30, 30)
    assert attention["flops"] == 4 * 128 * 30 * (8704 * 8705 // 2) == 581_900_697_600
    step = roofline.step(1, 8704, 3840, cfg["layer_types"], 11008, 30, 30, 30, 96, 192, 4, 100352, 256, 16)
    linear = 2 * 8704 * 3840 * (2 * 2880 + 3 * 5760 + 60) + 2 * 4 * 8704 * 11520 + rule["flops"]
    full = 2 * 8704 * 4 * 3840 * 3840 + attention["flops"]
    dense = 2 * 8704 * 3 * 3840 * 11008
    want = 12 * linear + 4 * full + 16 * dense + 2 * 8448 * 256 * 3840 + 2 * 3840 * 100352
    assert step["flops"] == want == 60_716_608_389_120 and step["bytes"] == 0.0


def test_the_rule_gives_the_full_layers_a_block_of_two_heads_at_the_published_sizes():
    """30 heads alone in their groups, 128 wide, unturned and unmasked: the
    largest of 8 / 4 / 2 that divides 30, in 1,088 x 1,088 tiles (18.9 MB of
    unrolled score tiles of 36), and the step counts its blocks."""
    cfg = decoder.DecoderConfig.from_mapping(_file())
    assert sa.causal_tiles(8704, 1, cfg.causal_q_tile, cfg.causal_kv_tile) == (1088, 1088)
    assert sa.heads_a_step(30, 1, 1088, 1088, 128, 128) == 2
    tiles, steps, parts = decoder.causal_call_steps(cfg, 3, 1, 8704)
    assert (tiles, steps, parts) == (30 * 36, 15 * 36, 15 * 36) and decoder.causal_call_steps(cfg, 0, 1, 8704) == (0, 0, 0)


# ---------------------------------------------------------------------------
# the adapter, and the cell's rehearsal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lacks", ["linear_decay", "linear_value_dim", "linear_beta_scale",
                                   "pre_norm", "qk_norm_span"])
def test_the_adapter_ends_the_run_where_the_package_lacks_the_mechanism(monkeypatch, lacks):
    from benchmark.programs import prefill_reordered

    assert lacks in prefill_reordered.MECHANISM
    older = dataclasses.make_dataclass(
        "Older", [(f.name, f.type, dataclasses.field(default=None))
                  for f in dataclasses.fields(decoder.DecoderConfig) if f.name != lacks], frozen=True)
    monkeypatch.setattr(decoder, "DecoderConfig", older)
    with pytest.raises(SystemExit) as e:
        prefill_reordered.Program({"name": "olmo_hybrid_7b_prefill_epix10k2m"}, 1, "", None)
    assert e.value.code not in (0, None) and lacks in str(e.value.code)


def test_the_cell_s_rehearsal_runs_the_served_path_and_is_correct():
    line, done = rehearse(CELL, seed=1, seconds=2, xla_flags=False, timeout=600)
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0 and line["metrics"] == {}
    assert line["cell"] == CELL and line["attempted"] > 0
    for name in ("device_put_ms", "device_wait_ms.hit", "device_idle_share.hit", "h2d_ms.hit"):
        assert name in line["would_report"], line["would_report"]
    assert "compiles inside the window 0" in done.stderr
