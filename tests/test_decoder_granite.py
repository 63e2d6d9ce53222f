"""Mamba-2's selective state-space scan (``ops/ssd.py``), position-free
grouped-query attention, the four multipliers and the trunk that mixes them
(``models/decoder.py`` reading Granite-4.0-H's keys) against the benchmark's
plain reference (``benchmark/reference/granite_decoder.py``: the recurrence
token by token) at small sizes on the CPU; the new cell's configuration
file, counters and counts."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_kit
from benchmark.reference import granite_decoder as ref
from decoder_kit import F32_PRODUCTS, Kit, checked, embedded, inputs, rehearse
from psana_ray_tpu.models import decoder
from psana_ray_tpu.ops import ssd
from test_manifest_entries import BENCH, need

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmark", "configs")
CONFIG = os.path.join(CONFIGS, "granite4_h_micro_prefill_epix10k2m.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "granite_epix_saturated"
M, A = "mamba", "attention"
# the controls' faults (benchmark/tests/granite_controls.py), at this size's chunk
FAULTS = {"bf16_state": {"state": "bfloat16"}, "state_not_carried": {"carry": 16},
          "no_skip": {"skip": False}, "no_dt_bias": {"dt_bias": False},
          "norm_before_gate": {"gate_first": False}, "no_conv_bias": {"conv_bias": False},
          "residual_one": {"residual": 1.0}, "scale_one_over_root": {"attn_scale": 16 ** -0.5},
          "rotary": {"rotary": True}, "no_logits_scaling": {"logits_scaling": 1.0},
          "no_embedding_multiplier": {"embedding": 1.0}}
# the decoder cells the benchmark had before this one: configuration file -> its cell's suffix
OTHERS = {"keye_vl2_prefill_epix10k2m": "keye", "lfm2_8b_a1b_prefill_epix10k2m": "lfm2",
          "kimi_k2_prefill_epix10k2m": "kimi", "deepseek_v32_prefill_epix10k2m": "dsv32",
          "ling3_flash_prefill_epix10k2m": "ling3", "laguna_s21_prefill_epix10k2m": "laguna"}


def mapping(**over):
    """Granite-4.0-H's Hugging Face keys at a small size: a whole period's
    kinds (``m m a m``), 8 scan heads of 16 over a state of 16."""
    m = dict(
        model_type="granitemoehybrid", hidden_size=64, num_hidden_layers=4,
        layer_types=[M, M, A, M], num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
        rms_norm_eps=1e-5, rope_theta=10000, rope_scaling=None, position_embedding_type="nope",
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4, mamba_conv_bias=True,
        mamba_n_groups=1, mamba_proj_bias=False, attention_bias=False, mamba_expand=2,
        residual_multiplier=0.22, embedding_multiplier=12, attention_multiplier=0.0625,
        logits_scaling=8, num_local_experts=0, num_experts_per_tok=0, intermediate_size=96,
        shared_intermediate_size=96, tie_word_embeddings=True, patch=8,
    )
    m.update(over)
    return m


# loud: the taps, the bias and the scan's own parameters are of order 1 as drawn
loud = functools.partial(decoder_kit.loud, keep=("conv_w",))
# the scan's chunks at most 16 rows: the trunk's 64 tokens cross three chunk edges (`scan_rows` follows `ROWS`)
PATCHES_OF = {"float32_products": lambda: decoder_kit.float32_products(ssd, ssd.ssd_scan),
              "chunks_of_16": lambda: decoder_kit.chunks_of_16(ssd, ssd.ssd_scan)}
KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=32, causal_kv_tile=32), loud=loud, patches=PATCHES_OF)
small, trunk_of, reference_of = KIT.small, KIT.trunk_of, KIT.reference_of


@pytest.fixture
def float32_products():
    with PATCHES_OF["float32_products"]():
        yield


# ---------------------------------------------------------------------------
# the kernel against the recurrence, token by token
# ---------------------------------------------------------------------------

def _kernel_case(case, seed=0, heads=8, p=16, state=16, seq=48, batch=2):
    rng = np.random.default_rng(seed)
    t, wide = batch * seq, heads * p
    xbc = rng.standard_normal((t, wide + 2 * state))
    dt = rng.standard_normal((t, heads))
    first = np.exp(rng.uniform(np.log(0.001), np.log(0.1), heads))
    dt_bias = first + np.log(-np.expm1(-first))  # Mamba-2's initialiser: softplus^-1 of the step
    a_log = np.log(rng.uniform(1.0, 16.0, heads))
    if case == "fastest_decay":  # -1.6 a token in every head over the whole chunk: e^-410 at 256 rows
        dt, dt_bias, a_log = np.zeros((t, heads)), np.full(heads, np.log(np.expm1(0.1))), np.full(
            heads, np.log(16.0))
    elif case == "slowest_decay":  # -0.001 a token: a state that forgets nothing inside a sequence
        dt, dt_bias, a_log = np.zeros((t, heads)), np.full(heads, np.log(np.expm1(0.001))), np.zeros(heads)
    elif case == "identical_rows":  # a detector's blank patches: one row again and again
        xbc, dt = np.tile(xbc[:1], (t, 1)), np.tile(dt[:1], (t, 1))
    arrays = [jnp.asarray(a, jnp.float32) for a in (
        xbc, rng.standard_normal((t, wide)), dt, dt_bias, a_log, rng.uniform(0.5, 1.5, heads),
        rng.uniform(0.5, 1.5, wide))]
    return arrays, dict(seq_len=seq, heads=heads, state=state, eps=1e-5)


def _recurrence(arrays, seq_len, heads, state, eps, **fault):
    """The reference's own lines on the kernel's operands, sequence by sequence."""
    xbc, z, dt, dt_bias, a_log, skip, gain = arrays
    t, wide = z.shape
    m = {"carry": 0, "state": "float32", **fault}
    x = xbc[:, :wide].reshape(t, heads, wide // heads)
    step = jax.nn.softplus(dt + dt_bias)
    y = jnp.concatenate([
        ref.scan(x[lo:lo + seq_len], xbc[lo:lo + seq_len, wide:wide + state],
                 xbc[lo:lo + seq_len, wide + state:], step[lo:lo + seq_len], -jnp.exp(a_log), m,
                 jnp.float32) for lo in range(0, t, seq_len)])
    y = (y + skip[None, :, None] * x).reshape(t, wide)
    return ref.rms(y * jax.nn.silu(z), gain, eps), step * -jnp.exp(a_log)


# sequences of 48 rows (no power of two) in chunks of 8, 16 and 48 rows (one chunk), and of 256 rows
# in chunks of 64, 128 and 256
CHUNKED = [(case, rows, 48) for rows in (8, 16, 48)
           for case in ("spread_decay", "fastest_decay", "slowest_decay", "identical_rows")]
CHUNKED += [(case, rows, 256) for rows in (64, 128, 256) for case in ("spread_decay", "fastest_decay")]


@pytest.mark.parametrize("case,rows,seq", CHUNKED, ids=[f"{c}-{n}-of-{s}" for c, n, s in CHUNKED])
def test_the_chunked_scan_is_the_recurrence_and_not_an_approximation(case, rows, seq,
                                                                     float32_products):
    """Two sequences in one array (a boundary inside it): with float32 products
    the kernel IS the token-by-token recurrence to float32's own rounding,
    whatever the chunk (so 64, 128 and 256 rows give one answer), the decay and
    the rows; at -1.6 a token over a whole chunk nothing overflows and nothing
    is NaN: every exponent is a difference of two running sums."""
    arrays, sizes = _kernel_case(case, seq=seq)
    with jax.default_matmul_precision("highest"):
        got = ssd.ssd_scan(*arrays, rows=rows, **sizes)
        want, log_decay = _recurrence(arrays, **sizes)
    if case == "fastest_decay":
        assert float(log_decay.max()) < -1.59 and rows * float(log_decay.min()) > -420
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=0)
    # and a state that crossed the sequences' boundary, or stopped at a chunk's, is another result
    if case in ("spread_decay", "slowest_decay"):
        leaked, _ = _recurrence(arrays, **{**sizes, "seq_len": 2 * seq})
        assert float(jnp.abs(leaked[seq:] - want[seq:]).max()) > 1e-2
        if rows < seq:
            dropped, _ = _recurrence(arrays, carry=rows, **sizes)
            assert float(jnp.abs(dropped - want).max()) > 1e-2


@pytest.mark.parametrize("case", ["spread_decay", "fastest_decay", "identical_rows"])
def test_bf16_products_keep_the_scan_within_their_rounding_of_the_recurrence(case):
    arrays, sizes = _kernel_case(case, seed=1)
    held = [a.astype(jnp.bfloat16) for a in arrays[:2]]  # what bf16 [x | B | C] and z hold
    got = ssd.ssd_scan(*held, *arrays[2:], rows=16, **sizes)
    with jax.default_matmul_precision("highest"):
        want, _ = _recurrence([a.astype(jnp.float32) for a in held] + arrays[2:], **sizes)
    assert got.dtype == jnp.bfloat16 and np.isfinite(np.asarray(got, np.float32)).all()
    err = np.sqrt(np.mean((np.asarray(got, np.float64) - np.asarray(want, np.float64)) ** 2)
                  / np.mean(np.asarray(want, np.float64) ** 2))
    assert err < 2e-2, err


SCAN_ROWS = {8704: 512, 34304: 512, 1024: 512, 640: 128, 768: 384, 1088: 272, 256: 256, 64: 64, 24: 24}


@pytest.mark.parametrize("seq", sorted(SCAN_ROWS))
def test_the_scan_s_rows_follow_from_the_sequence_alone(seq):
    """Whole lane tiles of at most 512 rows where the sequence has such a
    divisor (what the chip takes), else whole 8-row tiles (interpreted)."""
    rows = ssd.scan_rows(seq)
    assert rows == SCAN_ROWS[seq] and seq % rows == 0 and rows <= ssd.ROWS == 512
    assert rows % 128 == 0 or not [c for c in range(128, 513, 128) if seq % c == 0]


def test_whole_sequences_and_whole_tiles_are_asked_for():
    arrays, sizes = _kernel_case("spread_decay")
    with pytest.raises(ValueError, match="sequences of 40 rows"):
        ssd.ssd_scan(*arrays, **{**sizes, "seq_len": 40})
    with pytest.raises(ValueError, match="no chunk of whole 8-row tiles"):
        ssd.scan_rows(12)
    with pytest.raises(ValueError, match="whole lane tiles"):  # rows that do not divide
        ssd.ssd_scan(*arrays, rows=40, **sizes)
    with pytest.raises(ValueError, match="whole lane tiles"):  # the chip's rule, asked for here
        ssd.ssd_scan(*arrays, rows=16, interpret=False, **sizes)


def test_conv_silu_with_a_bias_is_the_reference_s_and_starts_anew_with_every_sequence():
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.standard_normal((32, 24)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((24, 4)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(24), jnp.float32)
    m = {"taps": 4, "conv_bias": True}
    got = decoder.conv_silu(u, w, 16, b)
    want = jnp.concatenate([ref.conv_silu(u[:16], w, b, m), ref.conv_silu(u[16:], w, b, m)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    without = decoder.conv_silu(u, w, 16)  # ling3's call: no bias, the program it was
    np.testing.assert_allclose(np.asarray(without), np.asarray(jnp.concatenate(
        [ref.conv_silu(v, w, b, {**m, "conv_bias": False}) for v in (u[:16], u[16:])])), atol=1e-6)
    assert float(jnp.abs(got - without).max()) > 0.1


# ---------------------------------------------------------------------------
# the trunk against the reference, float32, all positions, a batch of two
# ---------------------------------------------------------------------------

def test_the_state_space_trunk_matches_the_reference_at_all_positions_of_a_batch_of_two():
    cfg = small(mapping())
    x, got, stats = KIT.trunk(3, batch=2, under=(*F32_PRODUCTS, "chunks_of_16"))
    want_x, want = KIT.reference(3, batch=2)
    assert x.dtype == jnp.float32  # the stream of a model with a residual multiplier
    for a, b in ((x, want_x), (got, want)):
        scale = float(jnp.sqrt(jnp.mean(b ** 2)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4 * scale, rtol=0)
    # twelve statistics, the ones a step with linear layers returns: no new length, no new name
    names = decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS + decoder.LINEAR_STATS
    assert len(stats) == len(names) == 12 and cfg.has_linear and cfg.layer_stats == 10
    got_stats = dict(zip(names, (float(v) for v in stats)))
    assert got_stats["linear_attn_tokens_total"] == 3 * 2 * 64  # three layers that carry a state
    assert got_stats["linear_attn_chunks_total"] == 3 * 2 * 8 * (64 // 16)
    assert got_stats["attn_tiles_causal_total"] == got_stats["attn_tiles_live_total"] == 2  # one attention layer
    assert got_stats["decoder_tokens_total"] == 128 and got_stats["decoder_sequences_total"] == 2
    assert got_stats["expert_tokens_max_total"] == got_stats["expert_rows_routed_total"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_with_a_control_s_fault_in_it_is_another_trunk(fault):
    """Each of the controls' faults moves the reference's own output (hidden
    rows, or for the head's divisor the logits) by far more than the program
    lies from it: the gate-before-norm order, the skip, the step's bias, the
    convolution's bias, a state cut or narrowed, each multiplier, a rotary."""
    x, logits, _ = KIT.trunk(5, under=F32_PRODUCTS)  # made once for the eleven cases, as the clean reference
    want, other = KIT.reference(5), KIT.reference(5, **FAULTS[fault])
    which = 1 if fault == "no_logits_scaling" else 0
    got = (x, logits)[which]
    scale = float(jnp.sqrt(jnp.mean(want[which] ** 2)))
    near = float(jnp.sqrt(jnp.mean((got - want[which]) ** 2))) / scale
    far = float(jnp.sqrt(jnp.mean((other[which] - want[which]) ** 2))) / scale
    # (the embedded rows times 12 are most of the stream and a branch comes in at 0.22: a fault in
    # one operator moves the whole by a thousandth, a narrower state by a hundred-thousandth)
    assert near < 1e-6 and far > max(100 * near, 1e-5), (near, far)


MULTIPLIERS = {"residual_multiplier": ("residual", 0.22, 1.0),
               "embedding_multiplier": ("embedding", 12.0, 1.0),
               "attention_multiplier": ("attn_scale", 0.0625, 0.25),
               "logits_scaling": ("logits_scaling", 8.0, 1.0)}


@pytest.mark.parametrize("field", sorted(MULTIPLIERS))
def test_each_multiplier_moves_the_output_by_what_the_reference_says(field, float32_products):
    """The program under ANOTHER value of one multiplier is the reference under
    that value, and not the reference under the published one: 0.22, 12, 1/64
    (1/16 at this size's heads of 16) and 1/8 each act where the mathematics
    puts them."""
    key, published, another = MULTIPLIERS[field]
    m = mapping()
    assert getattr(small(m), field) == published
    cfg = dataclasses.replace(small(m), **{field: another})
    params = loud(decoder.init_params(cfg, jax.random.key(7), jnp.float32))
    patches, ids = inputs(7)
    with jax.default_matmul_precision("highest"):
        _, got, _ = trunk_of(params, patches, ids, cfg)
        _, same = reference_of(params, patches, ids, ref.sizes(m, **{key: another}))
        _, stated = reference_of(params, patches, ids, ref.sizes(m))
    scale = float(jnp.sqrt(jnp.mean(same ** 2)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(same), atol=2e-5 * scale, rtol=0)
    assert float(jnp.sqrt(jnp.mean((got - stated) ** 2))) > 1e-3 * scale


def test_without_a_rotary_nothing_reads_a_token_s_position(float32_products):
    """``position_embedding_type: nope``: the trunk's output is the same, bit
    for bit, wherever the sequence is said to sit; and no table of angles is built."""
    cfg = small(mapping())
    assert not cfg.rotary and not cfg.qk_norm
    params = KIT.params(9)
    assert "q_norm" not in params["layers"][2] and "wq" in params["layers"][2]
    patches, ids = inputs(9)
    x, _, _ = trunk_of(params, patches, ids, cfg)
    moved, _, _ = trunk_of(params, patches, ids, cfg, pos=np.arange(64) + 1000)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(moved))
    turned = dataclasses.replace(cfg, rotary=True)  # the field, and nothing else, turns q and k
    other, _, _ = trunk_of(params, patches, ids, turned)
    assert float(jnp.abs(other - x).max()) > 1e-3


@pytest.mark.parametrize("scale,factor", [(0.0625, 2.0), (0.25, 1.0), (None, 1.0)])
def test_under_a_stated_scale_q_and_k_are_drawn_so_that_the_scores_spread_as_under_the_usual_one(
        scale, factor):
    """``attention_multiplier`` 1/16 at this size's heads of 16 (1/64 at heads
    of 64): W_q and W_k at ``0.02 * sqrt(head_dim ** -0.5 / scale)``, the
    other matrices and a model without the key as they were."""
    m = mapping() if scale is None else mapping(attention_multiplier=scale)
    if scale is None:
        del m["attention_multiplier"]
    cfg = decoder.DecoderConfig.from_mapping(m)
    p = decoder.init_params(cfg, jax.random.key(13), jnp.float32)["layers"][2]
    spread = {k: float(jnp.std(p[k])) / 0.02 for k in ("wq", "wk", "wv", "wo")}
    assert abs(spread["wq"] - factor) < 0.05 * factor and abs(spread["wk"] - factor) < 0.05 * factor
    assert abs(spread["wv"] - 1) < 0.05 and abs(spread["wo"] - 1) < 0.05
    assert abs(cfg.softmax_scale * factor ** 2 - cfg.head_dim ** -0.5) < 1e-12


def test_a_sequence_of_the_batch_does_not_read_its_neighbour_s_state_or_taps():
    """Sequence 1 of a batch of two, alone and after another neighbour: the
    same rows (the state AND the convolution stop at a sequence's edge)."""
    cfg = small(mapping())
    params = KIT.params(11)
    patches, ids = inputs(11, batch=2)
    both, _, _ = trunk_of(params, patches, ids, cfg)
    alone, _, _ = trunk_of(params, patches[1:], ids, cfg)
    swapped, _, _ = trunk_of(params, patches[::-1], ids, cfg)
    np.testing.assert_allclose(np.asarray(both[64:]), np.asarray(alone), atol=1e-5)
    np.testing.assert_allclose(np.asarray(both[64:]), np.asarray(swapped[:64]), atol=1e-5)
    # the same layers told that the batch is ONE sequence of 128 hand the state and three rows on
    as_one, _ = jax.jit(lambda p: decoder.trunk(
        p, embedded(p, patches, ids, cfg), np.arange(128), cfg, 1))(params)
    assert float(jnp.abs(as_one[64:] - both[64:]).max()) > 1e-2


# ---------------------------------------------------------------------------
# the configuration file, the manifest's new entries, the adapter
# ---------------------------------------------------------------------------

def _file(name="granite4_h_micro_prefill_epix10k2m"):
    with open(os.path.join(CONFIGS, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        return next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")


def test_from_mapping_reads_the_published_keys():
    got = decoder.DecoderConfig.from_mapping(_file())
    assert (got.hidden_size, got.num_layers, got.num_heads, got.num_kv_heads, got.head_dim) == (
        2048, 40, 32, 8, 64)
    assert (got.ssm_heads, got.ssm_head_dim, got.ssm_state, got.conv_taps, got.conv_bias) == (
        64, 64, 128, 4, True)
    assert (got.residual_multiplier, got.embedding_multiplier, got.attention_multiplier,
            got.logits_scaling) == (0.22, 12.0, 0.015625, 8.0)
    assert got.softmax_scale == 1 / 64 and not got.rotary and not got.qk_norm and got.tie_embedding
    assert got.layer_types.count(decoder.MAMBA) == 36
    assert [i for i, op in enumerate(got.layer_types) if op == decoder.ATTENTION] == [5, 15, 25, 35]
    assert (got.num_experts, got.intermediate_size, got.vocab_size) == (0, 8192, 100352)
    assert got.has_linear and got.layer_stats == 10 and not got.holds_a_share
    assert {got.layer_kind(i) for i in range(40)} == {(decoder.MAMBA, False), (decoder.ATTENTION, False)}
    published = _file()
    with pytest.raises(ValueError, match="layer_types"):
        decoder.DecoderConfig.from_mapping({**published, "layer_types": published["layer_types"][:9]})
    # more than one group of B and C builds since PR 64 (tests/test_decoder_nemotron3.py)
    assert decoder.DecoderConfig.from_mapping({**published, "mamba_n_groups": 8}).ssm_groups == 8
    with pytest.raises(ValueError, match="groups of B and C"):
        decoder.DecoderConfig.from_mapping({**published, "mamba_n_groups": 7})
    with pytest.raises(ValueError, match="not built"):
        decoder.DecoderConfig.from_mapping({**published, "num_local_experts": 32})
    with pytest.raises(ValueError, match="layer_types"):  # another model's file cannot say `mamba`
        decoder.DecoderConfig.from_mapping({**_file("lfm2_8b_a1b_prefill_epix10k2m"),
                                            "layer_types": ["mamba"] * 12})


BY_KEY = {"residual_multiplier": (0.5, "residual_multiplier", 0.5),
          "embedding_multiplier": (3, "embedding_multiplier", 3.0),
          "logits_scaling": (4, "logits_scaling", 4.0),
          "attention_multiplier": (0.03125, "softmax_scale", 0.03125),
          "position_embedding_type": ("nope", "rotary", False)}


@pytest.mark.parametrize("key", sorted(BY_KEY))
def test_each_key_is_read_for_itself_whatever_the_file_s_model_type(key):
    """No spelling is known by its ``model_type``: another model's file that
    states one of Granite's keys (plain ``granite`` has the four multipliers
    too) is built WITH it, and this one's under another name is the same model."""
    value, field, want = BY_KEY[key]
    other = _file("lfm2_8b_a1b_prefill_epix10k2m")
    assert key not in other
    plain, got = (decoder.DecoderConfig.from_mapping(m) for m in (other, {**other, key: value}))
    assert getattr(got, field) == want != getattr(plain, field)
    assert got == dataclasses.replace(plain, **{
        "rotary" if field == "rotary" else key: want,
        **({"qk_norm": False} if key == "attention_multiplier" else {})})
    assert (got.stream_dtype == jnp.float32) == (key == "residual_multiplier")
    assert plain.stream_dtype is None and plain.qk_norm
    renamed = decoder.DecoderConfig.from_mapping(mapping(model_type="granite"))
    assert renamed == decoder.DecoderConfig.from_mapping(mapping())
    assert renamed.layer_types == (M, M, decoder.ATTENTION, M) and renamed.ssm_heads == 8


def test_the_file_holds_the_catalog_s_numbers_unchanged_and_cuts_nothing():
    row, cfg = _catalog_row(), _file()
    assert cfg["source"] == row["source_url"] and len(cfg["source"]) <= 200
    assert {k for k, v in row["config"].items() if cfg.get(k, "absent") != v} == set() == set(
        cfg["reduced"])
    assert "the whole model on one chip: 1 stage, 1 chip a layer, the whole vocabulary" in cfg[
        "deployment"]
    said = " ".join(cfg["assumed"])
    for reading in ("embedding_multiplier 12 like the prompt's rows", "time_step_limit (0, inf)",
                    "A = -U(1, 16)", "[0.001, 0.1]", "[0, 100,352)", "the gate BEFORE the norm",
                    "no rotary", "linear patch embedding"):
        assert reading in said, reading
    assert cfg["step_tokens"] == cfg["batch_size"] * cfg["sequence_tokens"] == 8704
    assert cfg["transport"] == {"scheme": "shm", "slots": 4} and cfg["vocab_size"] % 128 == 0
    assert cfg["reference"] == {"module": "granite_decoder", "query_block": 512, "sequences": [0]}
    # weights, recounted: 3.192 G parameters, 6.38 GB in bf16; a layer of each kind
    got = decoder.DecoderConfig.from_mapping(cfg)
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert round(count / 1e9, 3) == 3.192 and round(2 * count / 1e9, 2) == 6.38
    by_layer = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(p)) for p in shapes["layers"]]
    assert round(by_layer[0] / 1e6, 2) == 76.18 and round(by_layer[5] / 1e6, 2) == 60.82
    assert len(shapes["layers"][0]) < 16 and "head" not in shapes  # the tied table is the head
    rehearsal = decoder.DecoderConfig.from_mapping({**cfg, **cfg["rehearse"]})
    assert rehearsal.layer_types == (M, M, "full_attention", M) and cfg["rehearse"]["batch_size"] == 2


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_six_readers_have_nothing_of_what_this_one_brought(name):
    got = decoder.DecoderConfig.from_mapping(_file(name))
    assert (got.ssm_heads, got.ssm_head_dim, got.ssm_state, got.conv_bias) == (0, 0, 0, False)
    assert (got.residual_multiplier, got.embedding_multiplier, got.attention_multiplier,
            got.logits_scaling, got.rotary) == (1.0, 1.0, None, 1.0, True)
    assert got.softmax_scale == got.head_dim ** -0.5 and decoder.MAMBA not in got.layer_types
    assert got.has_linear == (OTHERS[name] == "ling3")
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    assert not [k for layer in shapes["layers"] for k in layer
                if k in ("conv_b", "dt_bias", "a_log", "d_skip", "ssm_norm", "w_out")
                and OTHERS[name] != "lfm2"]  # (lfm2's convolution has a w_out of its own)


def test_the_granite_cell_follows_laguna_s_and_reports_the_host_path_as_the_decoders_do():
    cell = BENCH.cell(CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "saturated", "granite4_h_micro_prefill_epix10k2m")
    config = BENCH.config(cell["config"])
    assert config["file"] == os.path.relpath(CONFIG, REPO) and len(cell["why"]) <= 200
    assert config["reduced"] == _file()["reduced"] == [] and len(config["why"]) <= 200
    cfg = _file()
    assert cfg["trace_names"]["ssd_kernel"] == "ssd_scan"  # the pallas_call's own name
    assert cfg["trace_names"]["attention_kernel"] == "masked_gqa_attention"
    assert cfg["trace_names"]["step"] == "jit_granite_step"


def test_granite_roofline_counts_at_the_published_sizes():
    from benchmark.roofline import granite, lfm2

    scan = granite.ssd_scan(1, 8704, 64, 64, 128)
    assert scan["flops"] == 5 * 64 * 128 * 64 * 8704 and round(scan["flops"] / 1e9, 1) == 22.8
    assert round(scan["bytes"] / 1e6, 1) == 220.6
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12  # bound by bytes: 0.27 ms against 0.12
    fn, [shapes] = need(CELL, "granite.ssd_scan")  # as the cell's file asks for it
    assert fn(**shapes) == scan
    cfg = _file()
    step = granite.step(1, 8704, 2048, cfg["layer_types"], 8192, 32, 8, 64, 64, 128, 4, 100352, 256,
                        16)["flops"]
    assert round(step / 1e12, 1) == 54.0
    # per token: a state-space layer 154.96 M, an attention layer 121.63 M without its pairs
    mamba = 2 * 2048 * (4096 + 4352 + 64) + 2 * 4096 * 2048 + 2 * 4 * 4352 + 5 * 64 * 128 * 64
    assert round((mamba + 6 * 2048 * 8192) / 1e6, 2) == 154.96
    assert round((2 * 2048 * 5120 + 6 * 2048 * 8192) / 1e6, 2) == 121.63
    pairs = lfm2.causal_attention(1, 8704, 2048, 32, 8)["flops"]
    assert round(pairs / 1e12, 3) == 0.310
    by_hand = 8704 * (36 * (mamba + 6 * 2048 * 8192) + 4 * (2 * 2048 * 5120 + 6 * 2048 * 8192)) \
        + 4 * pairs + 2 * 8448 * 256 * 2048 + 2 * 2048 * 100352
    assert step == by_hand


@pytest.mark.parametrize("lacks", ["ssm_state", "residual_multiplier", "embedding_multiplier",
                                   "attention_multiplier", "logits_scaling", "rotary", "conv_bias"])
def test_the_adapter_ends_the_run_where_the_package_lacks_the_mechanism(monkeypatch, lacks):
    from benchmark.programs import prefill_ssm

    fields = [f for f in dataclasses.fields(decoder.DecoderConfig) if f.name != lacks]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: fields)
    cfg = _file()
    cfg.update(cfg["rehearse"])
    with pytest.raises(SystemExit, match=lacks):
        prefill_ssm.Program(cfg, 1, "", None)


def test_the_cell_s_own_share_of_rows_lies_under_the_batched_adapter_s():
    from benchmark.programs import prefill_batched, prefill_ssm

    assert 0 < prefill_ssm.TOSSED_ROWS_SHARE < prefill_batched.TOSSED_ROWS_SHARE
    assert prefill_ssm.STEP_NAME == "granite_step"


@pytest.mark.parametrize("part", ["first_rows.0", "patch_rows.0", "prompt_rows.0"])
def test_every_part_of_rows_decides_in_this_adapter_a_sequence_s_first_rows_too(part, monkeypatch):
    """A share of rows over the limit that ``prefill_batched`` lets pass (under
    its 0.7) and this cell does not (over its 0.1), in one part at a time:
    ``first_rows``, record-only there, decides here like the spread rows."""
    from benchmark.programs import prefill_batched, prefill_ssm

    parts = {name: {"rows_over_limit": 0.5 if name == part else 0.0, "ok": True}
             for name in ("first_rows.0", "patch_rows.0", "prompt_rows.0")}
    verdict = {**parts, "isolated.0": {"ok": True}, "head": {"ok": True}, "ok": True}
    monkeypatch.setattr(prefill_batched.Program, "check", lambda self, frames: verdict)
    got = prefill_ssm.Program.check(object.__new__(prefill_ssm.Program), None)
    assert not got["ok"] and not got[part]["ok"]
    assert all(got[name]["ok"] for name in parts if name != part)


def test_the_cell_s_rehearsal_runs_the_served_path_is_correct_and_reports_its_counters():
    line, done = rehearse(CELL, seed=2)
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0 and line["cell"] == CELL
    for name in ("ring_depth.hit", "device_wait_ms.hit", "startup_trace_s", "startup_lower_s",
                 "startup_cache_load_s", "startup_compile_s", "startup_cache_misses",
                 "startup_rest_s"):
        assert name in line["would_report"], name
    # the check ran both sequences of the rehearsal's batch, and a sequence moved is itself
    verdict = checked(done)
    assert verdict["isolated.0"]["ok"] and verdict["isolated.1"]["ok"]
    assert verdict["patch_rows.1"]["rows_over_share_limit"] == 0.1
