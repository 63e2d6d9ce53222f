"""Mamba-1's selective scan (``ops/selective_scan.py``), differential
attention, the layers that read what an earlier layer made and the trunk whose
later layers run on the served rows alone (``models/decoder.py`` reading
Phi-4-mini-flash-reasoning's keys) against the benchmark's plain reference
(``benchmark/reference/phi4flash_decoder.py``: the recurrence token by token,
every layer on every row) at small sizes on the CPU; the new cell's
configuration file, adapter and counts."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_kit
from benchmark.reference import phi4flash_decoder as ref
from decoder_kit import HIGHEST, PROMPT, Kit, apart, checked, rehearse
from psana_ray_tpu.models import decoder
from psana_ray_tpu.ops import selective_scan as ss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATCHES = 40  # with the prompt's 8, 48 tokens a sequence: no power of two
S = PATCHES + PROMPT
CONFIG = os.path.join(REPO, "benchmark", "configs", "phi4_mini_flash_prefill_epix10k2m.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "phi4flash_epix_saturated"
M1, W, A, G, C = decoder.MAMBA1, decoder.SLIDING, decoder.ATTENTION, decoder.GMU, decoder.CROSS
# the controls' faults in the reference's place (benchmark/tests/phi4flash_controls.py) and the OTHER
# reading of every assumed point, at this size
FAULTS = {"state_not_carried": {"carry": 16}, "first_channel_s_decays": {"a": "first"},
          "initialiser_s_decays": {"a": "ramp"}, "no_softplus": {"softplus": False},
          "no_skip": {"skip": False}, "last_tap_alone": {"taps_used": (3,)},
          "one_softmax": {"lam": "zero"}, "lambda_init_alone": {"lam": "init"},
          "no_sub_norm": {"sub_norm": False}, "second_values_twice": {"values": "second"},
          "no_window": {"window": 0}, "window_doubled": {"window": 16},
          "plain_softmax": {"plain": True}, "rotary": {"rotary": True},
          "memory_after_gate": {"memory": "gated"}, "memory_from_an_earlier_scan": {"memory_from": 2},
          "keys_from_a_windowed_layer": {"kv_from": 3},
          "lambda_from_one": {"lam_base": 1}, "no_attention_bias": {"attn_bias": False},
          "no_conv_bias": {"conv_bias": False}, "no_dt_bias": {"dt_bias": False},
          "window_before_own": {"window_own": False}}


def mapping(**over):
    """The published file's keys at a small size: eight layers, so that every
    kind is present (``m w m w m a g c``), a window shorter than the sequence."""
    m = dict(model_type="phi4flash", hidden_size=64, intermediate_size=96, layer_norm_eps=1e-5,
             mb_per_layer=2, num_attention_heads=4, num_hidden_layers=8, num_key_value_heads=2,
             sliding_window=8, tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
             vocab_size=256, max_position_embeddings=262144, patch=8)
    m.update(over)
    return m


def loud(params, by=5.0):
    """The same tree with its 0.02-matrices scaled up and A made RANDOM (the
    initialiser's is the same ramp in every channel: a kernel that leaned on
    that would pass), so that every part of a layer moves its output."""
    def random_a(path, a):
        if getattr(path[-1], "key", "") != "a_log":
            return a
        return a + 0.5 * jax.random.normal(jax.random.key(a.shape[0]), a.shape)

    return jax.tree_util.tree_map_with_path(
        random_a, decoder_kit.loud(params, by, keep=("conv_w", "w_dt", "a_log")))


# the scan's chunks at most 16 rows: the trunk's 48 tokens cross two chunk edges
PATCHES_OF = {"chunks_of_16": lambda: decoder_kit.chunks_of_16(ss, ss.selective_scan)}
# tiles that cut 48 tokens into several: attention in 16 x 16. `trunk_of(..., rows=)`: the cut trunk
KIT = Kit(mapping, ref, tiles=dict(causal_q_tile=16, causal_kv_tile=16), loud=loud, patches=PATCHES_OF,
          frame=PATCHES)
small, inputs, trunk_of, reference_of = KIT.small, KIT.inputs, KIT.trunk_of, KIT.reference_of
CHUNKS_OF_16 = (HIGHEST, "chunks_of_16")


# ---------------------------------------------------------------------------
# the kernel against the recurrence, token by token
# ---------------------------------------------------------------------------

def _kernel_case(case, seq, batch=2, channels=256, state=16, seed=0):
    rng = np.random.default_rng(seed)
    t = batch * seq
    u, z = rng.standard_normal((t, channels)), rng.standard_normal((t, channels))
    delta = rng.standard_normal((t, channels))
    b, c = rng.standard_normal((t, state)), rng.standard_normal((t, state))
    first = np.exp(rng.uniform(np.log(0.001), np.log(0.1), channels))
    bias = first + np.log(-np.expm1(-first))  # the initialiser: softplus^-1 of the step
    a = -np.exp(rng.uniform(-1.0, 3.0, (channels, state)))  # a decay a channel AND state, no ramp
    if case == "fastest_decay":  # -1.6 a token everywhere
        delta, bias, a = np.zeros((t, channels)), np.full(channels, np.log(np.expm1(0.1))), np.full(
            (channels, state), -16.0)
    elif case == "identical_rows":  # a detector's blank patches: one row again and again
        u, delta, b, c = (np.tile(x[:1], (t, 1)) for x in (u, delta, b, c))
    bc = np.concatenate([b, c, np.zeros((t, ss.LANES - 2 * state))], axis=1)
    arrays = [jnp.asarray(x, jnp.float32) for x in (u, delta, bc, z, a, rng.uniform(0.5, 1.5, channels),
                                                    bias)]
    return arrays, b, c


def _recurrence(arrays, b, c, seq_len, **fault):
    """The reference's own lines on the kernel's operands, sequence by sequence."""
    u, delta, _, z, a, skip, bias = arrays
    m = {"carry": 0, **fault}
    step = jax.nn.softplus(delta + bias)
    y = jnp.concatenate([
        ref.scan(u[lo:lo + seq_len], jnp.asarray(b[lo:lo + seq_len], jnp.float32),
                 jnp.asarray(c[lo:lo + seq_len], jnp.float32), step[lo:lo + seq_len], a, m, jnp.float32)
        for lo in range(0, u.shape[0], seq_len)]) + skip * u
    return y * jax.nn.silu(z), y


# sequences of 40 rows (no chunk of 16 divides them: chunks of 8) and of 48 in chunks of 8, 16 and 48
# (one chunk); of 272 in ONE chunk whose turn of B and C ends ragged (272 = 2 x 128 + 16); tiles of
# 128 and 256 channels
TILED = [("spread_decay", 40, 8, 128), ("spread_decay", 48, 16, 128), ("spread_decay", 48, 48, 256),
         ("fastest_decay", 48, 16, 256), ("identical_rows", 48, 8, 128), ("spread_decay", 272, 272, 128),
         ("spread_decay", 256, 64, 256)]


@pytest.mark.parametrize("case,seq,rows,cols", TILED, ids=[f"{c}-{s}-in-{r}x{w}" for c, s, r, w in TILED])
def test_the_tiled_scan_is_the_recurrence_and_not_an_approximation(case, seq, rows, cols):
    """Float32 operands, so that what is left between the kernel and the
    recurrence is its FORM alone (the turn of B and C onto the sublanes is
    exact: three bf16 parts), at a BATCH of two: the second sequence starts
    from zero, whatever the first left."""
    arrays, b, c = _kernel_case(case, seq)
    got = ss.selective_scan(*arrays, seq_len=seq, rows=rows, cols=cols, keep=True)
    want = _recurrence(arrays, b, c, seq)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-4 * float(jnp.sqrt(jnp.mean(w ** 2))), rtol=0)
    alone = ss.selective_scan(*arrays, seq_len=seq, rows=rows, cols=cols)  # without the second output
    assert np.array_equal(np.asarray(alone), np.asarray(got[0]))
    # ... and a state NOT dropped where a chunk ends: the fault is another output
    if rows < seq:
        dropped = _recurrence(arrays, b, c, seq, carry=rows)[0]
        assert apart(dropped, want[0]) > 100 * max(apart(got[0], want[0]), 1e-7)


def test_a_sequence_does_not_start_from_its_neighbour_s_state():
    arrays, b, c = _kernel_case("spread_decay", 48)
    both = ss.selective_scan(*arrays, seq_len=48, rows=16)
    second = ss.selective_scan(*(a[48:] if a.shape[0] == 96 else a for a in arrays), seq_len=48, rows=16)
    assert np.array_equal(np.asarray(both[48:]), np.asarray(second))
    as_one = ss.selective_scan(*arrays, seq_len=96, rows=16)  # the fault: the batch's rows one sequence
    assert apart(as_one[48:], both[48:]) > 1e-3


@pytest.mark.parametrize("seq,channels,want", [(8704, 5120, (256, 512)), (34304, 5120, (256, 512)),
                                               (2304, 4096, (256, 512)), (24, 128, (24, 128)),
                                               (40, 64, (40, 64)), (8704, 640, (256, 128))])
def test_the_scan_s_tiles_follow_from_the_shapes_alone(seq, channels, want):
    assert ss.scan_tiles(seq, channels) == want
    assert seq % want[0] == 0 and channels % want[1] == 0


def test_whole_sequences_whole_tiles_and_a_state_that_fits_a_lane_tile_are_asked_for():
    arrays, _, _ = _kernel_case("spread_decay", 48)
    with pytest.raises(ValueError, match="sequences of 36"):
        ss.selective_scan(*arrays, seq_len=36)
    with pytest.raises(ValueError, match="chunks of 32"):
        ss.selective_scan(*arrays, seq_len=48, rows=32)
    wide = [arrays[0], arrays[1], arrays[2], arrays[3], jnp.zeros((256, 80)), arrays[5], arrays[6]]
    with pytest.raises(ValueError, match="state of at most 64"):
        ss.selective_scan(*wide, seq_len=48)


# ---------------------------------------------------------------------------
# the trunk against the reference; the cut against the trunk
# ---------------------------------------------------------------------------

def test_the_trunk_matches_the_reference_through_every_kind_of_layer_at_a_batch_of_two():
    cfg = small(mapping())
    assert cfg.layer_types == (M1, W, M1, W, M1, A, G, C)
    x, got, stats = KIT.trunk(3, batch=2, under=CHUNKS_OF_16)
    want_x, want = KIT.reference(3, batch=2)
    for a, b in ((x, want_x), (got, want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4 * float(jnp.sqrt(jnp.mean(b ** 2))), rtol=0)
    # twelve statistics, the ones a step with layers that carry a state returns
    names = decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS + decoder.LINEAR_STATS
    assert len(stats) == len(names) == 12 and cfg.has_linear and cfg.has_window
    said = dict(zip(names, (float(v) for v in stats)))
    assert said["linear_attn_tokens_total"] == 3 * 2 * S  # three scans
    assert said["linear_attn_chunks_total"] == 3 * 2 * (S // 16)  # one channel tile of 128
    band = 8 * 9 // 2 + (S - 8) * 8  # sum_t min(t + 1, 8)
    assert said["attn_pairs_selected_total"] == 2 * 2 * band  # two windowed layers, two sequences
    assert said["attn_pairs_causal_total"] == 2 * 2 * (S * (S + 1) // 2)
    assert said["decoder_tokens_total"] == 2 * S and said["decoder_sequences_total"] == 2


@pytest.mark.parametrize("rows", [(S - 1,), (0, 17, S - 1)], ids=["last", "three"])
def test_the_step_s_later_layers_on_the_served_rows_alone_are_the_same_layers(rows):
    """``frame_step``'s trunk (the cut) and ``frame_hidden``'s (all rows) are
    the same layers' functions at two row counts: equal at the served rows to
    1e-5 in float32 products, and the rows the layers ran are counted."""
    cfg = small(mapping())
    assert cfg.cut_layer == 5 and cfg.hands_on == (4, 5)
    x, logits, _ = KIT.trunk(4, batch=2, under=CHUNKS_OF_16)  # all rows: made once for the two cases
    cut_x, cut_logits, stats = KIT.trunk(4, batch=2, under=CHUNKS_OF_16, rows=rows)
    at = np.asarray(rows)
    want_x = x.reshape(2, S, -1)[:, at].reshape(2 * len(at), -1)
    want = logits.reshape(2, S, -1)[:, at].reshape(2 * len(at), -1)
    assert cut_x.shape == want_x.shape
    assert apart(cut_x, want_x) < 1e-5 and apart(cut_logits, want) < 1e-5
    names = (decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS + decoder.LINEAR_STATS
             + decoder.AHEAD_STATS + decoder.LOOP_STATS + decoder.BLOCK_STATS + decoder.ROWS_STATS)
    said = dict(zip(names, (float(v) for v in stats)))
    assert len(stats) == len(names) == 19
    assert said["trunk_rows_run_total"] == 5 * 2 * S + 3 * 2 * len(at)  # the cut's layer counted with it
    assert said["trunk_rows_full_total"] == 8 * 2 * S
    assert said["loop_passes_total"] == said["attn_grid_steps_total"] == 0


def test_a_schedule_with_no_tail_that_mixes_no_tokens_runs_every_layer_and_cuts_the_rows_after():
    """Granite's hybrid has no such tail: ``rows`` then only picks the rows
    of what every layer computed, and no counter of the cut is reported."""
    from test_decoder_granite import mapping as granite_mapping, small as granite_small

    cfg = granite_small(granite_mapping())
    assert cfg.cut_layer is None and cfg.hands_on == ()
    params = decoder.init_params(cfg, jax.random.key(1), jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2 * 64, 64)), jnp.float32)
    full, stats = jax.jit(lambda p: decoder.trunk(p, x, np.arange(64), cfg, 2))(params)
    some, cut_stats = jax.jit(lambda p: decoder.trunk(p, x, np.arange(64), cfg, 2, rows=(63,)))(params)
    assert np.array_equal(np.asarray(some), np.asarray(full[63::64]))
    assert len(cut_stats) == len(stats) == 12


def test_frame_step_serves_what_frame_hidden_computes_at_each_frame_s_last_row():
    from psana_ray_tpu.ops import fused_calibrate  # noqa: F401 — the step's first pass

    cfg = small(mapping(patch=16))
    params = decoder.init_params(cfg, jax.random.key(6), jnp.float32)
    rng = np.random.default_rng(6)
    frames = jnp.asarray(rng.integers(80, 200, (2, 2, 16, 128)), jnp.uint16)
    calib = (jnp.full((2, 16, 128), 100.0), jnp.ones((2, 16, 128)), jnp.zeros((2, 16, 128), jnp.uint8))
    ids = jnp.asarray(rng.integers(0, 256, 8))
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(lambda p: decoder.frame_step(p, calib, frames, ids, cfg=cfg,
                                                             threshold=10.0))(params)
        x, _ = jax.jit(lambda p: decoder.frame_hidden(p, calib, frames, ids, cfg=cfg,
                                                      threshold=10.0))(params)
    s = x.shape[0] // 2
    want = decoder.logits_of(decoder.head_params(params), x[s - 1::s], cfg)
    assert logits.shape == (2, 256) and apart(logits, want) < 1e-5
    assert len(stats) == 19 and float(stats[-1]) == 8 * 2 * s  # the cut's counters, last


@pytest.mark.parametrize("fault", ["keys_cut_to_the_served_rows", "the_other_frame_s_row",
                                   "memory_at_row_0"])
def test_a_fault_of_the_cut_s_own_moves_the_served_rows(fault):
    """Each fault of the cut's own, put into the PROGRAM as the controls put
    it (``benchmark/tests/phi4flash_controls.plant``): read by ``served`` alone
    on the chip."""
    from benchmark.tests.phi4flash_controls import plant

    cfg, params, (patches, ids) = small(mapping()), KIT.params(7), inputs(7, batch=2)
    x, good = KIT.trunk(7, batch=2)[0], KIT.trunk(7, batch=2, rows=(S - 1,))[0]  # once for the three cases
    restore = plant(decoder, fault)
    jax.clear_caches()
    try:
        with jax.default_matmul_precision("highest"):
            bad = trunk_of(params, patches, ids, cfg, rows=(S - 1,))[0]
    finally:
        restore()
        jax.clear_caches()
    assert apart(good, x[S - 1::S]) < 1e-5 and apart(bad, x[S - 1::S]) > 1e-2


# which layer of the small model a fault shows in alone (None: in the trunk, through what is handed on)
AT_LAYER = {"memory_from_an_earlier_scan": None, "keys_from_a_windowed_layer": None,
            "memory_after_gate": None, "lambda_from_one": 1, "no_window": 1, "window_doubled": 1,
            "window_before_own": 1, "state_not_carried": 0, "first_channel_s_decays": 0,
            "initialiser_s_decays": 0, "no_softplus": 0, "no_skip": 0, "last_tap_alone": 0,
            "no_conv_bias": 0, "no_dt_bias": 0}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_with_a_fault_or_the_other_reading_in_it_is_another_layer(fault):
    """Each of the controls' faults, and the other reading of each assumed
    point, a LAYER at a time: it moves the reference's own layer (the trunk,
    where the fault is in what is handed on) by far more than the program's
    trunk lies from the reference's — the two that the chip cannot see under
    the initialiser's A, one channel's decays in every channel and the ramp
    assumed, among them: the weights here have a random A."""
    m, params, (patches, ids) = mapping(), KIT.params(5), inputs(5, batch=2)
    x = KIT.trunk(5, batch=2)[0]  # once for the 22 cases, as the first frame's reference
    want = KIT.made("reference of frame 0", 5, lambda: reference_of(params, patches[:1], ids, ref.sizes(m))[0],
                    (HIGHEST,))
    near = apart(x[:S], want)
    faulty = ref.sizes(m, **FAULTS[fault])
    at = AT_LAYER.get(fault, 5)  # the attention's faults: in the full layer
    with jax.default_matmul_precision("highest"):
        if at is None:
            far = apart(reference_of(params, patches[:1], ids, faulty)[0], want)
        else:
            rows = jnp.asarray(np.random.default_rng(5).standard_normal((S, 64)), jnp.float32)
            p, kind = params["layers"][at], ref.kinds(ref.sizes(m))[at]
            def run(sizes):
                return jax.jit(lambda: ref.layer(p, rows, kind, sizes, jnp.float32, 16, at)[0])()

            far = apart(run(faulty), run(ref.sizes(m)))
    assert near < 1e-5 and not far <= max(100 * near, 1e-4), (near, far)


def test_lambda_init_follows_the_layer_s_index_from_zero():
    assert decoder.lambda_init(0) == pytest.approx(0.2) and decoder.lambda_init(17) == pytest.approx(
        0.8 - 0.6 * np.exp(-5.1))


# ---------------------------------------------------------------------------
# the spelling, the file, the adapter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layers", [8, 12, 32])
def test_the_schedule_is_the_published_rule_over_the_index(layers):
    cfg = decoder.DecoderConfig.from_mapping(mapping(num_hidden_layers=layers))
    half = layers // 2
    assert cfg.layer_types == tuple(ref.MAMBA.replace("mamba", M1) if k == ref.MAMBA else
                                    {ref.WINDOW: W, ref.FULL: A, ref.GMU: G, ref.CROSS: C}[k]
                                    for k in ref.kinds(ref.sizes(mapping(num_hidden_layers=layers))))
    counts = {k: cfg.layer_types.count(k) for k in (M1, W, A, G, C)}
    assert counts == {M1: half // 2 + 1, W: half // 2, A: 1, G: half // 2 - 1, C: half // 2 - 1}
    assert cfg.cut_layer == half + 1 and cfg.hands_on == (half, half + 1)
    assert all(cfg.read_from(i) == (half if cfg.layer_types[i] == G else half + 1)
               for i in range(half + 2, layers))
    assert all(cfg.read_from(i) is None for i in range(half + 2))
    assert all(cfg.layer_kind(i) == (cfg.layer_types[i], False) for i in range(layers))  # a dense MLP in each


def test_from_mapping_reads_the_published_keys_and_the_class_s_defaults():
    cfg = decoder.DecoderConfig.from_mapping(mapping(hidden_size=2560, num_attention_heads=40,
                                                     num_key_value_heads=20, sliding_window=512))
    assert (cfg.scan_channels, cfg.scan_dt_rank, cfg.ssm_state, cfg.conv_taps) == (5120, 160, 16, 4)
    assert cfg.head_dim == 64 and cfg.sliding_window == 512 and cfg.rms_eps == 1e-5
    assert cfg.norm == "layer" and cfg.attn_bias and cfg.diff_attention and cfg.conv_bias
    assert not cfg.rotary and not cfg.qk_norm and cfg.tie_embedding and cfg.stream_dtype is None
    assert cfg.num_experts == 0 and cfg.passes == 1 and cfg.block_select is None
    other = decoder.DecoderConfig.from_mapping(mapping(mamba_d_state=8, mamba_expand=4, mamba_d_conv=3,
                                                       mamba_dt_rank=6))
    assert (other.scan_channels, other.scan_dt_rank, other.ssm_state, other.conv_taps) == (256, 6, 8, 3)


@pytest.mark.parametrize("over,said", [
    ({"num_hidden_layers": 10}, "multiple of 4"), ({"mb_per_layer": 4}, "mb_per_layer 4"),
    ({"num_hidden_layers": 4}, "multiple of 4"), ({"mlp_bias": True}, "a bias in the MLP"),
    ({"lm_head_bias": True}, "a bias in the MLP"), ({"sliding_window": None}, "no sliding_window"),
    ({"num_key_value_heads": 1}, "do not pair"), ({"mamba_d_state": 80}, "state over 64")])
def test_from_mapping_refuses_what_is_not_built(over, said):
    with pytest.raises(ValueError, match=said):
        decoder.DecoderConfig.from_mapping(mapping(**over))


def test_only_what_each_kind_has_is_drawn():
    cfg = decoder.DecoderConfig.from_mapping(mapping())
    layers = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))["layers"]
    norms = {"norm1", "norm1_b", "norm2", "norm2_b"}
    mlp = {"w_gate", "w_up", "w_down"}
    lam = {"lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "sub_norm"}
    assert set(layers[0]) == norms | mlp | {"w_in", "conv_w", "conv_b", "w_x", "w_dt", "dt_bias",
                                            "a_log", "d_skip", "w_out"}
    assert set(layers[1]) == set(layers[5]) == norms | mlp | lam | {"w_qkv", "b_qkv", "wo", "b_o"}
    assert set(layers[6]) == norms | mlp | {"w_1", "w_2"}
    assert set(layers[7]) == norms | mlp | lam | {"w_q", "b_q", "wo", "b_o"}
    assert layers[0]["a_log"].shape == (128, 16) and layers[0]["w_x"].shape == (128, 4 + 32)
    assert layers[1]["w_qkv"].shape == (64, 64 + 32 + 32) and layers[1]["sub_norm"].shape == (32,)
    drawn = decoder.init_params(cfg, jax.random.key(0))["layers"][0]
    assert np.allclose(-np.exp(np.asarray(drawn["a_log"], np.float32)), -np.arange(1, 17))  # the ramp


def _file():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_file_holds_the_catalog_s_numbers_unchanged_and_cuts_nothing():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
    cfg = _file()
    assert cfg["source"] == row["source_url"] and len(cfg["source"]) <= 200
    assert {k for k, v in row["config"].items() if cfg.get(k, "absent") != v} == set() == set(
        cfg["reduced"])
    assert "the whole model on one chip" in cfg["deployment"]
    said = " ".join(cfg["assumed"])
    for reading in ("mamba_d_state 16", "ceil(2,560 / 16) = 160", "plain=True", "lam_base=1",
                    "attn_bias=False", "rotary=True", "window_own=False", "memory='gated'",
                    "A = -(1 .. 16)", "[0.001, 0.1]", "[0, 200,064)", "linear patch embedding"):
        assert reading in said, reading
    assert cfg["step_tokens"] == cfg["batch_size"] * cfg["sequence_tokens"] == 17408
    assert cfg["transport"] == {"scheme": "shm", "slots": 8} and cfg["program"] == "prefill_cut"
    assert cfg["reference"] == {"module": "phi4flash_decoder", "query_block": 512, "sequences": [-1]}
    # weights, recounted: 3.853 G parameters, 7.71 GB in bf16; a layer of each kind
    got = decoder.DecoderConfig.from_mapping(cfg)
    shapes = jax.eval_shape(lambda k: decoder.init_params(got, k), jax.random.key(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert round(count / 1e9, 3) == 3.853 and round(2 * count / 1e9, 2) == 7.71
    by_layer = [round(sum(int(np.prod(a.shape)) for a in jax.tree.leaves(p)) / 1e6, 2)
                for p in shapes["layers"]]
    assert (by_layer[0], by_layer[1], by_layer[18], by_layer[19]) == (119.9, 98.32, 104.87, 91.77)
    assert "head" not in shapes and got.cut_layer == 17  # the tied table is the head
    rehearsal = decoder.DecoderConfig.from_mapping({**cfg, **cfg["rehearse"]})
    assert rehearsal.layer_types == (M1, W, M1, W, M1, A, G, C) and rehearsal.sliding_window == 8
    assert rehearsal.sliding_window < cfg["rehearse"]["sequence_tokens"]


def test_phi4flash_roofline_counts_at_the_published_sizes():
    from benchmark.roofline import phi4flash

    scan = phi4flash.selective_scan(1, 8704, 2560, 2, 16)
    assert scan["flops"] == 7 * 16 * 5120 * 8704 and round(scan["bytes"] / 1e6, 1) == 446.2
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12  # bytes bound it ON PAPER: the file says why
    half = phi4flash.diff_attention(2, 8704, 512, 40, 20, 2560)  # ONE call: twenty half-heads
    assert half["flops"] == 384 * 20 * 2 * (512 * 513 // 2 + (8704 - 512) * 512)
    assert half["bytes"] == 2 * 17408 * 64 * (20 + 10 + 20 + 40)  # q, k, the pairs' values, the output
    full = phi4flash.diff_attention(2, 8704, 0, 40, 20, 2560)
    assert full["flops"] == 384 * 20 * 2 * (8704 * 8705 // 2)
    band = {"flops": 2 * half["flops"]}  # a layer makes two calls
    conv = phi4flash.conv_silu_taps(2, 8704, 2560, 2, 4)
    assert conv["bytes"] == 4 * 17408 * 5120 and conv["bytes"] / 819e9 > conv["flops"] / 197e12
    step = phi4flash.step(2, 8704, 2560, 32, 10240, 40, 20, 512, 2, 16, 4, 160, 200064, 256, 16)["flops"]
    assert round(step / 1e12, 1) == 66.2
    rows, mlp = 17408, 6 * 2560 * 10240
    mamba = 2 * 2560 * 10240 + 2 * 5120 * 192 + 2 * 160 * 5120 + 2 * 5120 * 2560 + 2 * 4 * 5120
    scores = 384 * 40 * 8704
    by_hand = (9 * rows * (mamba + mlp) + 8 * (rows * (2 * 2560 * 5120 + 2 * 2560 * 2560 + mlp))
               + 8 * band["flops"] + rows * 2 * 2560 * 2560 + 2 * (4 * 2560 * 2560 + scores + mlp)
               + 7 * 2 * (4 * 2560 * 5120 + mlp) + 7 * 2 * (4 * 2560 * 2560 + scores + mlp)
               + 2 * 2 * 8448 * 256 * 2560 + 2 * 2 * 2560 * 200064)
    assert step == by_hand
    assert phi4flash.scans_vector_ops(2, 8704, 2560, 32, 2, 16) == 9 * 2 * scan["flops"]  # stated apart


@pytest.mark.parametrize("lacks", ["scan_channels", "scan_dt_rank", "diff_attention", "norm", "attn_bias"])
def test_the_adapter_ends_the_run_where_the_package_lacks_the_mechanism(monkeypatch, lacks):
    from benchmark.programs import prefill_cut

    fields = [f for f in dataclasses.fields(decoder.DecoderConfig) if f.name != lacks]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: fields)
    cfg = _file()
    cfg.update(cfg["rehearse"])
    with pytest.raises(SystemExit, match=lacks):
        prefill_cut.Program(cfg, 1, "", None)


@pytest.mark.parametrize("part", ["first_rows.0", "patch_rows.1", "prompt_rows.1"])
def test_every_part_of_rows_decides_in_this_adapter_by_its_own_share(part, monkeypatch):
    from benchmark.programs import prefill_batched, prefill_cut

    assert 0 < prefill_cut.TOSSED_ROWS_SHARE < prefill_batched.TOSSED_ROWS_SHARE
    parts = {name: {"rows_over_limit": 0.5 if name == part else 0.0, "ok": True}
             for name in ("first_rows.0", "patch_rows.1", "prompt_rows.1")}
    verdict = {**parts, "isolated.0": {"ok": True}, "head": {"ok": True}, "ok": True}
    monkeypatch.setattr(prefill_batched.Program, "check", lambda self, frames: verdict)
    got = prefill_cut.Program.check(object.__new__(prefill_cut.Program), None)
    assert not got["ok"] and not got[part]["ok"]
    assert all(got[name]["ok"] for name in parts if name != part)


def test_the_cell_s_rehearsal_runs_the_served_path_is_correct_and_reports_the_cut_s_counters():
    line, done = rehearse(CELL, seed=1)
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0 and line["cell"] == CELL
    for name in ("ring_depth.hit", "device_wait_ms.hit", "startup_trace_s"):
        assert name in line["would_report"], name
    verdict = checked(done)
    assert verdict["isolated.0"]["ok"] and verdict["isolated.1"]["ok"] and verdict["served"]["ok"]
    assert verdict["served"]["sequences"] == 2 and verdict["first_rows.1"]["rows_over_share_limit"] == 0.1
