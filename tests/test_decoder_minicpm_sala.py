"""A selection of BLOCKS a key head scored by the attention's own pooled keys
(``parallel/sparse_attention.select_blocks``), the masked causal kernel under
its flags, linear attention with a fixed decay a head
(``ops/lightning.lightning_attention``) and the trunk that mixes them under
MiniCPM's three multipliers (``models/decoder.py`` reading MiniCPM-SALA's keys)
against the benchmark's plain reference
(``benchmark/reference/minicpm_sala_decoder.py``: the recurrence token by
token, the selection by a dense softmax and ``lax.top_k``) at small sizes on
the CPU; the other reading of every assumption that has one; the new cell's
manifest entries, counters and counts."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import minicpm_sala_decoder as ref
from benchmark.roofline import minicpm_sala as roofline
from decoder_kit import HIGHEST, Kit, inputs, loud
from psana_ray_tpu.models import decoder
from psana_ray_tpu.ops import lightning
from psana_ray_tpu.parallel import sparse_attention as sa
from test_manifest_entries import BENCH

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmark", "configs", "minicpm_sala_prefill_epix10k2m.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "minicpm_sala_epix_saturated"
SPARSE, LINEAR = "minicpm4", "lightning-attn"
SMALL_SELECTION = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=4, init_blocks=1,
                       window_size=16, dense_len=32)
# the controls' faults (benchmark/tests/minicpm_sala_controls.py), at this size's chunk
FAULTS = {"no_selection": {"select": False}, "no_forced_local_blocks": {"window_size": 0},
          "one_head_s_scores": {"group_sum": False}, "first_key_of_a_pool": {"pool": "first"},
          "first_head_s_decay": {"decay": "first"}, "no_decay": {"decay": "none"},
          "state_not_carried": {"carry": 16}, "linear_unturned": {"rotary": False},
          "rotary_in_sparse": {"attn_rotary": True}, "no_gate": {"gate": False},
          "m_is_1": {"residual": 1.0}}
# the assumptions of the file that have an OTHER reading, as the reference spells it
OTHER_READINGS = {"forced_blocks_beside_the_topk": {"forced": "beside"},
                  "a_norm_a_head_on_the_sparse_layers_too": {"attn_qk_norm": True},
                  "a_gate_a_head": {"gate": "head"}, "minimax_s_slopes_a_layer": {"decay": "minimax"},
                  "qk_norm_over_the_projection": {"qk_norm": "projection"},
                  "output_norm_over_the_projection": {"out_norm": "projection"},
                  "gate_before_the_norm": {"gate_first": True}}


def catalog_config() -> dict:
    with open(CATALOG) as f:
        return next(json.loads(line) for line in f if '"MiniCPM-SALA"' in line)["config"]


def mapping(**over):
    """MiniCPM-SALA's Hugging Face keys at a small size: its first four
    mixers (one sparse layer, three linear), 4 heads on 2 key heads of 16, a
    selection of 4 of 8 blocks of 8 keys past 32 tokens."""
    m = dict(catalog_config(), hidden_size=64, num_hidden_layers=4,
             mixer_types=[SPARSE, LINEAR, LINEAR, LINEAR], num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, lightning_nh=4, lightning_nkv=4,
             lightning_head_dim=16, intermediate_size=96, vocab_size=256, dim_model_base=16,
             published={"num_hidden_layers": 32}, sparse_config=dict(SMALL_SELECTION), patch=8)
    m.update(over)
    return m


# tiles that cut 64 tokens into several
KIT = Kit(mapping, ref, tiles=dict(q_tile=32, attn_q_tile=32, causal_q_tile=32, causal_kv_tile=32,
                                   linear_chunk=16))
small = KIT.small


def relative_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2, axis=-1)) / np.sqrt(np.mean(want ** 2, axis=-1))


def program_hidden(m, params, patches, ids):
    cfg = small(m)
    x = decoder.embed(params, patches, ids, cfg.embedding_multiplier, cfg.stream_dtype)
    return jax.jit(lambda p, x: decoder.trunk(p, x, np.arange(x.shape[0]), cfg))(params, x)


# ---------------------------------------------------------------------------
# the configuration's spelling
# ---------------------------------------------------------------------------

def test_from_mapping_reads_the_catalog_s_config_as_it_stands():
    cfg = decoder.DecoderConfig.from_mapping(catalog_config())
    assert cfg.num_layers == 32 and cfg.layer_types.count(decoder.ATTENTION) == 8
    assert cfg.layer_types.count(decoder.LINEAR) == 24 and cfg.layer_types[:4] == (
        decoder.ATTENTION, decoder.LINEAR, decoder.LINEAR, decoder.LINEAR)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) == (4096, 16384, 73448)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.linear_head_dim) == (32, 2, 128, 128)
    # no sparse_config in the file: MiniCPM4's published sizes
    assert cfg.block_select == sa.BlockSelection(32, 16, 64, 64, 1, 2048, 8192)
    assert cfg.linear_decay == "fixed" and cfg.linear_rotary and not cfg.rotary
    assert cfg.attn_gate == "elementwise" and not cfg.qk_norm and not cfg.indexer_heads
    assert not cfg.tie_embedding and cfg.stream_dtype == jnp.float32
    assert (cfg.embedding_multiplier, cfg.logits_scaling) == (12.0, 16.0)
    assert cfg.residual_multiplier == pytest.approx(1.4 / 32 ** 0.5)


def test_the_multipliers_use_the_published_depth_and_not_the_cut_s():
    with open(CONFIG) as f:
        cut = json.load(f)
    assert cut["num_hidden_layers"] == 4 == len(cut["mixer_types"]) and cut["reduced"] == ["num_hidden_layers"]
    cfg = decoder.DecoderConfig.from_mapping(cut)
    assert cfg.residual_multiplier == pytest.approx(1.4 / 32 ** 0.5)  # not 1.4 / 2
    assert ref.sizes(cut)["residual"] == pytest.approx(1.4 / 32 ** 0.5)
    uncut = decoder.DecoderConfig.from_mapping({k: v for k, v in cut.items() if k != "published"})
    assert uncut.residual_multiplier == pytest.approx(0.7)  # a file that states no other depth: its own


@pytest.mark.parametrize("mixers", [[SPARSE, LINEAR, "mamba", LINEAR], [SPARSE, LINEAR, LINEAR],
                                    [SPARSE] * 5], ids=["unknown_word", "too_few", "too_many"])
def test_from_mapping_refuses_a_malformed_mixer_types(mixers):
    with pytest.raises(ValueError, match="mixer_types"):
        decoder.DecoderConfig.from_mapping(mapping(mixer_types=mixers))


@pytest.mark.parametrize("over", [dict(lightning_nkv=2), dict(use_output_norm=False),
                                  dict(lightning_scale="1")], ids=["key_heads", "no_norm", "scale"])
def test_from_mapping_refuses_linear_layers_it_does_not_build(over):
    with pytest.raises(ValueError, match="not built"):
        decoder.DecoderConfig.from_mapping(mapping(**over))


def test_init_params_draws_only_what_each_kind_has():
    cfg = small(mapping())
    shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))
    sparse, linear = shapes["layers"][0], shapes["layers"][1]
    assert set(sparse) == {"norm1", "wq", "wk", "wv", "wo", "w_attn_gate", "norm2", "w_gate", "w_up",
                           "w_down"}  # no indexer, no q / k norm
    assert sparse["w_attn_gate"].shape == (64, 64) and sparse["wk"].shape == (64, 32)
    assert set(linear) == {"norm1", "w_q", "w_k", "w_v", "w_z", "q_norm", "k_norm", "o_norm", "wo",
                           "norm2", "w_gate", "w_up", "w_down"}  # no decay, no step size, no taps
    assert linear["q_norm"].shape == (16,) and "head" in shapes


# ---------------------------------------------------------------------------
# the linear kernel against the recurrence, token by token
# ---------------------------------------------------------------------------

def _linear_case(heads, d, seq, batch, turned, seed=0):
    rng = np.random.default_rng(seed)
    t = batch * seq
    q, k, v, z = (jnp.asarray(rng.standard_normal((t, heads * d)), jnp.float32) for _ in range(4))
    gains = [jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32) for _ in range(3)]
    angles = decoder.rotary_angles(np.arange(seq), 10000.0, d // 2) if turned else None
    return (q, k, v, z, *gains), angles


def _recurrence(arrays, angles, seq, heads, eps, scale):
    """The reference's own lines on the kernel's operands, sequence by sequence."""
    q, k, v, z, qg, kg, og = arrays
    t, d = q.shape[0], q.shape[1] // heads
    lam = jnp.exp(-jnp.asarray(lightning.decay_slopes(heads)))
    out = []
    for rows in (slice(b * seq, (b + 1) * seq) for b in range(t // seq)):
        qq = ref.rms(q[rows].reshape(seq, heads, d), qg, eps) * scale
        kk = ref.rms(k[rows].reshape(seq, heads, d), kg, eps)
        if angles is not None:
            qq, kk = ref.rotate(qq, angles), ref.rotate(kk, angles)

        def one(state, u):
            q1, k1, v1 = u
            state = lam[:, None, None] * state + jnp.einsum("hk,hv->hkv", k1, v1, precision="highest")
            return state, jnp.einsum("hkv,hk->hv", state, q1, precision="highest")

        _, o = jax.lax.scan(one, jnp.zeros((heads, d, d)), (qq, kk, v[rows].reshape(seq, heads, d)))
        gate = jax.nn.sigmoid(z[rows].reshape(seq, heads, d))
        out.append((ref.rms(o, og, eps) * gate).reshape(seq, heads * d))
    return jnp.concatenate(out)


@pytest.mark.parametrize("heads,d,seq,batch,chunk,turned", [
    (4, 16, 64, 2, 16, True), (8, 16, 64, 1, 32, True), (2, 128, 48, 1, 16, True),
    (3, 16, 40, 2, 8, False), (4, 16, 64, 1, 64, True)],
    ids=["4_heads_batch_2", "8_heads_two_blocks", "heads_of_a_lane_tile", "3_heads_unturned",
         "one_chunk"])
def test_the_linear_kernel_in_float32_products_is_the_recurrence(heads, d, seq, batch, chunk, turned):
    arrays, angles = _linear_case(heads, d, seq, batch, turned)
    turn = None if angles is None else decoder.turn_tables(jnp.tile(angles, (batch, 1)), d)
    got = lightning.lightning_attention(
        *arrays[:4], jnp.asarray(lightning.decay_slopes(heads)), *arrays[4:], turn, seq_len=seq,
        heads=heads, eps=1e-6, scale=d ** -0.5, chunk=chunk, product_dtype=jnp.float32)
    want = _recurrence(arrays, angles, seq, heads, 1e-6, d ** -0.5)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4 and float(jnp.max(jnp.abs(want))) > 1.0


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_the_linear_kernel_s_chunk_changes_nothing(chunk):
    arrays, angles = _linear_case(4, 16, 64, 2, True, seed=3)
    turn = decoder.turn_tables(jnp.tile(angles, (2, 1)), 16)

    def run(c):
        return lightning.lightning_attention(
            *arrays[:4], jnp.asarray(lightning.decay_slopes(4)), *arrays[4:], turn, seq_len=64,
            heads=4, eps=1e-6, scale=0.25, chunk=c, product_dtype=jnp.float32)

    assert float(jnp.max(jnp.abs(run(chunk) - run(64)))) < 2e-5
    assert lightning.step_rows(64, chunk) == (64, chunk) and lightning.step_rows(34304) == (512, 256)


def test_the_slopes_are_lightning_attention_2_s():
    s = lightning.decay_slopes(32)
    assert s.dtype == np.float32 and s[0] == pytest.approx(2 ** -0.25) and s[-1] == pytest.approx(2 ** -8)
    assert np.all(np.diff(s) < 0)
    np.testing.assert_allclose(np.asarray(ref.slopes(ref.sizes(mapping(lightning_nh=32, lightning_nkv=32,
                                                                      num_attention_heads=32)), 0)), s)


# ---------------------------------------------------------------------------
# the selection: ids bit for bit against lax.top_k on the reference's scores
# ---------------------------------------------------------------------------

def _selection_case(case, s=128, g=2, rep=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((s, g * rep * d)) * d ** -0.5 * 3.0
    k = rng.standard_normal((s, g * d))
    sel = dict(SMALL_SELECTION)
    if case == "ties":  # a detector's blank patches: one key again and again, every pooled score equal
        k = np.tile(k[:1], (s, 1))
    elif case == "forced_blocks_fill_the_topk":
        sel.update(topk=3, window_size=16)  # the first block and the latest two: nothing is chosen
    elif case == "fewer_blocks_than_topk":
        sel.update(topk=32)
    elif case == "sharp_scores":  # probabilities that underflow to 0: ties at 0 among far blocks
        q = q * 40.0
    return jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32), sa.BlockSelection(**sel)


def _reference_selection(q, k, g, sel):
    s, d = k.shape[0], k.shape[1] // g
    m = {"H": q.shape[1] // d, "G": g, "dh": 1.0, "group_sum": True, "pool": "mean", "forced": "among",
         **dataclasses.asdict(sel)}  # (q comes scaled already: dh 1)
    pooled = ref.pooled_keys(k.reshape(s, g, d), m)
    scores = jnp.stack([ref.block_scores(q.reshape(s, -1, d), pooled, 0, gi, m, jnp.float32)
                        for gi in range(g)])
    return scores, jnp.stack([ref.selection(scores[gi], 0, m) for gi in range(g)])


@pytest.mark.parametrize("case", ["random", "ties", "forced_blocks_fill_the_topk",
                                  "fewer_blocks_than_topk", "sharp_scores"])
def test_the_selected_block_ids_are_lax_top_k_s_on_the_reference_s_scores(case):
    q, k, sel = _selection_case(case)
    s, n_blocks = q.shape[0], q.shape[0] // sel.block_size
    flags, live, scores = sa.select_blocks(q, k, num_kv_heads=2, selection=sel, block_q=32,
                                           with_scores=True)
    with jax.default_matmul_precision("highest"):
        want_scores, want = _reference_selection(q, k, 2, sel)
    np.testing.assert_allclose(np.asarray(scores[:, :, :n_blocks]), np.asarray(want_scores), rtol=5e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(flags[:, :, :n_blocks] != 0), np.asarray(want))
    assert not np.asarray(flags[:, :, n_blocks:]).any()  # the lanes past the sequence's blocks
    kept = np.asarray(flags != 0).sum(-1)
    assert (kept == np.minimum(np.arange(s) // sel.block_size + 1, sel.topk)[None]).all()
    # a query before the first whole pool sees no pooled key: its one block, forced
    assert (np.asarray(scores)[:, :sel.kernel_size - 1] == 0).all() and (kept[:, :sel.block_size] == 1).all()
    assert live.shape == (2, s // 32, 1) and np.asarray(live).all()
    pairs = sum(int(sa.blocks_to_dense(flags[g], s, sel.block_size).sum()) for g in range(2))
    assert pairs == 2 * sel.pairs(s) == 2 * roofline.selected_pairs(s, sel.block_size, sel.topk)


def test_the_forced_blocks_are_the_first_and_the_latest():
    q, k, sel = _selection_case("random", seed=5)
    flags = np.asarray(sa.select_blocks(q, k, num_kv_heads=2, selection=sel, block_q=32)[0]) != 0
    t = np.arange(q.shape[0])
    last = t // sel.block_size
    assert flags[:, :, 0].all() and flags[:, t, last].all()
    assert flags[:, t[16:], last[16:] - 1].all()  # window_size 16: the latest two blocks
    assert not flags[:, t, np.minimum(last + 1, flags.shape[2] - 1)][:, :-8].any()  # nothing ahead


def test_the_selection_s_sizes_must_be_the_built_ones():
    for bad in (dict(kernel_size=8), dict(block_size=12), dict(block_size=3), dict(topk=2)):
        with pytest.raises(ValueError, match="built for"):
            sa.BlockSelection(**{**SMALL_SELECTION, **bad})
    assert sa.BlockSelection().tiles(34304) == (2048, 32, 640) and sa.BlockSelection().pairs(34304) == 131171072


# ---------------------------------------------------------------------------
# the masked causal kernel under a key head's block flags
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key_tile", [None, 32], ids=["one_key_tile", "several_key_tiles"])
@pytest.mark.parametrize("d", [16, 128], ids=["heads_of_16", "heads_of_a_lane_tile"])
def test_attention_under_block_flags_is_the_dense_masked_softmax(key_tile, d, monkeypatch):
    if key_tile:  # the rule's widest tile, shrunk: 96 keys in tiles of 32 (4 blocks of 8), padded to 128
        monkeypatch.setattr(sa, "MASK_TILE", key_tile)
    s, g, rep = 96, 2, 2
    rng = np.random.default_rng(d)
    sel = sa.BlockSelection(**SMALL_SELECTION)
    q = jnp.asarray(rng.standard_normal((s, g * rep * d)) * d ** -0.5, jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((s, g * d)), jnp.bfloat16) for _ in range(2))
    flags, _ = sa.select_blocks(q, k, num_kv_heads=g, selection=sel, block_q=32)
    assert sel.tiles(s)[0] == (key_tile or 64)
    got = sa.masked_gqa_attention(q[None], k[None], v[None], flags, num_kv_heads=g, block_q=32,
                                  mask_blocks=sel)[0]
    qf, kf, vf = (u.astype(jnp.float32) for u in (q, k, v))
    want = []
    for gi in range(g):
        open_ = sa.blocks_to_dense(flags[gi], s, sel.block_size)
        for h in range(gi * rep, (gi + 1) * rep):
            logit = jnp.dot(qf[:, h * d:(h + 1) * d], kf[:, gi * d:(gi + 1) * d].T, precision="highest")
            prob = jax.nn.softmax(jnp.where(open_, logit, -jnp.inf), axis=-1)
            want.append(jnp.dot(prob, vf[:, gi * d:(gi + 1) * d], precision="highest"))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - jnp.concatenate(want, axis=1)))) < 0.02


def test_block_flags_of_another_shape_are_refused():
    sel = sa.BlockSelection(**SMALL_SELECTION)
    q = jnp.zeros((1, 64, 64), jnp.bfloat16)
    k = jnp.zeros((1, 64, 32), jnp.bfloat16)
    with pytest.raises(ValueError, match="select blocks"):
        sa.masked_gqa_attention(q, k, k, jnp.zeros((1, 64, 128), jnp.int8), num_kv_heads=2,
                                mask_blocks=sel)


# ---------------------------------------------------------------------------
# the trunk against the reference, on both sides of dense_len
# ---------------------------------------------------------------------------

def _both(m, seed, **fault):
    """The program's rows and the reference's (float32 and bf16 operands)."""
    cfg = small(m)
    params = jax.jit(lambda k: decoder.init_params(cfg, k))(jax.random.key(seed))
    (patches,), ids = inputs(seed)
    sizes = ref.sizes(m, **fault)
    with jax.default_matmul_precision("highest"):
        want, stated = (ref.hidden(params, patches, ids, sizes, c, 32) for c in (jnp.float32, jnp.bfloat16))
    return params, patches, ids, want, stated


@pytest.mark.parametrize("dense_len", [32, 64], ids=["past_dense_len_selects", "within_dense_len_dense"])
def test_the_trunk_is_the_reference_s_on_both_sides_of_dense_len(dense_len):
    m = mapping(sparse_config={**SMALL_SELECTION, "dense_len": dense_len})
    params, patches, ids, want, stated = _both(m, seed=1)
    got, stats = program_hidden(m, params, patches, ids)
    err, yard = relative_rms(got, want), relative_rms(stated, want)
    assert np.median(err) <= 4 * np.median(yard) and np.mean(err > 4 * np.median(yard)) <= 0.3
    assert got.dtype == jnp.float32  # the stream between the layers
    selected, causal = float(stats[8]), float(stats[9])
    assert causal == 2 * 64 * 65 // 2
    assert selected == (2 * sa.BlockSelection(**SMALL_SELECTION).pairs(64) if dense_len == 32 else causal)


def _calls(jaxpr, name):
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"] == name:
            found += 1
        for inner in eqn.params.values():
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                found += _calls(getattr(inner, "jaxpr", inner), name)
    return found


@pytest.mark.parametrize("tokens,selects", [(32, 0), (64, 1)], ids=["32_tokens_dense", "64_tokens_select"])
def test_a_sequence_within_dense_len_attends_densely_and_a_longer_one_selects(tokens, selects):
    cfg = small(mapping())
    params = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))
    x = jax.ShapeDtypeStruct((tokens, 64), jnp.bfloat16)
    traced = jax.make_jaxpr(lambda p, x: decoder.trunk(p, x, np.arange(tokens), cfg))(params, x).jaxpr
    assert _calls(traced, "select_blocks") == selects
    assert _calls(traced, "masked_gqa_attention") == 1 and _calls(traced, "lightning_attention") == 3


def test_a_selection_is_one_sequence_s():
    cfg = small(mapping())
    params = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))
    with pytest.raises(ValueError, match="per sequence"):
        jax.eval_shape(lambda p, x: decoder.trunk(p, x, np.arange(64), cfg, batch=2), params,
                       jax.ShapeDtypeStruct((128, 64), jnp.bfloat16))


SPARSE_S = ("forced", "attn_qk_norm", "gate", "select", "window_size", "group_sum", "pool", "attn_rotary")


def _layer_apart(fault):
    """One layer of the reference on loud weights, sound, with bf16 operands
    and with ``fault``: how far the fault moves the layer's change of the
    stream, and how far the rounding does (the kind of layer the fault is
    in: the sparse one, or the last linear one)."""
    m = mapping()
    place = 0 if any(key in fault for key in SPARSE_S) else 3

    def sound():  # the layer at `place`, its input and the reference sound and with bf16 operands
        params = loud(jax.jit(lambda k: decoder.init_params(small(m), k))(jax.random.key(2)))
        kind, p = ref.kinds(ref.sizes(m))[place], params["layers"][place]
        x = 3.0 * jnp.asarray(np.random.default_rng(0).standard_normal((64, 64)), jnp.float32)
        return (kind, p, x, *(ref.layer(p, x, kind, ref.sizes(m), c, 32) - x for c in (jnp.float32, jnp.bfloat16)))

    kind, p, x, want, stated = KIT.made("layer", place, sound, (HIGHEST,))  # once a place for the 18 cases
    with jax.default_matmul_precision("highest"):
        other = ref.layer(p, x, kind, ref.sizes(m, **fault), jnp.float32, 32) - x

    def rms(u):
        return float(np.sqrt(np.mean(np.asarray(u, np.float64) ** 2)))

    return rms(other - want) / rms(want), rms(stated - want) / rms(want)


@pytest.mark.parametrize("name", sorted(OTHER_READINGS))
def test_other_reading_of_an_assumed_point_differs_measurably(name):
    """On loud weights each ``assumed`` point's two readings lie further
    apart than five times what rounding the operands to bf16 moves the layer."""
    apart, yard = _layer_apart(OTHER_READINGS[name])
    assert apart > 5 * yard and apart > 0.02, (apart, yard)


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_a_planted_fault_moves_the_reference_s_layer(name):
    """What the controls plant is not a no-op at this size."""
    apart, yard = _layer_apart(FAULTS[name])
    assert apart > 5 * yard and apart > 0.02, (apart, yard)


# ---------------------------------------------------------------------------
# the step's statistics, the roofline's counts, the manifest
# ---------------------------------------------------------------------------

def test_the_step_counts_its_pairs_tiles_and_chunks():
    from psana_ray_tpu.utils.metrics import PipelineMetrics

    m = mapping()
    params, patches, ids, _, _ = _both(m, seed=4)
    _, stats = program_hidden(m, params, patches, ids)
    metrics = PipelineMetrics()
    decoder.fold_step_stats(metrics, stats)
    c = metrics.snapshot()
    assert len(stats) == 12 and c["decoder_tokens_total"] == 64
    assert c["attn_tiles_live_total"] == c["attn_tiles_causal_total"] == 2  # one tile a key head
    assert c["attn_pairs_selected_total"] / c["attn_pairs_causal_total"] == pytest.approx(2880 / 4160)
    assert c["linear_attn_tokens_total"] == 3 * 64 and c["linear_attn_chunks_total"] == 3 * 4 * 4


def test_the_roofline_functions_count_the_published_step():
    with open(CONFIG) as f:
        cfg = json.load(f)
    sc = cfg["sparse_config"]
    need = roofline.step(cfg["sequence_tokens"], cfg["hidden_size"], cfg["mixer_types"],
                         cfg["intermediate_size"], 32, 2, 128, 32, 128, sc["kernel_stride"],
                         sc["block_size"], sc["topk"], cfg["vocab_size"], cfg["prompt_tokens"], cfg["patch"])
    assert 78.5e12 < need["flops"] < 79.1e12
    assert roofline.selected_pairs(34304, 64, 64) == 131171072
    assert roofline.sparse_attention(34304, 32, 2, 128, 64, 64)["flops"] == 4 * 128 * 32 * 131171072
    assert roofline.lightning_attention(34304, 32, 128)["flops"] == 4 * 128 * 128 * 32 * 34304
    assert 0.29e12 < roofline.select_blocks(34304, 32, 2, 128, 16, 64)["flops"] < 0.31e12


def test_the_configuration_keeps_every_published_width_and_lists_what_it_assumes():
    with open(CONFIG) as f:
        cfg = json.load(f)
    published = catalog_config()
    changed = {k for k, v in published.items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "mixer_types"} and cfg["mixer_types"] == published["mixer_types"][:4]
    assert cfg["published"]["num_hidden_layers"] == 32 and "eight pipeline stages" in cfg["deployment"]
    assert sum("OTHER reading" in point for point in cfg["assumed"]) >= 5
    assert cfg["rehearse"]["sequence_tokens"] > cfg["rehearse"]["sparse_config"]["dense_len"]
    assert cfg["sequence_tokens"] == 34304 > cfg["sparse_config"]["dense_len"]


def test_the_manifest_s_cell_runs_this_configuration_under_saturated_traffic_on_one_chip():
    cell = BENCH.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("minicpm_sala_prefill_epix10k2m", "saturated", 1)
    with open(CONFIG) as f:
        assert BENCH.file(CELL) == json.load(f)


def test_the_adapter_names_fields_the_decoder_has():
    from benchmark.programs import prefill_block_sparse

    have = {f.name for f in dataclasses.fields(decoder.DecoderConfig)}
    assert set(prefill_block_sparse.MECHANISM) <= have
    assert 0.0 < prefill_block_sparse.TOSSED_ROWS_SHARE < 0.7
