"""A BLOCK of heads a grid step of the batched causal kernel (PR 66): where
a head is alone in its group ``_causal_kernel`` takes ``hb`` heads a step,
head ``h + 1``'s score product written before head ``h``'s softmax. Every
head's own arithmetic is the single-head form's, so the outputs are equal
TO THE BIT (interpret mode, small shapes); the rule that picks ``hb`` is a
table of the served shapes; its two counters reach the pipeline's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_kit import Kit
from psana_ray_tpu.models import decoder
from psana_ray_tpu.parallel import sparse_attention as sa

D, DS = 128, 64  # a head of one lane block, and latent attention's rotary part


def _operands(seed, b, s, g):
    rng = np.random.default_rng(seed)

    def draw(shape, dtype=jnp.bfloat16, by=0.5):
        return jnp.asarray(rng.standard_normal(shape) * by, dtype)

    angles = jnp.asarray(rng.uniform(0, 6, (b * s, DS // 2)), jnp.float32)
    return {"q": draw((b, s, g * D)), "kv": draw((b, s, g * 2 * D)),
            "qs": draw((b, s, g * DS), jnp.float32), "ks": draw((b, s, DS)),
            "tables": decoder.turn_tables(angles), "rng": rng}


def _split(kv, g):
    b, s, _ = kv.shape
    halves = kv.reshape(b, s, g, 2, D)
    return halves[:, :, :, 0].reshape(b, s, g * D), halves[:, :, :, 1].reshape(b, s, g * D)


def _mask(ops, s, bq, bk):
    draw = ops["rng"].standard_normal
    return sa.select_keys(jnp.asarray(draw((4, s, 32)), jnp.float32), jnp.asarray(draw((s, 32)), jnp.float32),
                          jnp.asarray(draw((s, 4)), jnp.float32), topk=s // 3, block_q=bq // 2,
                          block_k=bk, mask_k=bk)[0]


CASES = {
    # dsv32's form: ONE sequence under the selection's mask, the shared part turned by the kernel,
    # keys and values of one array
    "masked_shared_turned": dict(b=1, g=8, masked=True, joint=True),
    # kimi's: a batch of two, maskless (a branch below the diagonal and one on it)
    "maskless_shared_turned_batch_of_two": dict(b=2, g=4, masked=False, joint=True),
    # keys and values of two arrays: two blocks a step
    "two_arrays": dict(b=2, g=4, masked=False, joint=False),
    # the kernel's own rotary (the looped reader's): q and k float32, each head's key tile turned
    "turned_by_the_kernel": dict(b=2, g=4, rotary=True),
}


KIT = Kit()  # no model here: its cache alone, keyed by a case's name


def _served(name, heads):
    return KIT.made("served", (name, heads), lambda: np.asarray(
        _attend(CASES[name], heads).astype(jnp.float32)), under=None)


def _attend(case, heads, s=64, bq=32, bk=32):
    b, g = case["b"], case["g"]
    ops = _operands(len(case) + g, b, s, g)
    if case.get("rotary"):
        rng = ops["rng"]
        q, k = (jnp.asarray(rng.standard_normal((b, s, g * D)) * 0.5, jnp.float32) for _ in range(2))
        tables = decoder.turn_tables(jnp.asarray(rng.uniform(0, 6, (b * s, D // 2)), jnp.float32), D)
        return sa._causal_attention(q, k, _split(ops["kv"], g)[1], g, bq, bk, True, turn=tables,
                                    q_scale=D ** -0.5, heads=heads)
    k, v = (ops["kv"], None) if case["joint"] else _split(ops["kv"], g)
    mask = _mask(ops, s, bq, bk) if case["masked"] else None
    return sa._causal_attention(ops["q"], k, v, g, bq, bk, True, ops["qs"], ops["ks"], mask,
                                shared_turn=ops["tables"], shared_scale=0.3, heads=heads)


@pytest.mark.parametrize("hb", [2, 4])
@pytest.mark.parametrize("name", sorted(CASES))
def test_a_block_of_heads_a_grid_step_equals_one_head_a_step_to_the_bit(name, hb):
    one, block = _served(name, 1), _served(name, hb)
    assert np.abs(one).mean() > 0.01 and np.isfinite(one).all()
    assert (one == block).all()


@pytest.mark.parametrize("g,want,takes", [(6, 4, 3), (6, 8, 6), (5, 4, 1), (4, 2, 2)])
def test_heads_that_a_block_does_not_divide_fall_to_the_next_smaller(g, want, takes):
    """A timing run's or a test's ``heads`` is a ceiling: the step takes the
    largest number of heads at or under it that divides the groups."""
    assert sa.heads_a_step(g, 1, 32, 32, D, D, DS, want=want) == takes
    if g == 6 and want == 4:
        case = dict(b=1, g=6, masked=False, joint=True)
        one, block = (np.asarray(_attend(case, heads).astype(jnp.float32)) for heads in (1, want))
        assert (one == block).all()
    # the rule's own choice is one of 8 / 4 / 2 or none
    assert sa.heads_a_step(g, 1, 32, 32, D, D, DS) == {6: 2, 5: 1, 4: 4}[g]


# the served shapes: (groups, heads a group, query tile, key tile, head, values, shared part,
# masked, window, turned by the kernel) -> the heads a grid step
SERVED = {
    "dsv32": ((128, 1, 512, 2176, 128, 128, 64, True, None, False), 8),
    "kimi": ((64, 1, 1088, 1088, 128, 128, 64, False, None, False), 2),
    "ling3_latent": ((32, 1, 1088, 1088, 128, 128, 64, False, None, False), 2),
    "ouro": ((16, 1, 768, 768, 128, 128, 0, False, None, True), 1),  # its 48 call sites: a start's cost
    "lfm2": ((8, 4, 1088, 1088, 64, 64, 0, False, None, False), 1),
    "granite": ((8, 4, 1088, 1088, 64, 64, 0, False, None, False), 1),
    "laguna_full": ((8, 6, 512, 1088, 128, 128, 0, False, None, True), 1),
    "laguna_windowed": ((8, 9, 256, 768, 128, 128, 0, False, 512, True), 1),  # (the key window: 512 + 256)
    "nemotron3": ((2, 16, 256, 1088, 128, 128, 0, False, None, False), 1),
    "keye": ((4, 8, 256, 2176, 128, 128, 0, True, None, False), 1),
    "minicpm_sala": ((2, 16, 128, 2048, 128, 128, 0, True, None, False), 1),
    "phi4flash_full": ((10, 2, 1088, 1088, 64, 128, 0, False, None, False), 1),
    "phi4flash_windowed": ((10, 2, 256, 768, 64, 128, 0, False, 512, False), 1),
}
# and the PARTS a grid step cuts each one's stacked group into, with the reading that chose it: the
# kernel alone on the v5e, ms, the parent's body -> the rule's parts (and the other parts read), each
# skewed by one; part after part read slower at every shape (my chip runs, PR 75, seed 7500000001,
# twenty calls a reading; the two windowed rows my chip runs, PR 77, seed 7700000002;
# `_causal_attention`'s docstring has the whole table)
PARTS = {
    "dsv32": (1, "a head alone in its group: a block of heads (PR 66)"),
    "kimi": (1, "a head alone in its group"),
    "ling3_latent": (1, "a head alone in its group"),
    "ouro": (1, "a head alone in its group"),
    "lfm2": (4, "25.35 -> 20.30 (2: 21.19); in 544 x 1,088 tiles 25.80 -> 20.49: the tile stays"),
    "granite": (4, "lfm2's shape at one sequence: 6.10 -> 4.84 (2: 5.06)"),
    "laguna_full": (3, "19.33 -> 15.54 (2: 16.60, 6: 15.86)"),
    "laguna_windowed": (3, "PR 77, one key window of 768 rows a query tile, ONE branch: 8.12 -> 3.71 (1: 3.92, 9 parts "
                           "of 0.79 MB 3.65: under the floor; the band's tiles at three parts 7.50)"),
    "nemotron3": (4, "25.25 -> 20.32 (2: 20.69, 8: 20.68, 16: 20.69)"),
    "keye": (8, "84.93 -> 65.61 (2: 75.38, 4: 68.32); in 128 x 2,176 tiles 85.65 -> 64.61: the tile stays"),
    "minicpm_sala": (8, "79.09 -> 66.51 (2: 77.13, 4: 69.40; 16: 62.91, past the bodies a start pays for)"),
    "phi4flash_full": (2, "7.61 -> 6.12"),
    "phi4flash_windowed": (1, "PR 77, the key window: 3.02 -> 1.67 (two parts of 0.79 MB 1.62: under the floor)"),
}


@pytest.mark.parametrize("name", sorted(SERVED))
def test_the_rule_gives_every_served_shape_its_heads_a_step(name):
    (g, rep, bq, bk, d, dv, ds, masked, window, turned), takes = SERVED[name]
    hb = sa.heads_a_step(g, rep, bq, bk, d, dv, ds, masked=masked, window=window, turned=turned)
    assert hb == takes and hb in (1,) + sa.BLOCK_HEADS and 16 not in sa.BLOCK_HEADS
    if rep > 1 or turned:  # a group's stacked heads already share a grid step; the kernel's own turn
        assert hb == 1
        return
    assert sa.block_vmem_bytes(hb, bq, bk, d, dv, ds, masked=masked) < sa._VMEM_LIMIT
    bodies = 1 if masked else 2
    assert hb * bodies * 4 * bq * bk <= sa.UNROLLED_SCORE_BYTES < 2 * hb * bodies * 4 * bq * bk


@pytest.mark.parametrize("name", sorted(SERVED))
def test_the_rule_gives_every_served_shape_its_parts_a_step(name):
    (g, rep, bq, bk, d, dv, ds, masked, window, turned), _ = SERVED[name]
    parts, reading = PARTS[name]
    assert sa.parts_a_step(rep, bq, bk, ds, masked=masked, window=window) == parts and reading
    if rep == 1:
        assert parts == 1
        return
    bodies = 2 if window is None and not masked else 1  # the body's branches (under a window ONE: PR 77)
    assert rep % parts == 0 and parts * bodies <= sa.PART_BODIES or parts == 1
    # the MOST parts that fill the floor within the bodies a start pays for
    finer = [cut for cut in range(parts + 1, rep + 1) if rep % cut == 0]
    assert all(cut * bodies > sa.PART_BODIES or rep // cut * bq * bk * 4 < sa.PART_SCORE_BYTES for cut in finer)
    if parts > 1:
        assert rep // parts * bq * bk * 4 >= sa.PART_SCORE_BYTES


def test_the_tiles_the_rule_is_asked_at_are_the_served_calls_own():
    """``causal_steps`` derives a call's tiles as the call does: dsv32's 512 x
    2,176 under masks of 128 x 2,176 (44 pairs a head), kimi's 1,088 x 1,088
    (36), the looped reader's 768 x 768 (6, one head a step): head tiles over
    grid steps is the heads a step."""
    assert sa.causal_steps(1, 8704, 128, 1, 128, 128, 64, block_q=1088, block_k=1088,
                           mask_tiles=(128, 2176)) == (128 * 44, 16 * 44, 16 * 44)
    assert sa.causal_steps(2, 8704, 64, 1, 128, 128, 64, block_q=1088, block_k=1088) == (
        2 * 64 * 36, 2 * 32 * 36, 2 * 32 * 36)
    assert sa.causal_steps(2, 2304, 16, 1, 128, 128, block_q=1088, block_k=1088,
                           turned=True) == (2 * 16 * 6,) * 3
    # a stacked group is ONE head tile a visit and, since PR 75, four parts (lfm2's 1,088 x 1,088)
    assert sa.causal_steps(4, 8704, 8, 4, 64, 64, block_q=1088, block_k=1088) == (
        4 * 8 * 36, 4 * 8 * 36, 4 * 4 * 8 * 36)


# ---------------------------------------------------------------------------
# a STACKED group's rows in PARTS a grid step (PR 75): heads that share their keys
# ---------------------------------------------------------------------------

def _grid(rng, shape, dtype=jnp.bfloat16):
    """Components on a grid of 1/16 within +-2 (and a rotary's tables in halves, below): a score's
    every product and float32 sum is exact, so XLA's CPU backend, which adds a product of ANOTHER
    height in another order, gives every height the same scores (the TPU's matrix unit does not
    know the height: `_chip_archive/pr75/alone.py` compared every served shape's parts there)."""
    return jnp.asarray(np.clip(np.round(rng.standard_normal(shape) * 8) / 16, -2, 2), dtype)


# every addressing a stacked group has: (groups, heads a group, head, values) and what rides beside
STACKED = {
    "heads_of_64_head_major": dict(g=2, rep=4, d=64),  # lfm2's, granite's
    "six_turned_by_the_kernel_and_gated": dict(g=2, rep=6, rotary=True, gate=True),  # laguna's full
    # laguna's windowed: the band's lower edge crosses key tile 0 for the query tiles 1 and 2
    "nine_turned_under_a_window_whose_lower_edge_crosses_a_tile": dict(
        g=1, rep=9, rotary=True, gate=True, window=24, bq=16),
    "sixteen_in_place_unturned": dict(g=1, rep=16, bq=16),  # nemotron3's
    "sixteen_under_a_mask_stacked_in_the_kernel": dict(g=1, rep=16, masked=True, b=1),  # keye's addressing
    "sixteen_under_a_block_selection_s_flags": dict(g=2, rep=16, flags=True, b=1),  # minicpm_sala's
    "two_half_heads_over_values_twice_as_wide": dict(g=2, rep=2, d=64, dv=128),  # phi4flash's full
    "two_half_heads_under_a_window": dict(g=2, rep=2, d=64, dv=128, window=24, bq=16),  # and windowed
}


def _stacked(name, cut):
    return KIT.made("stacked", (name, cut), lambda: _stacked_call(name, cut), under=None)


def _stacked_call(name, cut):
    case = {"b": 2, "d": D, "bq": 32, "window": None, **STACKED[name]}
    b, g, rep, d, bq, bk = case["b"], case["g"], case["rep"], case["d"], case["bq"], 32
    dv = case.get("dv", d)
    rng = np.random.default_rng(len(name))
    s, extra = 96 if case.get("flags") else 64, {"window": case["window"]}
    wide = jnp.float32 if case.get("rotary") else jnp.bfloat16  # the kernel turns what the products wrote
    q, k, v = _grid(rng, (b, s, g * rep * d), wide), _grid(rng, (b, s, g * d), wide), _grid(rng, (b, s, g * dv))
    if case.get("rotary"):
        tables = decoder.turn_tables(jnp.asarray(rng.uniform(0, 6, (b * s, d // 2)), jnp.float32), d)
        extra.update(turn=tuple(jnp.round(2 * table) / 2 for table in tables), q_scale=0.125)
    if case.get("gate"):
        extra["out_gate"] = jnp.asarray(rng.uniform(0, 1, (b, s, g * rep)), jnp.float32)
    if case.get("masked"):
        extra["mask"] = _mask({"rng": rng}, s, bq, bk)
    with pytest.MonkeyPatch.context() as patch:
        if case.get("flags"):  # `select_blocks`' own flags, in key tiles of 32 (four blocks of 8) over 96 keys
            from test_decoder_minicpm_sala import SMALL_SELECTION

            patch.setattr(sa, "MASK_TILE", 32)
            sel = sa.BlockSelection(**SMALL_SELECTION)
            extra.update(mask_blocks=sel, mask=sa.select_blocks(
                q[0], k[0], num_kv_heads=g, selection=sel, block_q=32)[0])
        return np.asarray(sa._causal_attention(q, k, v, g, bq, bk, True, cut=cut, **extra).astype(jnp.float32))


@pytest.mark.parametrize("name,cut", [(name, cut) for name, case in sorted(STACKED.items()) for cut in sorted(
    {next(c for c in (2, 3) if case["rep"] % c == 0), case["rep"]})])
def test_a_stacked_group_s_rows_in_parts_equal_one_stacked_product_to_the_bit(name, cut):
    """``cut`` parts of whole heads a grid step, part ``p + 1``'s score product
    written before part ``p``'s softmax, against ONE product over the stacked
    rows (``cut=1``: the body as it was), at every addressing a stacked group
    has: every row's arithmetic is what it was."""
    whole, parts = _stacked(name, 1), _stacked(name, cut)
    assert np.abs(whole).mean() > 0.01 and np.isfinite(whole).all()
    assert (whole == parts).all()


def test_parts_that_do_not_divide_a_group_fall_to_the_next_smaller_and_heads_alone_take_none():
    assert sa.parts_a_step(6, 32, 32, want=4) == 3 and sa.parts_a_step(9, 32, 32, want=2) == 1
    assert sa.parts_a_step(4, 32, 32, want=16) == 4
    assert sa.parts_a_step(1, 512, 2176, masked=True) == sa.parts_a_step(1, 512, 2176, want=2) == 1  # blocks, not parts
    assert sa.parts_a_step(8, 1088, 1088) == 4 and sa.parts_a_step(8, 1088, 1088, masked=True) == 8  # the branches
    assert sa.parts_a_step(8, 1088, 1088, window=512) == 8 and sa.parts_a_step(9, 1088, 1088, window=512) == 3
    assert sa.parts_a_step(2, 1088, 1088, DS) == sa.parts_a_step(2, 32, 32, DS, want=2) == 1  # latent attention's


def _tiny_step(nope):
    from test_decoder_kimi import PROMPT, mapping, small

    from benchmark import harness

    cfg = small(mapping(qk_nope_head_dim=nope, v_head_dim=nope, num_hidden_layers=2))
    params = decoder.init_params(cfg, jax.random.key(1), jnp.bfloat16)
    detector = {"panels": 2, "height": 16, "width": 112, "pedestal_adu": 100.0,
                "photon_adu": 35.0, "bad_pixel_fraction": 0.003}
    calib = harness.make_calibration(detector, 1)
    frames = np.random.default_rng(2).integers(90, 140, (2, 2, 16, 112)).astype(np.uint16)
    ids = jnp.arange(PROMPT, dtype=jnp.int32)
    logits, stats = jax.jit(lambda f: decoder.frame_step(params, calib, f, ids, cfg=cfg, threshold=10.0))(frames)
    assert np.isfinite(np.asarray(logits)).all()
    return cfg, 2 * 2 * 14 + PROMPT, np.asarray(stats)


def test_the_block_s_counters_reach_the_pipeline_s_through_fold_step_stats():
    """A tiny served step whose latent heads are whole lane blocks takes four
    heads a grid step: its vector has the seventh length, BLOCK_STATS last,
    every group it has not at 0."""
    from psana_ray_tpu.utils.metrics import PipelineMetrics

    cfg, s, stats = _tiny_step(128)
    assert stats.shape == (len(decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS
                               + decoder.LINEAR_STATS + decoder.AHEAD_STATS + decoder.LOOP_STATS
                               + decoder.BLOCK_STATS),) == (17,)
    metrics = PipelineMetrics()
    for _ in range(3):
        decoder.fold_step_stats(metrics, stats)
    snap = metrics.snapshot()
    pairs = len(sa._band_tiles(s, *sa.causal_tiles(s, 1, cfg.causal_q_tile, cfg.causal_kv_tile)))
    assert snap["attn_head_tiles_total"] == 3 * cfg.num_layers * 2 * cfg.num_heads * pairs
    assert snap["attn_head_tiles_total"] / snap["attn_grid_steps_total"] == 4 == cfg.num_heads
    assert snap["decoder_tokens_total"] == 3 * 2 * s and snap["loop_passes_total"] == 0
    assert metrics.counters["attn_grid_steps_total"] == snap["attn_grid_steps_total"]


def test_a_step_of_one_head_a_grid_step_keeps_the_vector_it_had():
    """Heads of 16 are no whole lane blocks: the step's vector is the six
    values it was, and neither counter exists."""
    from psana_ray_tpu.utils.metrics import PipelineMetrics

    cfg, s, stats = _tiny_step(16)
    assert stats.shape == (len(decoder.STEP_STATS),)
    assert all(tiles == steps == parts for tiles, steps, parts in (
        decoder.causal_call_steps(cfg, i, 2, s) for i in range(cfg.num_layers)))
    metrics = PipelineMetrics()
    decoder.fold_step_stats(metrics, stats)
    assert not set(decoder.BLOCK_STATS) & set(metrics.counters)
    assert dataclasses.replace(cfg, causal_q_tile=32).layer_stats == cfg.layer_stats == 4


def _stacked_step(monkeypatch, floor=None, run=True):
    """Laguna's small trunk (two full layers of two heads a key head, two windowed of three, 64
    tokens in 32 x 32 tiles, a batch of two), under the rule as it is or with its floor taken away:
    its statistics vector, run or (the shape alone) traced."""
    from test_decoder_laguna import embedded, inputs, mapping, small

    if floor is not None:
        monkeypatch.setattr(sa, "PART_SCORE_BYTES", floor)
    jax.clear_caches()  # the rule is read while a call is traced
    cfg = small(mapping())
    params = decoder.init_params(cfg, jax.random.key(3), jnp.float32)
    patches, ids = inputs(3, batch=2)
    step = jax.jit(lambda p: decoder.trunk(p, embedded(p, patches, ids), np.arange(64), cfg, 2))
    _, stats = step(params) if run else jax.eval_shape(step, params)
    jax.clear_caches()
    return cfg, stats


def test_the_part_tiles_reach_the_pipeline_s_counters_and_a_step_that_cuts_nothing_keeps_its_vector(monkeypatch):
    """At the test's size no part fills the rule's floor: the step's vector is
    the ten values it was. With the floor taken away the full layers' two
    heads a key head go in two parts: twenty values, ``BLOCK_STATS`` at what
    the calls take, ``ROWS_STATS``' places 0, ``PART_STATS`` last."""
    from psana_ray_tpu.utils.metrics import PipelineMetrics

    cfg, stats = _stacked_step(monkeypatch, run=False)
    assert stats.shape == (10,) and all(
        tiles == steps == parts for tiles, steps, parts in (
            decoder.causal_call_steps(cfg, i, 2, 64) for i in range(cfg.num_layers)))
    _, stats = _stacked_step(monkeypatch, floor=1)
    names = (decoder.STEP_STATS + decoder.SHARE_STATS + decoder.PAIR_STATS + decoder.LINEAR_STATS
             + decoder.AHEAD_STATS + decoder.LOOP_STATS + decoder.BLOCK_STATS + decoder.ROWS_STATS
             + decoder.PART_STATS)
    assert stats.shape == (len(names),) == (20,)
    metrics = PipelineMetrics()
    for _ in range(3):
        decoder.fold_step_stats(metrics, stats)
    snap = metrics.snapshot()
    # a key head's visits a layer: 3 pairs of 32 x 32 tiles; under the window ONE step a query tile
    # of 8 (`causal_tiles`: 8 rows against a key window of 24)
    assert sa.causal_tiles(64, 3, 32, 32, 16) == (8, 24)
    full, band = 2 * 2 * len(sa._band_tiles(64, *sa.causal_tiles(64, 2, 32, 32))), 2 * 2 * (64 // 8)
    assert (full, band) == (2 * 2 * 3, 2 * 2 * 8)
    assert snap["attn_head_tiles_total"] == snap["attn_grid_steps_total"] == 3 * 2 * (full + band)
    # (two parts a step of a full layer, three of a windowed one: its body is ONE branch)
    assert snap["attn_part_tiles_total"] == 3 * 2 * (2 * full + 3 * band)
    assert snap["trunk_rows_run_total"] == snap["trunk_rows_full_total"] == snap["loop_passes_total"] == 0
    assert snap["decoder_tokens_total"] == 3 * 2 * 64
