"""A BLOCK of heads a grid step of the batched causal kernel (PR 66): where
a head is alone in its group ``_causal_kernel`` takes ``hb`` heads a step,
head ``h + 1``'s score product written before head ``h``'s softmax. Every
head's own arithmetic is the single-head form's, so the outputs are equal
TO THE BIT (interpret mode, small shapes); the rule that picks ``hb`` is a
table of the served shapes; its two counters reach the pipeline's."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@functools.lru_cache(maxsize=None)
def _served(name, heads):
    return np.asarray(_attend(CASES[name], heads).astype(jnp.float32))


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


# the nine served shapes: (groups, heads a group, query tile, key tile, head, values, shared part,
# masked, window, turned by the kernel) -> the heads a grid step
SERVED = {
    "dsv32": ((128, 1, 512, 2176, 128, 128, 64, True, None, False), 8),
    "kimi": ((64, 1, 1088, 1088, 128, 128, 64, False, None, False), 2),
    "ling3_latent": ((32, 1, 1088, 1088, 128, 128, 64, False, None, False), 2),
    "ouro": ((16, 1, 768, 768, 128, 128, 0, False, None, True), 1),  # its 48 call sites: a start's cost
    "lfm2": ((8, 4, 1088, 1088, 64, 64, 0, False, None, False), 1),
    "granite": ((8, 4, 1088, 1088, 64, 64, 0, False, None, False), 1),
    "laguna_full": ((8, 6, 512, 1088, 128, 128, 0, False, None, True), 1),
    "laguna_windowed": ((8, 9, 256, 512, 128, 128, 0, False, 512, True), 1),
    "nemotron3": ((2, 16, 256, 1088, 128, 128, 0, False, None, False), 1),
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


def test_the_tiles_the_rule_is_asked_at_are_the_served_calls_own():
    """``causal_steps`` derives a call's tiles as the call does: dsv32's 512 x
    2,176 under masks of 128 x 2,176 (44 pairs a head), kimi's 1,088 x 1,088
    (36), the looped reader's 768 x 768 (6, one head a step): head tiles over
    grid steps is the heads a step."""
    assert sa.causal_steps(1, 8704, 128, 1, 128, 128, 64, block_q=1088, block_k=1088,
                           mask_tiles=(128, 2176)) == (128 * 44, 16 * 44)
    assert sa.causal_steps(2, 8704, 64, 1, 128, 128, 64, block_q=1088, block_k=1088) == (2 * 64 * 36, 2 * 32 * 36)
    assert sa.causal_steps(2, 2304, 16, 1, 128, 128, block_q=1088, block_k=1088,
                           turned=True) == (2 * 16 * 6,) * 2
    assert sa.causal_steps(4, 8704, 8, 4, 64, 64, block_q=1088, block_k=1088) == (4 * 8 * 36,) * 2


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
    assert all(tiles == steps for tiles, steps in (
        decoder.causal_call_steps(cfg, i, 2, s) for i in range(cfg.num_layers)))
    metrics = PipelineMetrics()
    decoder.fold_step_stats(metrics, stats)
    assert not set(decoder.BLOCK_STATS) & set(metrics.counters)
    assert dataclasses.replace(cfg, causal_q_tile=32).layer_stats == cfg.layer_stats == 4
