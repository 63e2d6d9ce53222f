"""Ask the chip's compiler before the chip: the main path's kernels and
device programs ALONE, at real widths, compiled for a DESCRIBED v5e:2x2
topology (``CASES``; as ``tests/test_chip_compile.py``, which holds the whole
served steps), and what is read from a compiled program's text: the SFX
step's kernels, an expert layer's router, products and way back. A program is
compiled once a worker (``chip.compiled``), whichever test reads it first.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import BF16, F32, H, PANELS, S, SHAPE, W, array_sized_moves, compiled


def _calib(dtype):
    from psana_ray_tpu.ops import fused_calibrate

    def fn(raw, ped, gain, mask):
        return fused_calibrate(
            raw, ped, gain, mask, threshold=10.0, interpret=False, out_dtype=BF16
        )

    panel = (PANELS, H, W)
    return fn, [S((8, *panel), dtype), S(panel, F32), S(panel, F32), S(panel, jnp.uint8)], 1


def _flash_fwd():
    from psana_ray_tpu.parallel import flash

    q = S((2, 4, 8448, 128), BF16)
    return (lambda q_, k, v: flash._pallas_attention_with_stats(q_, k, v, False)), [q, q, q], 1


def _flash_bwd():
    from psana_ray_tpu.parallel import flash

    q = S((2, 4, 8448, 128), BF16)
    lse = S((2, 4, 8448), F32)

    def fn(q_, k, v, o, lse_, do):
        return flash._pallas_attention_bwd(q_, k, v, o, lse_, do, False)

    return fn, [q, q, q, q, lse, q], 2  # the dkv kernel and the dq kernel


def _resnet_stage4():
    """First stage-4 bottleneck of ResNet-50 on epix10k2M at batch 32:
    22x24x1024 in, stride 2, projection — the VMEM-tight block."""
    from psana_ray_tpu.models.pallas_resnet import fused_bottleneck

    cin, f = 1024, 512

    def fn(x, w1, w2, w3, wp, *affines):
        return fused_bottleneck(
            x, w1, w2, w3, affines, wp=wp, stride=2, w_true=24, interpret=False
        )

    affines = [S((1, c), F32) for c in (f, f, f, f, 4 * f, 4 * f, 4 * f, 4 * f)]
    return fn, [
        S((32, 22, 24, cin), BF16), S((cin, f), BF16), S((9, f, f), BF16),
        S((f, 4 * f), BF16), S((cin, 4 * f), BF16), *affines,
    ], 1


def _sfx_serve_step(per_frame=True):
    """The program ``python -m psana_ray_tpu.sfx`` compiles at its
    defaults: u16 frames -> fused calibration -> PeakNetUNetTPU
    (64,128,256,512; s2d=2; frozen) -> find_peaks(128, 0.5, 2), built by
    the pipeline's own constructor; its weights and calibration constants
    are arguments of the step. The SERVED form takes the batch as 8
    per-frame ``u16[16,352,384]`` operands (each frame went to the device
    as it landed, PR 43); the whole-array form ``u16[8,16,352,384]`` is
    what ``benchmark/programs/sfx_dp.py`` lowers per shard."""
    from flax.core import meta

    from psana_ray_tpu.models import PeakNetUNetTPU
    from psana_ray_tpu.models.init import eval_shape_init
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline

    variables = meta.unbox(eval_shape_init(
        PeakNetUNetTPU(features=(64, 128, 256, 512), norm="frozen", s2d=2),
        (1, 64, 64, 1),
    ))
    panel = (PANELS, H, W)
    calib = (np.zeros(panel, np.float32), np.ones(panel, np.float32), np.ones(panel, np.uint8))
    pipe = SfxPipeline(variables, writer=None, calib=calib)
    resident = jax.tree.map(lambda a: S(a.shape, a.dtype), (pipe._variables, pipe._calib))
    b = SfxConfig.batch_size
    frames = tuple(S(panel, jnp.uint16) for _ in range(b)) if per_frame else S((b, *panel), jnp.uint16)
    pins = [functools.partial(_peaks_read_the_packed_map, rows=b * PANELS)]
    if per_frame:
        pins.append(functools.partial(_the_stack_is_one_pass_in_place, frames=b))
    # the calibration kernel and the local-maximum kernel
    return pipe._device_step, [*resident, frames], 2, *pins


KEYE_S = 34304  # 33,792 patches of an epix10k2M frame + 512 prompt tokens


def _keye_select():
    """Index scores + exact top-2048 selection at the published indexer
    sizes (16 heads of 64, one key head): a 128-query tile's whole score
    row, 67 x 128 x 512 int32, sits in VMEM."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    def fn(q, k, w):
        return sa.select_keys(q, k, w, topk=2048, block_q=128, block_k=512, interpret=False)[0]

    return fn, [S((16, KEYE_S, 64), BF16), S((KEYE_S, 64), BF16), S((KEYE_S, 16), F32)], 1


def _keye_attention():
    """Grouped-query attention under the selection's mask, 32 query heads
    on 4 key-value heads of 128, as the step makes the call since PR 68:
    the batched causal body over ONE sequence, the mask written in sixteen
    key tiles of 2,176 over keys padded to 34,816 (``mask_tile``: no wide
    tile divides 34,304), a query tile of 256 (eight stacked heads of 2,176
    float32 scores a row: 17.8 MB), q read token-major and stacked in the
    kernel. ONE Mosaic call; of the array-sized operands only k and v are
    touched on the way in (two pads of 35 MB), q and o not at all."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    def fn(q, k, v, mask):
        return sa.masked_gqa_attention(q, k, v, mask, num_kv_heads=4, block_q=256,
                                       interpret=False)

    mask_k = sa.mask_tile(KEYE_S, 512)
    assert mask_k == 2176 and sa.causal_steps(
        1, KEYE_S, 4, 8, 128, 128, block_q=256, mask_tiles=(128, mask_k)) == (4512, 4512, 8 * 4512)  # eight parts a step (PR 75)

    def pin(text):
        entry = text[text.index("ENTRY"):]
        assert len(re.findall(r"^\s*(?:ROOT )?%masked_gqa_attention[.\d]* = ", entry, re.M)) == 1
        moved = array_sized_moves(entry, KEYE_S * 4096, ("copy", "transpose", "reshape", "convert"))
        assert not moved, moved
        assert f"bf16[1,{16 * mask_k},512]" in entry  # k and v, padded to the mask's whole tiles

    kv = S((1, KEYE_S, 512), BF16)
    return fn, [S((1, KEYE_S, 4096), BF16), kv, kv, S((268, 16, 128, mask_k), jnp.int8)], 1, pin


def _keye_experts():
    """The dropless expert layer at 128 experts of 2048 x 768, top 8:
    the row gather's kernel, then three megablox grouped products over
    274,432 sorted rows."""
    from psana_ray_tpu.parallel.moe import dropless_moe

    def fn(x, router, w_gate, w_up, w_down):
        return dropless_moe(x, router, w_gate, w_up, w_down, k=8, num_experts=128,
                            interpret=False)

    up = S((128, 2048, 768), BF16)
    return fn, [S((KEYE_S, 2048), BF16), S((2048, 128), BF16), up, up,
                S((128, 768, 2048), BF16)], 4, functools.partial(
                    _rows_move_once_each_way, tokens=KEYE_S, k=8)


LFM2_B, LFM2_S = 4, 8704  # four frames of 8,448 patches (16 x 16 pixels) + 256 prompt tokens


def _lfm2_attention():
    """The maskless causal form at LFM2's heads: 32 query heads on 8
    key-value heads of 64, four sequences, a grid of the tiles at or below
    the diagonal only (scalar-prefetched tile tables)."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    def fn(q, k, v):
        return sa.masked_gqa_attention(q, k, v, num_kv_heads=8, block_q=256, block_k=512,
                                       interpret=False)

    kv = S((LFM2_B, LFM2_S, 512), BF16)
    return fn, [S((LFM2_B, LFM2_S, 2048), BF16), kv, kv], 1


def _lfm2_conv():
    """The gated short convolution on 34,816 rows of 2,048: two matrix
    products around the one-pass kernel of the gates and three taps."""
    from psana_ray_tpu.models import decoder

    cfg = decoder.DecoderConfig(hidden_size=2048, num_layers=1, num_heads=32, num_kv_heads=8,
                                head_dim=64, vocab_size=65536, rms_eps=1e-5, rope_theta=1e6,
                                layer_types=("conv",))

    def fn(p, x):
        return decoder.gated_short_conv(p, x, LFM2_B, cfg)

    p = {"norm1": S((2048,), BF16), "w_in": S((2048, 6144), BF16), "conv_w": S((2048, 3), BF16),
         "w_out": S((2048, 2048), BF16)}
    return fn, [p, S((LFM2_B * LFM2_S, 2048), BF16)], 1


def _lfm2_experts():
    """The dropless expert layer at 32 experts of 2048 x 1792, top 4 under
    the sigmoid router: the grouped product's output tile is cut to 896
    (whole, it overflows Mosaic's scoped VMEM); the rows reach expert
    order through the row gather's kernel."""
    from psana_ray_tpu.parallel.moe import dropless_moe

    def fn(x, router, bias, w_gate, w_up, w_down):
        return dropless_moe(x, router, w_gate, w_up, w_down, k=4, num_experts=32,
                            scoring="sigmoid", select_bias=bias, gate_eps=1e-6, interpret=False)

    up = S((32, 2048, 1792), BF16)
    return fn, [S((LFM2_B * LFM2_S, 2048), BF16), S((2048, 32), BF16), S((32,), F32), up, up,
                S((32, 1792, 2048), BF16)], 4, functools.partial(
                    _rows_move_once_each_way, tokens=LFM2_B * LFM2_S, k=4)


KIMI_B, KIMI_S, KIMI_D = 2, 8704, 7168  # two frames of 8,448 patches + 256 prompt tokens


def _kimi_attention():
    """Latent attention's prefill at Kimi-K2's heads: 64 heads, a score of a
    128-deep product per head plus a 64-deep product against the ONE rotary
    key (read from its ``[B, S, 64]`` array: no ``[B, S, 64 * 192]`` key
    exists), values 128 wide. Since PR 61 the rotary query comes float32 and
    UNTURNED with the two angle tables, and the kernel turns its tile once a
    query tile (its halves cut at lane 32, a scratch of ``[1088, 64]``): the
    form the cell serves."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    def fn(q, k, v, q_rope, k_rope, cos, sin):
        return sa.masked_gqa_attention(q, k, v, num_kv_heads=64, block_q=1088, block_k=1088,
                                       q_shared=q_rope, k_shared=k_rope, shared_turn=(cos, sin),
                                       shared_scale=0.1147, interpret=False)

    wide = S((KIMI_B, KIMI_S, 64 * 128), BF16)

    def no_broadcast_key(text):
        assert f"[{KIMI_B},{KIMI_S},{64 * 192}]" not in text
        assert f"[{KIMI_B},64,{KIMI_S},192]" not in text

    table = S((KIMI_B * KIMI_S, 64), F32)
    return fn, [wide, wide, wide, S((KIMI_B, KIMI_S, 64 * 64), F32),
                S((KIMI_B, KIMI_S, 64), BF16), table, table], 1, no_broadcast_key


def _kimi_experts():
    """The expert layer on a holder of 12 of 384 experts of 7168 x 2048,
    top 8 under the sigmoid router: a loop over the HELD rows in chunks, so
    no array of all 139,264 token slots' rows exists. The loop's body calls
    three Pallas kernels, the grouped products, and the layer no other:
    ``gmm_roofline_share.kimi`` divides by the time of every Pallas call
    under the scope ``moe`` (``readers/roofline_share_per_run.py``)."""
    from psana_ray_tpu.parallel.moe import dropless_moe

    def fn(x, router, bias, w_gate, w_up, w_down):
        return dropless_moe(x, router, w_gate, w_up, w_down, k=8, num_experts=384,
                            experts_held=(0, 12), scoring="sigmoid", select_bias=bias,
                            gate_eps=1e-20, gate_scale=2.827, interpret=False)

    def held_rows_only(text):
        slots = KIMI_B * KIMI_S * 8
        assert f"[{slots},{KIMI_D}]" not in text and f"[{slots},2048]" not in text
        assert "while(" in text  # the loop over the held rows' chunks
        kernels = re.findall(r'^\s*(?:ROOT )?%([\w.\-]+) = .*custom_call_target="tpu_custom_call"', text, re.M)
        assert len(kernels) == 3, kernels  # gate, up, down: the row gather is XLA's at this width

    up = S((12, KIMI_D, 2048), BF16)
    return fn, [S((KIMI_B * KIMI_S, KIMI_D), BF16), S((KIMI_D, 384), BF16), S((384,), F32), up, up,
                S((12, 2048, KIMI_D), BF16)], 3, held_rows_only


DSV32_S = 8704  # one frame of 8,448 patches + 256 prompt tokens


def _dsv32_select():
    """The selection at DeepSeek-V3.2's indexer: 64 index heads of 128 over
    one sequence of 8,704, each query's 2,048 best keys, scored and counted
    in pieces of 128 x 512 (17 a row, whose flags leave as they did) and
    WRITTEN as a mask of 128 x 2,176 (four key tiles a row: 17 of the 68
    lane blocks each, ``sparse_attention.mask_tile``'s choice for the
    attention under it): a query tile's 64 index queries (2 MB), the whole
    index key and the tile's score row stay in VMEM."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    def fn(q, k, w):
        return sa.select_keys(q, k, w, topk=2048, block_q=128, block_k=512, interpret=False)

    def mask_in_the_attention_s_tiles(text):
        assert f"s8[{DSV32_S // 128},{DSV32_S // 2176},128,2176]" in text
        assert f"s8[{DSV32_S // 128},{DSV32_S // 512},128,512]" not in text
        assert f"s32[{DSV32_S // 128},{DSV32_S // 512}]" in text  # the pieces' flags

    return fn, [S((64, DSV32_S, 128), BF16), S((DSV32_S, 128), BF16), S((DSV32_S, 64), F32)], 1, \
        mask_in_the_attention_s_tiles


def _dsv32_attention():
    """Latent attention under the selection's mask at DeepSeek-V3.2's heads:
    128 heads of 128 + 64 against the ONE rotary key, values 128 wide, one
    sequence of 8,704 in 512 x 2,176 tiles (the key tile the mask was
    written in; the largest multiple of its query tile under 1,088 that
    divides 8,704): 44 pairs of tiles at or below the diagonal a head, the
    length of the table the grid reads (153 at 512 x 512, until PR 47).
    ONE Pallas call, and the mask is read in the layout ``select_keys``
    wrote: no ``[8704, 8704]`` copy of it exists. The rotary query float32
    and unturned with its tables, as kimi's (PR 61)."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    def fn(q, k, v, q_rope, k_rope, cos, sin, mask):
        return sa.masked_gqa_attention(q, k, v, mask, num_kv_heads=128, block_q=1088, block_k=1088,
                                       q_shared=q_rope, k_shared=k_rope, shared_turn=(cos, sin),
                                       shared_scale=0.0722, interpret=False)

    wide = S((1, DSV32_S, 128 * 128), BF16)
    mask_k = sa.mask_tile(DSV32_S, 512)

    def one_call_in_wide_tiles_and_no_relaid_mask(text):
        kernels = re.findall(r'custom_call_target="tpu_custom_call"', text)
        assert len(kernels) == 1, kernels
        assert f"s8[{DSV32_S},{DSV32_S}]" not in text and f"[1,{DSV32_S},{128 * 192}]" not in text
        assert mask_k == 2176 and f"s8[{DSV32_S // 128},4,128,2176]" in text
        assert "s32[44]" in text and "s32[153]" not in text  # the (query tile, key tile) table

    table = S((DSV32_S, 64), F32)
    return fn, [wide, wide, wide, S((1, DSV32_S, 128 * 64), F32), S((1, DSV32_S, 64), BF16),
                table, table, S((DSV32_S // 128, DSV32_S // mask_k, 128, mask_k), jnp.int8)], 1, \
        one_call_in_wide_tiles_and_no_relaid_mask


def _latent_block(b, heads, takes, masked=False):
    """The latent layer's call AS IT IS SERVED since PR 66: keys and values of
    ONE array (``v`` None), the rotary query float32 with its tables, and a
    BLOCK of ``takes`` heads a grid step (``sparse_attention.heads_a_step``:
    eight under dsv32's mask in 512 x 2,176 tiles, two in kimi's and ling3's
    maskless 1,088 x 1,088) — q, the heads' ``[k | v]`` and the output wider
    blocks of the same arrays, ``m``, ``l``, ``acc`` and the turned scratch
    ``takes`` times as tall, the body's heads unrolled: a VMEM refusal or an
    unaligned slice shows here, on a CPU."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    s = DSV32_S
    mask_k = sa.mask_tile(s, 512)

    def fn(q, kv, q_rope, k_rope, cos, sin, *mask):
        return sa.masked_gqa_attention(q, kv, None, *mask, num_kv_heads=heads, block_q=1088,
                                       block_k=1088, q_shared=q_rope, k_shared=k_rope,
                                       shared_turn=(cos, sin), shared_scale=0.1147, interpret=False)

    def one_call_of_blocks(text):
        assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 1
        tiles, steps, parts = sa.causal_steps(b, s, heads, 1, 128, 128, 64, block_q=1088, block_k=1088,
                                              mask_tiles=(128, mask_k) if masked else None)
        assert tiles == takes * steps and parts == steps == b * (heads // takes) * (44 if masked else 36)

    table = S((b * s, 64), F32)
    mask = [S((s // 128, s // mask_k, 128, mask_k), jnp.int8)] if masked else []
    return fn, [S((b, s, heads * 128), BF16), S((b, s, heads * 256), BF16), S((b, s, heads * 64), F32),
                S((b, s, 64), BF16), table, table] + mask, 1, one_call_of_blocks


LING3_B, LING3_S, LING3_H = 4, 8704, 32  # four frames of 8,448 patches + 256 prompt tokens


def _one_kernel_and_no_copy_of(kernel, dims):
    """A pin on a compiled program's text: its only Mosaic kernel is ``kernel``,
    and no copy, slice, pad or fusion of an array of ``dims`` (a regex of its
    dimensions) stands in the entry computation beside it."""
    def one_kernel_and_no_copy_of_its_operands(text):
        kernels = re.findall(r'^\s*(?:ROOT )?%([\w.\-]+) = .*custom_call_target="tpu_custom_call"', text, re.M)
        assert [k.split(".")[0] for k in kernels] == [kernel], kernels
        entry = text[text.index("ENTRY"):]
        assert not re.search(rf"= \w+\[{dims}\][^ ]* (copy|slice|pad|fusion)\(", entry)

    return one_kernel_and_no_copy_of_its_operands


def _ling3_delta_rule():
    """The gated delta rule with a per-channel decay at Ling-3.0's linear
    layers' sizes: 4 x 32 head-sequences of 8,704 tokens, heads of 128, in
    chunks of 128 rows: ONE kernel (the gate, the L2 norms, the recurrence,
    the output's norm and gate), its operands the arrays their products and
    the convolution wrote, ``[q | k | v]`` read in place as three column
    blocks of one array."""
    from psana_ray_tpu.ops.delta_rule import gated_delta_rule

    def fn(qkv, f, z, beta, log_a, bias, gain):
        return gated_delta_rule(qkv, f, z, beta, log_a, bias, gain, seq_len=LING3_S,
                                heads=LING3_H, lower=-5.0, eps=1e-6, interpret=False)

    rows, wide = LING3_B * LING3_S, LING3_H * 128

    return fn, [S((rows, 3 * wide), BF16), S((rows, wide), F32), S((rows, wide), BF16),
                S((rows, LING3_H), F32), S((LING3_H,), F32), S((wide,), F32), S((128,), F32)], 1, \
        _one_kernel_and_no_copy_of("gated_delta_rule", f"{rows},{3 * wide}")


def _conv_silu(rows, wide, bias=False):
    """The 4-tap convolution and its SiLU ahead of a delta rule or a scan, at a
    cell's own shape (sequences of 8,704 rows; ``bias`` where the model has
    one): ONE Mosaic kernel (``ops/short_conv.conv_silu_taps``; XLA's loop
    fusion until PR 73) that reads the product's array where it lies and
    writes the next kernel's operand: no copy, slice, pad or fusion of
    ``[rows, wide]`` beside it."""
    from psana_ray_tpu.models.decoder import conv_silu

    return (lambda u, w, *b: conv_silu(u, w, LING3_S, *b)), [
        S((rows, wide), BF16), S((wide, 4), BF16), *[S((wide,), BF16)] * bias], 1, \
        _one_kernel_and_no_copy_of("conv_silu_taps", f"{rows},{wide}")


def _granite_ssd_scan():
    """Mamba-2's selective scan at Granite-4.0-H-Micro's sizes: one sequence of
    8,704 tokens, 64 heads of 64 over a state of 128, in chunks of 512 rows: ONE
    kernel (the step's softplus, the decays, the scan, the skip, the gate, the
    norm over all 4,096 channels), ``x``, ``B`` and ``C`` read in place as column
    blocks of the array their convolution wrote."""
    from psana_ray_tpu.ops.ssd import ssd_scan

    def fn(xbc, z, dt, dt_bias, a_log, skip, gain):
        return ssd_scan(xbc, z, dt, dt_bias, a_log, skip, gain, seq_len=8704, heads=64, state=128,
                        eps=1e-5, interpret=False)

    return fn, [S((8704, 4352), BF16), S((8704, 4096), BF16), S((8704, 64), F32), S((64,), F32),
                S((64,), F32), S((64,), F32), S((4096,), BF16)], 1, \
        _one_kernel_and_no_copy_of("ssd_scan", "8704,(4352|4096)")


def _nemotron3_ssd_scan():
    """The same scan in EIGHT groups of B and C (Nemotron-H's: 64 heads of 64
    over a state of 128, eight heads a group, ``[x | B | C]`` 6,144 wide), four
    sequences of 8,704: ONE kernel whose grid step is a whole group (``C B^T``
    the step's own, the norm over the group's 512 channels closed inside it),
    ``x`` and each group's ``B`` and ``C`` read in place as column blocks."""
    from psana_ray_tpu.ops.ssd import ssd_scan

    def fn(xbc, z, dt, dt_bias, a_log, skip, gain):
        return ssd_scan(xbc, z, dt, dt_bias, a_log, skip, gain, seq_len=8704, heads=64, state=128,
                        eps=1e-5, interpret=False)

    rows = 4 * 8704
    return fn, [S((rows, 6144), BF16), S((rows, 4096), BF16), S((rows, 64), F32), S((64,), F32),
                S((64,), F32), S((64,), F32), S((4096,), BF16)], 1, \
        _one_kernel_and_no_copy_of("ssd_scan", "34816,(6144|4096)")


def _nemotron3_attention():
    """The maskless causal form at the widest group any cell has: 32 query
    heads of 128 on 2 key-value heads, SIXTEEN a group, four sequences of
    8,704, unturned (no rotary): ``causal_tiles`` gives the stacked score tile
    ``[16 * bq, 1088]`` float32 its 20 MiB at a query tile of 256 rows (301 fit), which
    the compiler takes within VMEM."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    def fn(q, k, v):
        return sa.masked_gqa_attention(q, k, v, num_kv_heads=2, block_q=1088, block_k=1088,
                                       interpret=False)

    assert sa.causal_tiles(8704, 16, 1088, 1088) == (256, 1088)  # 301 rows fit; 256 divides 8,704
    kv = S((4, 8704, 256), BF16)
    return fn, [S((4, 8704, 4096), BF16), kv, kv], 1


def _row_gather(n, d, m):
    """The row gather's kernel driven directly at ``x [n, d]``, ``m`` rows out:
    a row read through ``rows_as_words``' view, its real word sublanes copied
    into a place of whole 8-sublane tiles, ``m`` whole tiles of 1,024 or ragged
    (laguna's 65,280 = 63.75: the rule leaves that call to XLA, whose gather
    keeps an ``x`` of 102 MiB in vector memory; the ragged tile compiles)."""
    from psana_ray_tpu.ops import row_gather

    def fn(x, idx):
        return row_gather._kernel_rows(x, idx, 1024, False)

    return fn, [S((n, d), BF16), S((m,), jnp.int32)], 2


CASES = {
    "row_gather_104448_rows_of_34816x2560": lambda: _row_gather(34816, 2560, 104448),
    "row_gather_156672_rows_of_34816x2688_an_odd_last_chunk": lambda: _row_gather(34816, 2688, 156672),
    "row_gather_65280_rows_of_17408x3072_a_ragged_last_tile": lambda: _row_gather(17408, 3072, 65280),
    "nemotron3_ssd_scan_4x8704x64x64x128_in_8_groups": _nemotron3_ssd_scan,
    "nemotron3_causal_gqa_attention_4x8704x32_on_2x128": _nemotron3_attention,
    "granite_ssd_scan_8704x64x64x128": _granite_ssd_scan,
    "ling3_gated_delta_rule_4x8704x32x128": _ling3_delta_rule,
    "ling3_conv_silu_34816x12288": lambda: _conv_silu(LING3_B * LING3_S, 3 * LING3_H * 128),
    "nemotron3_conv_silu_34816x6144_with_a_bias": lambda: _conv_silu(34816, 6144, bias=True),
    "granite_conv_silu_8704x4352_with_a_bias": lambda: _conv_silu(8704, 4352, bias=True),
    "olmo_hybrid_conv_silu_8704x3840_q_and_k_a_head_at_whole_lane_tiles": lambda: _conv_silu(8704, 3840),
    "olmo_hybrid_conv_silu_8704x5760_v": lambda: _conv_silu(8704, 5760),
    "dsv32_select_keys_8704x64x128": _dsv32_select,
    "dsv32_masked_latent_attention_1x8704x128x192": _dsv32_attention,
    "kimi_latent_attention_2x8704x64x192": _kimi_attention,
    "dsv32_latent_attention_a_block_of_8_heads_a_step": lambda: _latent_block(1, 128, 8, masked=True),
    "kimi_latent_attention_a_block_of_2_heads_a_step": lambda: _latent_block(KIMI_B, 64, 2),
    "ling3_latent_attention_a_block_of_2_heads_a_step": lambda: _latent_block(LING3_B, LING3_H, 2),
    "kimi_held_experts_17408x8_12_of_384": _kimi_experts,
    "lfm2_causal_gqa_attention_4x8704x64": _lfm2_attention,
    "lfm2_gated_short_conv_34816": _lfm2_conv,
    "lfm2_dropless_experts_34816x4": _lfm2_experts,
    "keye_select_keys_34304": _keye_select,
    "keye_masked_gqa_attention_34304": _keye_attention,
    "keye_dropless_experts_34304x8": _keye_experts,
    "calib_epix10k2M_u16": lambda: _calib(jnp.uint16),
    "calib_epix10k2M_f32": lambda: _calib(F32),
    "sfx_serve_step_cli_defaults": _sfx_serve_step,
    "sfx_serve_step_whole_array": lambda: _sfx_serve_step(per_frame=False),
    "resnet50_stage4_bottleneck": _resnet_stage4,
    "flash_fwd_2x4x8448x128": _flash_fwd,
    "flash_bwd_2x4x8448x128": _flash_bwd,
}


def _rows_move_once_each_way(text, tokens, k):
    """The dropless expert layer as compiled (PR 39): no second pass over
    the gathered ``[T*k, 2048]`` rows that fills where an index is out of
    range (``jnp.take``'s default mode), no ``[T, k, 2048]`` array (at k 4
    a relayout into half-filled tiles), and the three grouped products
    under the name their roofline share is read by."""
    entry = text[text.index("ENTRY"):]
    filled = [line for line in entry.splitlines()
              if f"[{tokens * k},2048]" in line.split(" fusion(")[0] and "select_n" in line]
    assert not filled, filled
    assert f"[{tokens},{k},2048]" not in entry
    assert len(re.findall(r"^\s*(?:ROOT )?%gmm[.\d]* = ", entry, re.M)) == 3
    assert len(re.findall(r"^\s*(?:ROOT )?%row_gather[.\d]* = ", entry, re.M)) == 1


def _the_stack_is_one_pass_in_place(text, frames):
    """What the per-frame operands cost the served step (PR 43), as
    compiled: XLA does NOT fuse the stack into the convert ahead of the
    calibration kernel. It writes the ``u16[B,16,352,384]`` batch by one
    in-place ``dynamic-update-slice`` fusion a frame (each moves one
    frame's 4.33 MB in and out: one pass over the batch in all), and ONE
    convert then reads the whole batch, as it reads the whole-array
    form's operand; no ``concatenate`` or ``copy`` of the batch stands in
    the entry computation, and the calibration kernel is still one call.
    A convert written per frame, ahead of the stack, is hoisted behind it
    again and compiles to this same text."""
    entry = text[text.index("ENTRY"):]
    batch = rf"u16\[{frames},{PANELS},{H},{W}\]"
    stacked = re.findall(rf"^\s*(%[\w.\-]+) = {batch}\S* (\S+?)\(", entry, re.M)
    assert len(stacked) == frames and {op for _, op in stacked} == {"fusion"}, stacked
    assert all("dynamic-update-slice" in name for name, _ in stacked), stacked
    rows = frames * PANELS
    whole = rf"(?:u16|f32)\[(?:{frames},{PANELS}|{rows}),{H},{W}\]"
    passes = re.findall(rf"^\s*(?:ROOT )?(%[\w.\-]+) = {whole}\S* (concatenate|copy|convert)\(", entry, re.M)
    assert [op for _, op in passes] == ["convert"], passes
    assert len(re.findall(r"^\s*(?:ROOT )?%fused_calibrate[.\d]* = ", entry, re.M)) == 1


_BYTES = {"f32": 4, "s32": 4, "bf16": 2, "u16": 2, "pred": 1, "u8": 1, "s8": 1}


def _peaks_read_the_packed_map(text, rows):
    """What ``find_peaks_ms`` rests on (PR 41), in the SFX step as compiled
    for ``rows`` panel rows: the head's probabilities go from the fusion
    that writes them into ONE kernel and come out as 15,104 candidates a
    row. No gather cuts phases (a strided ``jnp`` index is one: nine
    gather fusions before), no float32 map at full resolution exists
    under ``peaknet`` or ``find_peaks``, at most one map-sized copy, pad or slice stands
    under ``find_peaks`` (five before), and ``top_k`` reads one candidate
    per block."""
    entry = text[text.index("ENTRY"):]
    assert not re.findall(r'op_name="[^"]*/nms/[^"]*gather', text)
    def shapes(hlo):  # (dtype, dims) of every array named in a piece of HLO text
        return [(t, [int(x) for x in d.split(",") if x]) for t, d in SHAPE.findall(hlo)]

    for line in text.splitlines():
        if "/peaknet/" not in line and "/find_peaks/" not in line:
            continue  # the calibration kernel reads its frames as float32
        for dtype, dims in shapes(line):
            full = dtype == "f32" and H in dims and W in dims and np.prod(dims) >= rows * H * W
            assert not full, line[:200]
    the_map = rows * H * W * 4
    passes = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) (copy|pad|slice|fusion|transpose)\(", line)
        if m and "/find_peaks/" in line:
            size = sum(_BYTES[t] * int(np.prod(dims)) for t, dims in shapes(m.group(2)))
            if size >= 0.7 * the_map:
                passes.append(m.group(1))
    assert len(passes) <= 1, passes
    assert len(re.findall(r"^\s*(?:ROOT )?%peak_nms[.\d]* = ", entry, re.M)) == 1
    top_k = [line for line in entry.splitlines() if 'custom_call_target="TopK"' in line]
    assert len(top_k) == 1
    operand = re.search(r"custom-call\((%[\w.\-]+)\)", top_k[0]).group(1)
    assert re.search(rf"^\s*{re.escape(operand)} = f32\[{rows},15104\]", entry, re.M), operand


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_described_v5e(case, one_chip, monkeypatch):
    text, memory, (min_mosaic, *pins) = compiled(CASES[case], one_chip, monkeypatch)
    assert text.count("tpu_custom_call") >= min_mosaic
    for pin in pins:
        pin(text)
    assert sum(memory) < 16e9  # one v5e chip: 16 GB of HBM for arguments, outputs and temporaries


def test_the_served_peaknet_is_the_plain_flax_model(one_chip, monkeypatch):
    """ROADMAP S1 (4), as compiled for the described v5e: the SFX step's
    only Mosaic kernels are the calibration kernel and ``peak_nms``, one
    call each, and none stands under the scope ``peaknet`` — the U-Net is
    XLA's own convolutions. ``test_compiles_for_described_v5e`` counts
    kernels from below only (``>= min_mosaic``)."""
    text, *_ = compiled(_sfx_serve_step, one_chip, monkeypatch)  # CASES' sfx_serve_step_cli_defaults: compiled once
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = sorted(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    assert names == ["fused_calibrate", "peak_nms"], names
    assert not [line[:200] for line in calls if "/peaknet/" in line]
    assert any("/peaknet/" in line and " convolution(" in line for line in text.splitlines())


def _ling3_experts():
    """The expert layer on a holder of 128 of 512 experts of 2560 x 768,
    top 8 of the 4 best of 8 groups under the sigmoid router: 1.5 even
    shares of the slots in one pass ahead of the held rows' loop."""
    from psana_ray_tpu.parallel.moe import dropless_moe

    def fn(x, router, bias, w_gate, w_up, w_down):
        return dropless_moe(x, router, w_gate, w_up, w_down, k=8, num_experts=512,
                            experts_held=(0, 128), scoring="sigmoid", select_bias=bias,
                            gate_eps=1e-20, gate_scale=2.5, groups=8, groups_kept=4,
                            interpret=False)

    up = S((128, 2560, 768), BF16)
    return fn, [S((LING3_B * LING3_S, 2560), BF16), S((2560, 512), BF16), S((512,), F32), up, up,
                S((128, 768, 2560), BF16)]


@pytest.mark.parametrize("layer", ["ling3", "lfm2"])
def test_the_router_indexes_nothing_by_data(layer, one_chip, monkeypatch):
    """ONE expert layer at ling3's and at lfm2's published sizes, as
    compiled (PR 51): under the scope ``moe_route`` there is no ``scatter``
    (``bincount``'s: 2.4 ms a layer at ling3's 278,528 slots), no ``gather``
    (``take_along_axis``'s: 2.9 ms) and no sort but the slots' own
    ``argsort``s over ``T * k`` (``lax.top_k`` was a full sort of ``[T, 512]``:
    3.3 ms, and two more for the group limit); no array over slots AND
    experts (``[T, k, E]``, ``[T * k, E]``) exists, inside a fusion or out; and on a holder of a share the
    products and the way back stand under ``moe_experts`` alone, where until
    PR 51 the whole layer stood under ``moe_route``."""
    case, tokens, k, experts = {"ling3": (_ling3_experts, LING3_B * LING3_S, 8, 512),
                                "lfm2": (_lfm2_experts, LFM2_B * LFM2_S, 4, 32)}[layer]
    text, *_ = compiled(case, one_chip, monkeypatch)
    routed = [line for line in text.splitlines() if "/moe_route/" in line]
    assert len(routed) > 20  # the scope reaches the compiled text
    by_data = [line.strip()[:160] for line in routed
               if re.search(r" (scatter|gather|custom-call)\(", line) or "TopK" in line]
    assert not by_data, by_data
    sorts = [line for line in routed if re.search(r" sort\(", line)]
    assert 1 <= len(sorts) <= 2, sorts
    for line in sorts:  # each an argsort of the T * k slots: keys and their places, one axis
        dims = {d for _, d in SHAPE.findall(line.split(" sort(")[0])}
        assert dims == {str(tokens * k)}, line[:200]
    spread = [sorted(dims) for dims in ((tokens, k, experts), (tokens * k, experts), (tokens, k * experts))]
    sized = [line.strip()[:160] for line in text.splitlines()
             for _, dims in SHAPE.findall(line.split(", metadata=")[0])
             if sorted(int(x) for x in dims.split(",") if x) in spread]
    assert not sized, sized[:3]
    assert not re.findall(r'op_name="[^"]*moe_route/[^"]*moe_experts', text)
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all("/moe_experts/" in line and "/moe_route/" not in line for line in kernels)


def test_a_share_holder_s_way_back_moves_no_row_of_every_token(one_chip, monkeypatch):
    """ONE expert layer at ling3's sizes, as compiled (PR 52): under
    ``moe_experts`` XLA gathers NO rows of 2,560 outside the loop: none
    writes ``[T, 2560]`` (the way back was eight of them, 1.6 ms each, three
    slots of four fetched to be thrown away), and since PR 70 the ``[ahead,
    2560]`` of the way out leave by the row gather's kernel over a words view
    of ``x`` (the pass moves 3 rows a row of ``x``: ``row_gather.tile_rows``).
    The way back is two kernels, and what ``sum_counted_rows`` writes tile
    by tile reaches ``[T, 2560]`` float32 by a bitcast, no pass."""
    import collections

    from psana_ray_tpu.parallel import moe

    text, *_ = compiled(_ling3_experts, one_chip, monkeypatch)
    tokens = LING3_B * LING3_S
    ahead = moe.rows_ahead(tokens * 8, 128, 512)
    rows_gathered = [int(SHAPE.search(line).group(2).split(",")[0]) for line in text.splitlines()
                     if " gather(" in line and "/moe_experts/" in line and "/while/" not in line
                     and SHAPE.search(line).group(2).endswith(",2560")]
    assert rows_gathered == [] and ahead == 104448, rows_gathered
    moved = [line.strip()[:200] for line in text.splitlines()
             if re.match(rf"\s*(?:ROOT )?%row_gather[.\d]* = bf16\[{ahead},2560\]", line)]
    assert len(moved) == 1 and re.search(r", %rows_as_words[.\d]*\), custom_call_target", moved[0]), moved
    kernels = collections.Counter(
        re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line and "/while/" not in line)
    assert kernels == {"gmm": 3, "rows_as_words": 2, "row_gather": 1, "sum_counted_rows": 1}, kernels
    entry = text[text.index("ENTRY"):]
    written = [line.strip()[:120] for line in entry.splitlines()
               if re.match(rf"\s*(?:ROOT )?%[\w.\-]+ = f32\[{tokens},2560\]", line)
               and "/sum_counted_rows/" in line.replace("jit(sum_counted_rows)", "/sum_counted_rows/")]
    assert written and all(" bitcast(" in line for line in written), written


def _nemotron3_experts():
    """The UNGATED expert layer on a holder of 64 of 128 experts of 2688 x
    1856 (14.5 lane tiles: the up weights read transposed), top 6 under the
    sigmoid router: every held row in the pass ahead on an even load."""
    from psana_ray_tpu.parallel.moe import dropless_moe

    def fn(x, router, bias, w_up, w_down):
        return dropless_moe(x, router, None, w_up, w_down, k=6, num_experts=128, experts_held=(0, 64),
                            scoring="sigmoid", select_bias=bias, gate_eps=1e-20, gate_scale=2.5,
                            interpret=False)

    return fn, [S((LING3_B * LING3_S, 2688), BF16), S((2688, 128), BF16), S((128,), F32),
                S((64, 2688, 1856), BF16), S((64, 1856, 2688), BF16)]


@pytest.mark.parametrize("layer", ["lfm2", "ling3", "nemotron3"])
def test_nothing_but_the_kernels_stands_between_an_expert_layer_s_up_and_down_products(
        layer, one_chip, monkeypatch):
    """ONE expert layer at lfm2's (all held), ling3's (the pass ahead of the
    loop) and nemotron3's (ungated) published sizes, as compiled (PR 65): the
    activation is the up product's last step (``moe.gmm``), so the only
    float32 array of ``[rows, F]`` an expert layer has is the GATE's product,
    written by one grouped product and read by the next, the loop's turn of
    2,048 rows alike — no fusion, copy or convert writes or reads one (in
    the whole text: a fusion's own computation names its parameters' types),
    and the ungated layer has none. Until PR 65 XLA ran ``silu(gate) * up`` and
    the rounding as a fusion of its own over two such arrays (lfm2: 2 x 998
    MB read, 250 MB written, 3.5 ms a layer under products the MXU bounds),
    ``relu(up)^2`` over one."""
    from psana_ray_tpu.parallel import moe

    case, rows, width, gated = {
        "lfm2": (_lfm2_experts, LFM2_B * LFM2_S * 4, 1792, True),
        "ling3": (_ling3_experts, moe.rows_ahead(LING3_B * LING3_S * 8, 128, 512), 768, True),
        "nemotron3": (_nemotron3_experts, moe.rows_ahead(LING3_B * LING3_S * 6, 64, 128), 1856, False)}[layer]
    text, *_ = compiled(case, one_chip, monkeypatch)
    def named(kind):  # an array's type stands on its writer's line and, as a kernel's operand layout, its reader's
        lines = [line.split(", metadata=")[0].strip() for line in text.splitlines() if kind in line]
        assert all('custom_call_target="tpu_custom_call"' in line for line in lines), lines[:3]
        return [bool(re.match(rf"(?:ROOT )?%[\w.\-]+ = {re.escape(kind)}", line)) for line in lines]

    for size in [rows] if layer == "lfm2" else [rows, moe.HELD_CHUNK]:
        # the gate's product: one kernel writes it, the next reads it, and nothing else names it
        assert sorted(named(f"f32[{size},{width}]")) == ([False, True] if gated else [])
        # the hidden rows leave the up product rounded, once, for the down product alone
        assert sorted(named(f"bf16[{size},{width}]")) == [False, True]
    products = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line
                and "/while/" not in line and re.match(r"\s*(?:ROOT )?%gmm", line)]
    assert len(products) == (3 if gated else 2)  # outside the loop, by the name the roofline shares read


