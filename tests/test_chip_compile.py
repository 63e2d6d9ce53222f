"""Ask the chip's compiler before the chip: the main path's device
programs, at real widths, compiled for a DESCRIBED v5e:2x2 topology (no
chip attached — jax.experimental.topologies). Interpret-mode tests cannot
see what Mosaic refuses (unaligned slices, VMEM overflow) nor what does
not fit HBM; these do, at no chip time. Nothing runs, so they say nothing
about results or speed — ``chip_smoke.py`` on the chip does that.

Also here: the compile-cache placement rule, the smoke's refusal to pass
off the chip, and the producer staying JAX-free (the one-process-per-chip
rule's cheap guards).
"""

import functools
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PANELS, H, W = 16, 352, 384  # epix10k2M
BF16, F32 = jnp.bfloat16, jnp.float32
S = jax.ShapeDtypeStruct  # case arguments are shapes; the test adds the device


@pytest.fixture
def cache_setting():
    """Snapshot/restore the process-wide persistent-cache settings."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
        "jax_include_full_tracebacks_in_locations",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


@pytest.fixture
def one_chip(cache_setting):
    """Sharding on one described v5e chip; persistent cache OFF around the
    compile (an entry written for a described device cannot be read back
    without one — the next run would warn and recompile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # The pins below name kernels as a fresh process names them
    # (``%row_gather``, ``%peak_nms``: the ``pallas_call``'s own name, which
    # locations carry only with full tracebacks, JAX's default). An
    # earlier test of this xdist worker that ran a CLI's ``main`` in-process
    # (``tests/test_sfx.py``) has been through ``configure_compile_cache``,
    # which turns them off for good: the kernel is then named after the
    # function around it (``%gather_rows``), as on the chip. Which files
    # share a worker changes with every test added, so state it here;
    # ``cache_setting`` puts back what it found. The traces go too: a
    # kernel's wrapper asks ``default_backend()`` while it is TRACED and
    # the trace is cached by shapes alone, so one made on the CPU would be
    # lowered here in the kernel's place, and one made here would reach a
    # later CPU test.
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()


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
        moved = _array_sized_moves(entry, KEYE_S * 4096, ("copy", "transpose", "reshape", "convert"))
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


_SHAPE = re.compile(r"\b(f32|s32|bf16|u16|pred|u8|s8)\[([\d,]*)\]")
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
        return [(t, [int(x) for x in d.split(",") if x]) for t, d in _SHAPE.findall(hlo)]

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


def _compile_case(fn, arg_shapes, one_chip, monkeypatch):
    """One of ``CASES``' programs compiled for the described chip."""
    # code that asks default_backend() would take its CPU (interpret)
    # branch under a described topology; steer it here, not in the program
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), arg_shapes)
    return jax.jit(fn).lower(*args).compile()  # raises what the chip's compiler would


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_described_v5e(case, one_chip, monkeypatch):
    fn, arg_shapes, min_mosaic, *pins = CASES[case]()
    compiled = _compile_case(fn, arg_shapes, one_chip, monkeypatch)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= min_mosaic
    for pin in pins:
        pin(text)
    mem = compiled.memory_analysis()
    # one v5e chip: 16 GB of HBM for arguments, outputs and temporaries
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    ) < 16e9


def test_the_served_peaknet_is_the_plain_flax_model(one_chip, monkeypatch):
    """ROADMAP S1 (4), as compiled for the described v5e: the SFX step's
    only Mosaic kernels are the calibration kernel and ``peak_nms``, one
    call each, and none stands under the scope ``peaknet`` — the U-Net is
    XLA's own convolutions. ``test_compiles_for_described_v5e`` counts
    kernels from below only (``>= min_mosaic``)."""
    fn, arg_shapes, *_ = _sfx_serve_step()
    text = _compile_case(fn, arg_shapes, one_chip, monkeypatch).as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = sorted(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    assert names == ["fused_calibrate", "peak_nms"], names
    assert not [line[:200] for line in calls if "/peaknet/" in line]
    assert any("/peaknet/" in line and " convolution(" in line for line in text.splitlines())


# sha256 of the served step's lowered text (StableHLO), the kernels' serialized bodies cut out
# (they carry file paths and line numbers): what `decoder.frame_step` traces to for the two
# decoders the benchmark had before PR 42 (and, since PR 46, for PR 42's own). Pinned on PR 41's tree first (PR 42's trunk lowered
# to it), then again in PR 42, knowingly: with every share sent to `_held_rows_moe`, the
# all-held path lost what it did for a share (the held mask and its `where` on the gates, the
# grouped product's group offset of 0, the slice of the per-expert counts); the device times
# did not move (PERF.md section 6). A PR that means to change
# one of these programs re-pins it, knowingly: an equal text is an equal key in the compile
# cache, and a decoder cell's warm `setup_s` (bound 0.1) pays seconds for anything new to trace
PINNED_STEPS = {
    # all four re-pinned in PR 51, knowingly: every decoder's router takes its k experts by k dense
    # passes over [T, E], reads their gates by a compare and a row sum and counts each held
    # expert's slots by column sums of a compare (no top_k, take_along_axis or bincount: ids, raw
    # affinities and counts bit for bit what they were; the renormalising sum over k written out
    # in the order the TPU's lane reduce gave it), and a share-holder's products left moe_route.
    # Before that: dsv32's pinned on PR 49's tree in PR 50; kimi's on PR 43's tree in PR 46 and
    # again in PR 48 (token-major operands for the latent kernel)
    # (the three steps with a latent layer — dsv32's, kimi's and ling3's — re-pinned in PR 61,
    # knowingly: `_latent_projections` hands the rotary query on float32 and unturned, as its
    # product wrote it (ONE three-dimensional product `[T, rq] x [rq, H, 64]`: written as a
    # two-dimensional one and reshaped, six of kimi's seven layers compiled to a column-major
    # product and a copy of it), `latent_attention` makes the step's two angle tables, and the
    # latent kernel takes them as two more operands with a fourth scratch; keye's, lfm2's, laguna's,
    # granite's and the looped reader's were hashed before and after and did not move: a call
    # without the tables traces the kernel it traced, `tests/test_decoder_kimi.py` holds its body)
    # (the seven steps with routed experts — these four, ling3's, laguna's and nemotron3's — re-pinned
    # in PR 65, knowingly: an expert layer's up product is `moe.gmm`, a grouped product of the repo's
    # own (megablox's grid, metadata and store mask) whose last contraction step stores
    # `silu(gate) * acc` (`relu(acc)^2` where the experts have no gate) rounded once: it takes the
    # gate's float32 product as one more operand and writes the hidden rows in the activations' type,
    # and the float32 up product, the `logistic`, the two multiplies and the `convert` that stood
    # between the products in the all-held path, the held rows' loop and the pass ahead are gone
    # from the step's own text; granite's and the looped reader's, which run no grouped product,
    # were hashed before and after and did not move)
    # (the three steps with a latent layer — dsv32's, kimi's and ling3's — re-pinned in PR 66,
    # knowingly: a grid step of `_causal_kernel` takes a BLOCK of heads there
    # (`sparse_attention.heads_a_step`: eight under dsv32's mask, two in kimi's and ling3's latent
    # calls), so the call's grid is `(B, G / hb, pairs)`, keys and values of one array ride in as
    # ONE operand where they were two blocks of it, and the step's statistics vector ends in
    # `BLOCK_STATS` (seventeen values, the last places ONE constant of the shapes); keye's, lfm2's,
    # laguna's, granite's, nemotron3's and the looped reader's were hashed before and after and
    # did not move: heads that share their keys, heads of 64, keye's `_attn_kernel` (gone since PR 68) and a kernel that
    # turns q and k itself take one group a step as they did, and at one head a step the kernel's
    # body is the jaxpr it was (`tests/test_decoder_kimi.py -k traces_the_kernel`))
    # (the five steps whose causal calls hold a STACKED group — keye's, lfm2's, laguna's, granite's and
    # nemotron3's — re-pinned in PR 75, knowingly: a grid step cuts the group's rows into parts
    # (`sparse_attention.parts_a_step`: eight under keye's mask, four at lfm2's and granite's heads of
    # 64 and in nemotron3's sixteen a group, three in laguna's full calls), part p + 1's
    # score product written before part p's softmax; the kernel's body rides in the blanked
    # `backend_config`, so what moved in the step's own text is the statistics vector, which ends in
    # `PART_STATS` (twenty values: `attn_part_tiles_total` last, `BLOCK_STATS` at what the calls take,
    # ONE constant of the shapes). dsv32's, kimi's, ling3's, the looped reader's and olmo_hybrid's were
    # hashed before and after and did NOT move: `rep == 1` in every causal call of theirs)
    "deepseek_v32_prefill_epix10k2m": "4459560881f4932af4843903dfaa127c21ecbadaa451ce1890d074e6fb249eac",
    "kimi_k2_prefill_epix10k2m": "aabd919baf99e48f437abf546ab498c3b2c100d1f38b24cf58eff093ba9c462a",
    # (keye's ALONE re-pinned in PR 68, knowingly: its four selection-attention calls leave
    # `_attn_kernel` (deleted: ROADMAP D12) for the batched causal body under the mask — `[1, S, .]`
    # operands, the mask `[268, 16, 128, 2176]` in a key tile that does not divide 34,304
    # (`mask_tile`), k and v padded to 34,816 rows, a table of 1,128 causal pairs as scalar prefetch,
    # q as ONE token-major block a group with a fourth scratch; the nine others were hashed before
    # and after and did not move: at 8,704 tokens every rule gives what it gave, and the maskless
    # cells take none of the changed branches)
    "keye_vl2_prefill_epix10k2m": "874b8b6514802f5e70b9062a37c41ac16b82f4c8b72feb759a57b56574f6084a",
    "lfm2_8b_a1b_prefill_epix10k2m": "b2f6c0b7cc4c217d608bdd9f3f4d7640728b046b9cc6bb7cd07e77c9307728c3",
    # pinned in PR 56, both hashed on PR 55's tree first and NEITHER moved by it: laguna's runs
    # nothing of `ops/delta_rule.py`; ling3's does, and PR 56 rewrote that kernel's body (the heads
    # of a grid step side by side), but a Mosaic kernel's body rides in its call's `backend_config`,
    # which this test blanks (it carries file names and line numbers): the pin holds what is
    # AROUND a kernel (its operands, their shapes and types, its grid's result) and no kernel's
    # body. A kernel's own cache entry follows its body and its file's path
    # (ling3's again in PR 61: its one latent layer, above)
    # (ling3's and nemotron3's re-pinned in PR 70, knowingly: once an expert layer, in the pass ahead of
    # the held rows' loop, a `rows_as_words` call (`x [34816, 2560 | 2688]` -> `u32[34816, 12, 128]`)
    # and a `row_gather` call (the slots' tokens, twice, and that view -> `[104448 | 156672, .]`)
    # stand where a `gather` of `x` stood (a `pad` of nothing before the view, as on the way back): `row_gather.tile_rows` takes bfloat16 rows of any whole
    # number of 128-column chunks where the call moves at least as many rows as `x` holds and `x` is
    # past what XLA's own gather keeps in vector memory; the loop's turn (2,048 rows) keeps its
    # `gather`. The eight others were hashed before and after and did not move: laguna's `x
    # [17408, 3072]` is 102 MiB and stays XLA's by that rule, kimi's and dsv32's turns move fewer
    # rows than `x` holds, lfm2's and keye's 2,048 columns take the kernel operand for operand as
    # they did, granite's, ouro's and olmo_hybrid's gather no rows)
    # (the FOUR steps that run `decoder.conv_silu` — ling3's, granite's, nemotron3's and olmo_hybrid's —
    # re-pinned in PR 73, knowingly: ahead of every delta rule and every scan a `conv_silu_taps` call
    # (`ops/short_conv.py`: the array, the taps transposed to float32 `[taps, C]` and, in granite's
    # and nemotron3's, the bias as float32 `[1, C]` -> the array's shape and type) stands where the
    # `pad`, the four `slice`s and `convert`s, the multiplies, the adds and the `logistic` of XLA's
    # loop fusion stood: six calls in ling3's step over `[34816, 12288]`, 36 in granite's over
    # `[8704, 4352]`, six in nemotron3's over `[34816, 6144]`, 36 in olmo_hybrid's (q's and k's over
    # `[8704, 3840]` with the taps laid a head at whole lane tiles as before, v's over `[8704, 5760]`);
    # the kernel's output equals the fusion's to the bit at all five shapes on the chip (PERF.md
    # section 5). The six others — keye's, lfm2's, kimi's, dsv32's, laguna's and the looped reader's —
    # were hashed before and after and did not move: none calls `conv_silu`, and lfm2's
    # `gated_conv_taps` call is operand for operand what it was (its body, which now reaches the
    # rows before a row through the function `conv_silu_taps` shares, rides in `backend_config`))
    "ling3_flash_prefill_epix10k2m": "f9b85f1ad1bb9f9a6a99f9637b189a7e8cd137e7bc2380ebe3f580e060ab16d0",
    # laguna's re-pinned in PR 58, knowingly: its nine attention calls take k, v and the query
    # tile's gate where their products wrote them, q as `[G, H/G, B*S, d]` (a layout of the
    # rotary's fusion) and write o token-major `[B, 1, S, H*128]`, already gated, for `W_o` to
    # read; the six others were hashed before and after and did not move (heads alone in their
    # groups were in place already, heads of 64 stay head-major)
    # (again in PR 63, knowingly, with the looped reader's: where a layer has a rotary, heads of whole
    # lane blocks and no selection, `_projections` hands q and k on float32 and unturned as W_q's and
    # W_k's products wrote them, `_attention` makes the layer type's two tables `[T, 128]`, and the
    # batched kernel takes them as four more operands (the query tile's rows and the key tile's) with
    # a fourth scratch, q as ONE token-major block `[B, 1, S, H*128]`; the six others were hashed
    # before and after and did not move: the rule is a branch taken in Python, `angles is None`,
    # heads of 64 and a selection on its other side, and the latent cells' path is not touched)
    "laguna_s21_prefill_epix10k2m": "bf2e9f3b3e8d3f54776ab762bae36e60139cc66bbf43473adae59659828f158b",
    # pinned in PR 57, which brought it: the six above were hashed on PR 56's tree first and none
    # moved, though every one of them now traces `_projections`, `embed`, `logits_of` and `trunk`
    # through the new fields' branches (taken in Python, before anything is traced)
    "granite4_h_micro_prefill_epix10k2m": "c505aaa9ec91d92190243b4a5e593f2cd6dc452cfe299023e9d682c9bab1415c",
    # pinned in PR 60, which brought it: the seven above were hashed on PR 58's tree first and none
    # moved (the looped trunk, the sandwich and the gate are branches taken in Python, before
    # anything is traced; at one pass `trunk` is the code it was, to the letter)
    # (re-pinned in PR 63 with laguna's, above: its 48 call sites take q and k float32 and unturned)
    "ouro_2p6b_prefill_epix10k2m": "b888bb6e4984349512f6b63d1da4b969d42000eac112d1dc1b8e5c3303d092ce",
    # pinned in PR 64, which brought it: the eight above were hashed on PR 63's tree first and none
    # moved, though every one of them now goes through `layer_kind`'s and `decoder_layer`'s branches
    # for a layer of ONE block, `moe.hidden_rows` (a gated layer's products in the order its three
    # copies wrote them), `ssd_scan`'s groups read from the shapes (granite's kernel at one group is
    # the kernel it was: `tests/test_decoder_nemotron3.py -k traced_equation` holds its body) and
    # `grouped_tiles`' and `rows_as_words`' rules for a width of 14.5 or 10.5 lane tiles
    # (re-pinned in PR 70 with ling3's, above: six `rows_as_words` and six `row_gather` calls where
    # six `gather`s of `x [34816, 2688]` stood)
    "nemotron3_nano_prefill_epix10k2m": "f3a91345e7df789180c0ba3a100203f7f3f440ee80c99a5152047949ed26bd37",
    # pinned in PR 67, which brought it: the nine above were hashed on PR 66's tree first and none
    # moved, though every one of them now goes through `_projections`' rule for the q / k norm (none,
    # a head, the whole projection) and for a block without a norm before its branch, `init_params`'
    # and `decoder_layer`'s branches for the two forms of linear attention and the two norms a branch
    # may have (all taken in Python, before anything is traced); ling3's kernel, whose body this
    # test blanks, is held to the traced equation by `tests/test_decoder_olmo_hybrid.py -k ling3`
    "olmo_hybrid_7b_prefill_epix10k2m": "8cf4e88c84e23a402a343c6010b09e1fee7e07cbc84fc431a1967bd3dcb885da",
}


def _decoder_cell(name):
    """A decoder cell's configuration as the benchmark runs it: the mapping, the
    ``DecoderConfig`` and the parameters' shapes."""
    import json

    from psana_ray_tpu.models import decoder

    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    dcfg = decoder.DecoderConfig.from_mapping(cfg)
    return cfg, dcfg, jax.eval_shape(lambda k: decoder.init_params(dcfg, k), jax.random.key(0))


def _lowered_step(name, one_chip):
    """A decoder cell's served step, lowered for the described chip at the
    sizes the benchmark runs: ``(the mapping, the DecoderConfig, the lowering)``."""
    from psana_ray_tpu.models import decoder

    cfg, dcfg, params = _decoder_cell(name)
    calib = (S((PANELS, H, W), F32), S((PANELS, H, W), F32), S((PANELS, H, W), jnp.uint8))
    frames = S((cfg["batch_size"], PANELS, H, W), jnp.uint16)
    ids = S((cfg["prompt_tokens"],), jnp.int32)

    def step(p, c, f, i):
        return decoder.frame_step(p, c, f, i, cfg=dcfg, threshold=10.0)

    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), (params, calib, frames, ids))
    return cfg, dcfg, jax.jit(step).lower(*args)


@pytest.mark.parametrize("name", sorted(PINNED_STEPS))
def test_the_other_decoders_steps_lower_to_the_programs_they_were(name, one_chip, monkeypatch):
    import hashlib

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = _lowered_step(name, one_chip)
    text = lowered.as_text()
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED_STEPS[name]


def test_the_ling3_step_compiles_with_its_kernels_where_the_roofline_functions_count_them(
        one_chip, monkeypatch):
    """The whole served step of ``ling3_flash_prefill_epix10k2m`` at the
    published sizes, compiled for the described v5e (half a minute): it fits
    the chip beside nothing else (weights 10.5 GB), and its Mosaic kernels
    are the ones the cell's roofline metrics read by name or by count: six
    ``gated_delta_rule`` (one a linear layer: ``kda_roofline_share.ling3``
    reads each call), the grouped products under ``moe`` — eighteen in the
    pass ahead of the held rows' loop (``kimi_k2.held_products``'
    ``call_sites``: the ones that RUN on any load under 1.5 even shares) and
    the loop's own eighteen, which run only on what overflows the pass —
    and, since PR 52, the pass's way back, two kernels an expert layer
    (``rows_as_words`` lays the down product's rows out a block each,
    ``sum_counted_rows`` copies a token's counted rows and writes their gated
    sum: with them 30 Pallas instructions run under ``moe``, not ``call_sites``'
    18, so ``gmm_roofline_share.ling3``, which reads every ``pallas_call``
    there, reads nothing, and ``gmm_ahead_roofline_share.ling3`` reads the
    pass's eighteen by their name, ``%gmm``, which this test pins) and,
    since PR 70, the pass's way OUT, two more an expert layer (a second
    ``rows_as_words``, the view of ``x [34816, 2560]`` beside the way back's
    of ``out``, and ``row_gather``, ONE an expert layer: the pass moves 3
    rows a row of ``x``, a turn of the loop 2,048 of 34,816, and
    ``row_gather.tile_rows`` leaves that one to XLA's gather: 42 under ``moe``),
    one ``masked_gqa_attention``, the calibration kernel, since PR 73 six
    ``conv_silu_taps`` (one a linear layer over ``[q | k | v]``, under the scope
    ``conv``: ``conv_ms.ling3`` reads it there), and no other."""
    import collections

    from benchmark.roofline import kimi_k2

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = _lowered_step("ling3_flash_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes < 15e9
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    sites = kimi_k2.held_products(cfg["step_tokens"], 8, 2560, 768, 128, 7, 1, 0.25)["call_sites"]
    expert_layers = dcfg.num_layers - dcfg.num_dense_layers
    linear = cfg["layer_types"].count("linear_attention")
    assert names == {"gated_delta_rule": linear, "conv_silu_taps": linear, "gmm": 2 * sites,
                     "rows_as_words": 2 * expert_layers, "row_gather": expert_layers,
                     "sum_counted_rows": expert_layers, "masked_gqa_attention": 1, "fused_calibrate": 1}, names
    assert sites == 3 * expert_layers == 18
    assert all("/moe/" in line for line in calls
               if re.match(r"\s*%(gmm|rows_as_words|row_gather|sum_counted_rows)", line))
    assert all("/kda/" in line for line in calls if re.match(r"\s*%gated_delta_rule", line))
    assert all("/conv/" in line for line in calls if re.match(r"\s*%conv_silu_taps", line))


def test_the_granite_step_compiles_whole_with_its_kernels_under_the_scopes_a_trace_reads(
        one_chip, monkeypatch):
    """The whole served step of ``granite4_h_micro_prefill_epix10k2m`` at the
    published sizes, ALL 40 layers and the whole vocabulary, compiled for the
    described v5e (under half a minute: 36 of the layers are one function at
    one shape, traced and lowered once): it fits the chip (weights 6.4 GB,
    half a GB of temporaries), and its Mosaic kernels are 36 ``ssd_scan`` (one a
    state-space layer, under the scope ``ssd``: ``ssd_roofline_share.granite``
    reads each call by that name), 36 ``conv_silu_taps`` ahead of them (under
    ``conv``, since PR 73), 4 ``masked_gqa_attention`` (under ``sparse_attn``),
    the calibration kernel, and no other."""
    import collections

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = _lowered_step("granite4_h_micro_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 6.3e9 < mem.argument_size_in_bytes < 6.5e9 and mem.temp_size_in_bytes < 1.5e9
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    assert names == {"ssd_scan": cfg["layer_types"].count("mamba") == 36 and 36, "conv_silu_taps": 36,
                     "masked_gqa_attention": cfg["layer_types"].count("attention") == 4 and 4,
                     "fused_calibrate": 1}, names
    assert all("/ssd/" in line for line in calls if re.match(r"\s*%ssd_scan", line))
    assert all("/conv/" in line for line in calls if re.match(r"\s*%conv_silu_taps", line))
    assert all("/sparse_attn/" in line for line in calls if re.match(r"\s*%masked_gqa", line))
    # the stream between the layers is float32 (`decoder.trunk`), every product's operands bf16
    text = compiled.as_text()
    assert re.search(r"f32\[8704,2048\]", text) and not re.search(r"f32\[8704,8192\]\{[^}]*\} dot\(", text)


def test_the_nemotron3_step_compiles_whole_with_nothing_array_sized_between_a_block_s_parts(
        one_chip, monkeypatch):
    """The whole served step of ``nemotron3_nano_prefill_epix10k2m`` at the
    published sizes (fourteen layers of ONE block each, four frames), compiled
    for the described v5e (three quarters of a minute): it fits the chip
    (weights 9.2 GB, 3.1 GB of temporaries), and its Mosaic kernels are six
    ``ssd_scan`` (under ``ssd``), six ``conv_silu_taps`` ahead of them (under
    ``conv``, since PR 73), two ``masked_gqa_attention`` at sixteen heads
    a group (under ``sparse_attn``), under ``moe`` TWO grouped products an
    expert layer in the pass ahead of the held rows' loop and two in the loop
    (``nemotron3.held_products``' ``call_sites``: an ungated expert has no
    gate's product) with the pass's way back (``rows_as_words``,
    ``sum_counted_rows``) and, since PR 70, its way out (a second
    ``rows_as_words``, the view of ``x [34816, 2688]`` whose odd last chunk is
    the low halves of a word, and ``row_gather``, one an expert layer, for the
    156,672 rows XLA's gather moved), the calibration kernel, and no other. Nothing
    array-sized stands between ``W_in``'s products, the convolution, the scan
    and ``W_out``, nor between the rows' gather, the two grouped products and
    the way back: no copy, transpose, slice or pad of ``[34816, 6144 | 4096]``
    or of ``[156672, .]`` (until PR 64 ``sum_counted_rows`` padded a width of
    10.5 lane-tile pairs, 2,688, in a pass of its own). What IS left, and
    named in PERF.md: q relaid head-major for its sixteen heads a group, one
    copy of ``[34816, 2, 16, 128]`` an attention layer."""
    import collections

    from benchmark.roofline import nemotron3

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = _lowered_step("nemotron3_nano_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 9.1e9 < mem.argument_size_in_bytes < 9.3e9 and mem.temp_size_in_bytes < 4e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    sites = nemotron3.held_products(cfg["step_tokens"], 6, 2688, 1856, 64, 14, pattern, 0.5)["call_sites"]
    assert names == {"ssd_scan": pattern.count("M"), "conv_silu_taps": pattern.count("M"),
                     "masked_gqa_attention": pattern.count("*"), "gmm": 2 * sites, "rows_as_words": 2 * pattern.count("E"),
                     "row_gather": pattern.count("E"), "sum_counted_rows": pattern.count("E"),
                     "fused_calibrate": 1}, names
    assert sites == 2 * pattern.count("E") == 12
    assert all("/ssd/" in line for line in calls if re.match(r"\s*%ssd_scan", line))
    assert all("/conv/" in line for line in calls if re.match(r"\s*%conv_silu_taps", line))
    assert all("/sparse_attn/" in line for line in calls if re.match(r"\s*%masked_gqa", line))
    assert all("/moe/" in line for line in calls
               if re.match(r"\s*%(gmm|rows_as_words|row_gather|sum_counted_rows)", line))
    entry = text[text.index("ENTRY"):]
    moved = re.findall(r"= \w+\[(?:34816,(?:6144|4096)|4,8704,(?:6144|4096)|156672,\d+)\][^ ]* "
                       r"(?:copy|transpose|slice|pad|concatenate)\(.*", entry)
    assert not moved, [line[:160] for line in moved[:3]]
    assert len(re.findall(r"= bf16\[34816,2,16,128\][^ ]* copy\(", entry)) == pattern.count("*")
    # no held expert's weights are copied: the device lays `w_up [64, 2688, 1856]` out with the
    # contraction minor (`{1,2,0}`: 1,856 is no whole lane tiles) and the grouped product reads it
    # TRANSPOSED, a bitcast (until then a copy of 638 MB at every use, 2.02 ms under no scope)
    assert not re.findall(r"= bf16\[64,(?:2688,1856|1856,2688)\][^ ]* (?:copy|transpose|fusion)\(", text)


def test_the_olmo_hybrid_step_compiles_whole_with_its_kernels_where_the_roofline_functions_count_them(
        one_chip, monkeypatch):
    """The whole served step of ``olmo_hybrid_7b_prefill_epix10k2m`` at the
    published sizes (sixteen layers, one frame), compiled for the described
    v5e (a quarter of a minute: twelve of the layers are one function at one
    shape): it fits the chip (weights 8.2 GB, under a GB of temporaries), and
    its Mosaic kernels are the ones the roofline functions count: twelve
    ``gated_delta_net`` (one a linear layer, under the scope ``gdn``:
    ``olmo_hybrid.delta_rule`` counts a call), 36 ``conv_silu_taps`` ahead of
    them (q's, k's and v's a layer, under ``conv``, since PR 73), four ``masked_gqa_attention``
    (under ``sparse_attn``: ``olmo_hybrid.causal_attention``) at TWO heads a
    grid step (30 heads alone in their groups, unturned: 15 x 36 grid steps a
    call), the calibration kernel, and no other. q and k leave their
    products a head at whole lane tiles (``[8704, 3840]`` for 30 heads of 96:
    the pad is on the 11 M-element WEIGHT) and nothing array-sized stands
    between those products, the convolutions, the kernel and ``W_o``: no
    copy, slice, pad or transpose of a bf16 ``[8704, 2880 | 3840 | 5760]``."""
    import collections

    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = _lowered_step("olmo_hybrid_7b_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 8.1e9 < mem.argument_size_in_bytes < 8.3e9 and mem.temp_size_in_bytes < 1.5e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    assert names == {"gated_delta_net": cfg["layer_types"].count("linear_attention") == 12 and 12,
                     "conv_silu_taps": 3 * 12, "masked_gqa_attention": cfg["layer_types"].count("full_attention") == 4 and 4,
                     "fused_calibrate": 1}, names
    assert all("/gdn/" in line for line in calls if re.match(r"\s*%gated_delta_net", line))
    assert all("/conv/" in line for line in calls if re.match(r"\s*%conv_silu_taps", line))
    assert all("/sparse_attn/" in line for line in calls if re.match(r"\s*%masked_gqa", line))
    assert sa.heads_a_step(30, 1, 1088, 1088, 128, 128) == 2
    from psana_ray_tpu.models import decoder

    assert decoder.causal_call_steps(dcfg, 3, 1, 8704) == (30 * 36, 15 * 36, 15 * 36)  # what the step counts
    entry = text[text.index("ENTRY"):]
    moved = re.findall(r"= bf16\[8704,(?:2880|3840|5760)\][^ ]* (?:copy|transpose|slice|pad|concatenate)\(.*",
                       entry)
    assert not moved, [line[:160] for line in moved[:3]]
    # what IS left, and named in PERF.md section 7 (5): a full layer's q and k products leave their
    # fusion float32 and COLUMN-major (XLA's choice for the whole-projection norm's row sums in the
    # product's epilogue) and are copied row-major before the norm scales them: two a full layer
    assert len(re.findall(r"= f32\[8704,3840\][^ ]* copy\(", entry)) == 2 * 4
    assert len(re.findall(r"= bf16\[3840,30,128\][^ ]* pad\(", entry)) == 2 * 12  # W_q's and W_k's


def test_the_delta_net_kernel_the_chip_compiles_carries_its_state_float32():
    """The file states a float32 state a head, and on the chip no limit of the
    cell's ``correct`` tells a state CARRIED in bf16 from it (0.36-2.23
    yardsticks of 4: the state is a bf16 MXU operand either way). So the
    kernel Mosaic is handed, traced at the published sizes as the step calls
    it (not interpreted), is read: its one scratch is ``float32 [6, 192,
    128]`` (six heads a grid step, the state transposed, a head's 96 keys at
    128 lanes), what is stored there is float32, and nothing of a state's
    shape is ever widened from bf16 (rounded on its way to the next chunk)."""
    import functools

    from psana_ray_tpu.ops import delta_rule as dr

    _, dcfg, _ = _decoder_cell("olmo_hybrid_7b_prefill_epix10k2m")
    t, h, d_v, bf16 = 8704, dcfg.num_heads, dcfg.linear_value_dim, jnp.bfloat16
    wide = jax.eval_shape(lambda u: dr.lanes_a_head(u, h), S((1, h * dcfg.linear_head_dim), bf16)).shape[1]
    operands = (S((t, wide), bf16), S((t, wide), bf16), S((t, h * d_v), bf16), S((t, h), F32),
                S((t, h * d_v), bf16), S((t, h), F32), S((h,), F32), S((h,), F32), S((d_v,), bf16))
    traced = jax.make_jaxpr(functools.partial(
        dr.gated_delta_net, seq_len=t, heads=h, key_dim=dcfg.linear_head_dim, eps=dcfg.rms_eps,
        chunk=dcfg.linear_chunk, interpret=False))(*operands)

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for inner in eqn.params.values():
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                    yield from pallas_calls(getattr(inner, "jaxpr", inner))

    (call,) = pallas_calls(traced.jaxpr)
    assert call.params["name"] == "gated_delta_net" and not call.params["interpret"]
    (scratch,) = call.params["grid_mapping"].scratch_avals
    group, lanes = dr.head_group(h, wide // h, d_v), wide // h
    assert (group, lanes) == (dr.HEAD_GROUP, 128) == (6, 128)
    assert scratch.dtype == F32 and scratch.shape == (group, d_v, lanes)
    body = call.params["jaxpr"]
    state_ref = body.invars[-1]
    assert state_ref.aval.shape == scratch.shape and state_ref.aval.dtype == F32
    stored = [eqn.invars[1].aval for eqn in body.eqns
              if eqn.primitive.name == "swap" and eqn.invars[0] is state_ref]
    assert len(stored) == group and all(a.dtype == F32 and a.shape == (d_v, lanes) for a in stored)
    rounded = [eqn for eqn in body.eqns if eqn.primitive.name == "convert_element_type"
               and eqn.invars[0].aval.dtype == bf16 and eqn.invars[0].aval.shape == (d_v, lanes)]
    assert not rounded


def test_the_minicpm_sala_step_compiles_whole_with_its_kernels_under_the_scopes_a_trace_reads(
        one_chip, monkeypatch):
    """The whole served step of ``minicpm_sala_prefill_epix10k2m`` at the
    published sizes (four layers, one frame of 34,304 tokens), compiled for the
    described v5e (under ten seconds): it fits the chip (weights 3.4 GB, 4 GB
    of temporaries: a float32 stream and the MLP's 16,384-wide rows), and its
    Mosaic kernels are the ones the roofline functions count, each under the
    scope a trace reads: ONE ``select_blocks`` (under ``block_select``:
    ``minicpm_sala.select_blocks``), ONE ``masked_gqa_attention`` under its flags
    (``sparse_attn``: ``minicpm_sala.sparse_attention``), three
    ``lightning_attention`` (``lightning``: ``minicpm_sala.lightning_attention``),
    the calibration kernel, and no other. The sparse layer's calls meet shapes
    no other cell compiles: sixteen heads a group UNDER a mask (a query tile of
    128 x 2,048 keys, the keys padded to seventeen tiles), two selections a
    layer from ``[2, 34304, 640]`` flags."""
    import collections

    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = _lowered_step("minicpm_sala_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 3.4e9 < mem.argument_size_in_bytes < 3.5e9 and mem.temp_size_in_bytes < 4.5e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    assert names == {"select_blocks": 1, "masked_gqa_attention": 1, "lightning_attention": 3,
                     "fused_calibrate": 1}, names
    for kernel, scope in (("select_blocks", "block_select"), ("masked_gqa", "sparse_attn"),
                          ("lightning_attention", "lightning")):
        assert all(f"/{scope}/" in line for line in calls if re.match(rf"\s*%{kernel}", line))
    assert re.search(r"s8\[2,34304,640\]", text)  # the flags, a key head each
    sel = dcfg.block_select
    assert sel.tiles(34304) == (2048, 32, 640)
    assert sa._masked_query_tile(34304, dcfg.attn_q_tile, 128, 16 * 2048) == 128
    assert decoder.causal_call_steps(dcfg, 1, 1, 34304) == (0, 0, 0)  # a linear layer makes no causal call


def test_the_lightning_kernel_the_chip_compiles_carries_its_state_float32(one_chip, monkeypatch):
    """The file states a float32 state a head; a state CARRIED in bf16 would
    pass the chip's limits (it is a bf16 MXU operand either way: olmo_hybrid's
    finding). So the kernel Mosaic is handed, traced at the published sizes as
    the step calls it (not interpreted), is read: its first scratch is
    ``float32 [4, 128, 128]`` (four heads a grid step), what is stored there is
    float32 of a state's shape, and the kernel alone compiles for the described
    v5e at S 34,304, 32 heads of 128 x 128, in chunks of 256 rows."""
    import functools

    from psana_ray_tpu.ops import lightning

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, h, d = 34304, 32, 128
    operands = (S((t, h * d), F32), S((t, h * d), F32), S((t, h * d), BF16), S((t, h * d), BF16),
                S((h,), F32), S((d,), BF16), S((d,), BF16), S((d,), BF16), (S((t, d), F32), S((t, d), F32)))
    fn = functools.partial(lightning.lightning_attention, seq_len=t, heads=h, eps=1e-6,
                           scale=d ** -0.5, interpret=False)
    traced = jax.make_jaxpr(fn)(*operands)

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for inner in eqn.params.values():
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                    yield from pallas_calls(getattr(inner, "jaxpr", inner))

    (call,) = pallas_calls(traced.jaxpr)
    assert call.params["name"] == "lightning_attention" and not call.params["interpret"]
    state, masks, falls, left = call.params["grid_mapping"].scratch_avals
    assert state.dtype == F32 and state.shape == (lightning.HEADS, d, d) == (4, 128, 128)
    assert masks.shape == (4, 256, 256) and lightning.step_rows(t) == (512, 256)
    body = call.params["jaxpr"]

    def swaps(jaxpr):  # every store into a ref of the state's shape, the chunk loop's body included
        for eqn in jaxpr.eqns:
            ref = eqn.invars[0].aval if eqn.invars else None
            if eqn.primitive.name == "swap" and getattr(ref, "shape", None) == state.shape:
                assert ref.dtype == F32
                yield eqn.invars[1].aval
            for inner in eqn.params.values():
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                    yield from swaps(getattr(inner, "jaxpr", inner))

    stored = list(swaps(body))
    assert all(a.dtype == F32 for a in stored)  # a head's state a chunk leaves, four a chunk
    assert [a.shape for a in stored if a.shape == (d, d)] == [(d, d)] * lightning.HEADS
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), operands)
    assert jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call") >= 1


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones among them."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for inner in eqn.params.values():
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                yield from _pallas_calls(getattr(inner, "jaxpr", inner))


def test_the_selective_scan_the_chip_compiles_holds_one_float32_state_and_no_token_channel_state_array(
        one_chip, monkeypatch):
    """Mamba-1's scan at the published sizes (2 x 8,704 tokens, 5,120 channels
    over a state of 16), traced as the step calls it (not interpreted): ONE
    kernel whose scratch holds ONE float32 array of a state's size, ``[10,
    16, 512]`` (a channel tile a slot); nothing the call makes outside or
    inside it is as large as ``[T, 5,120, 16]`` (5.7 GB in float32: what an
    associative scan of XLA's would write) or loops over the tokens in HBM;
    and Mosaic takes it for the described v5e, with the second output a
    later layer's memory unit reads."""
    import functools

    from psana_ray_tpu.ops import selective_scan as ss

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, s, c, n = 17408, 8704, 5120, 16
    operands = (S((t, c), BF16), S((t, c), F32), S((t, ss.LANES), BF16), S((t, c), BF16),
                S((c, n), F32), S((c,), F32), S((c,), F32))
    fn = functools.partial(ss.selective_scan, seq_len=s, keep=True, interpret=False)
    traced = jax.make_jaxpr(fn)(*operands)
    (call,) = _pallas_calls(traced.jaxpr)
    assert call.params["name"] == "selective_scan" and not call.params["interpret"]
    assert ss.scan_tiles(s, c) == (256, 512)
    scratch = call.params["grid_mapping"].scratch_avals
    states = [a for a in scratch if int(np.prod(a.shape)) == c * n]
    assert [(a.shape, a.dtype) for a in states] == [((10, n, 512), F32)]
    assert all(a.dtype == F32 for a in scratch)
    made = [v.aval for eqn in traced.jaxpr.eqns for v in eqn.outvars]
    assert max(int(np.prod(a.shape)) for a in made + list(scratch)) <= t * c < t * c * n
    assert not any(eqn.primitive.name in ("scan", "while") for eqn in traced.jaxpr.eqns)
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), operands)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * t * c  # no array beside its operands


def test_one_differential_windowed_layer_compiles_at_the_published_sizes_as_two_band_calls(
        one_chip, monkeypatch):
    """Phi-4-mini-flash's windowed layer at 2 x 8,704 tokens: 20 head pairs
    over 10 key pairs of 2 x 64, two calls of the batched kernel at ``d`` 64,
    ``dv`` 128, two query half-heads a key half-head, in 256 x 512 tiles under
    the window of 512 — shapes no other cell compiles — and nothing else of
    Mosaic's in the layer."""
    import json

    from psana_ray_tpu.models import decoder
    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(REPO, "benchmark", "configs", "phi4_mini_flash_prefill_epix10k2m.json")) as f:
        cfg = decoder.DecoderConfig.from_mapping(json.load(f))
    assert cfg.layer_types[1] == decoder.SLIDING and cfg.sliding_window == 512
    assert sa.causal_tiles(8704, 2, cfg.causal_q_tile, cfg.causal_kv_tile, 512) == (256, 512)
    shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.key(0))["layers"][1]
    layer = {k: v for k, v in shapes.items() if not k.startswith(("w_gate", "w_up", "w_down", "norm2"))}
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip),
                        (layer, S((17408, 2560), BF16)))
    text = jax.jit(lambda p, x: decoder.diff_attention(p, x, 2, cfg, 1, window=512)[0]).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2 and text.count("windowed_gqa_attention") >= 2


def test_the_block_selection_and_the_call_under_its_flags_compile_at_sixteen_heads_a_group(
        one_chip, monkeypatch):
    """The sparse layer's two calls ALONE at the published sizes (S 34,304, 32
    query heads on 2 key heads of 128): the selection kernel (a query tile's
    ``[128, 2560]`` score row a head, four lane segments of 640 blocks) and the
    masked causal kernel under its flags, ``causal_tiles``, ``mask_tile`` and
    ``heads_a_step`` at a shape keye's eight and nemotron3's maskless sixteen
    have not compiled."""
    import functools

    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, h, g, d = 34304, 32, 2, 128
    sel = sa.BlockSelection()
    q, k = S((t, h * d), BF16, sharding=one_chip), S((t, g * d), BF16, sharding=one_chip)
    select = jax.jit(functools.partial(sa.select_blocks, num_kv_heads=g, selection=sel, interpret=False))
    text = select.lower(q, k).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "s8[2,34304,640]" in text
    flags = S((g, t, 640), jnp.int8, sharding=one_chip)
    q3, k3 = (S((1, *a.shape), BF16, sharding=one_chip) for a in (q, k))
    attend = jax.jit(lambda q, k, v, m: sa.masked_gqa_attention(
        q, k, v, m, num_kv_heads=g, block_q=256, mask_blocks=sel, interpret=False))
    compiled = attend.lower(q3, k3, k3, flags).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert sa.heads_a_step(g, h // g, 128, 2048, d, d, masked=True) == 1


def test_the_ouro_step_compiles_whole_as_one_loop_around_one_stack_of_layers(one_chip, monkeypatch):
    """The whole served step of ``ouro_2p6b_prefill_epix10k2m`` at the published
    sizes, ALL 48 layers four times over and the whole vocabulary, compiled for
    the described v5e (a quarter of a minute: the 48 layers are one function at
    one shape, traced and lowered once, and the four passes are a loop IN the
    program): it fits the chip with the weights held ONCE (5.34 GB of
    arguments, a third of a GB of temporaries), holds ONE ``while``, whose body
    has the 48 ``masked_gqa_attention`` call sites (under ``sparse_attn``; the
    calibration kernel stands outside) and no copy of a weight."""

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = _lowered_step("ouro_2p6b_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert 5.3e9 < mem.argument_size_in_bytes < 5.4e9 and mem.temp_size_in_bytes < 0.6e9
    text = compiled.as_text()
    loops = re.findall(r" while\(.*body=%?([\w.\-]+)", text)
    assert len(loops) == 1, loops
    start = text.index("%" + loops[0] + " (")
    body = text[start:text.index("\n}\n", start)].splitlines()
    calls = [line for line in body if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == dcfg.num_layers == 48 and text.count('custom_call_target="tpu_custom_call"') == 49
    assert all(re.match(r"\s*%masked_gqa_attention", line) and "/sparse_attn/" in line for line in calls)
    assert any("/pass_end/" in line for line in body)
    # the weights are the loop's invariants: nothing in the body copies, transposes or converts one
    weights = {tuple(a.shape) for a in jax.tree.leaves(_decoder_cell("ouro_2p6b_prefill_epix10k2m")[2])
               if a.ndim == 2}
    moved = [line for line in body
             if (m := re.match(r"\s*%[\w.\-]+ = bf16\[([\d,]+)\]\S* (copy|transpose|convert)\(", line))
             and tuple(int(n) for n in m.group(1).split(",")) in weights | {w[::-1] for w in weights}]
    assert not moved, moved[:3]


def test_the_laguna_step_compiles_with_its_kernels_where_the_roofline_functions_count_them(
        one_chip, monkeypatch):
    """The whole served step of ``laguna_s21_prefill_epix10k2m`` at the
    published sizes, compiled for the described v5e: it fits the chip beside
    nothing else (weights 11.4 GB), and its Mosaic kernels are the ones the
    cell's roofline metrics read by name: three ``masked_gqa_attention`` (the
    full layers, 6 query heads of 128 a group, under ``sparse_attn``), six
    ``windowed_gqa_attention`` (the same body over the band's tiles, 9 a
    group, under ``window_attn``), the grouped products under ``moe`` —
    twenty-four in the pass ahead of the held rows' loop (``call_sites``,
    named ``gmm``) and the loop's own twenty-four (named after the jit the
    loop stands in) — and the pass's way back at TEN slots a token
    and 24 lane chunks a row (``rows_as_words``, ``sum_counted_rows``, whose
    step of 5,120 slots takes 60 MB of VMEM for its two buffers), the
    calibration kernel, and no other: the rows go OUT by XLA's gather, and
    since PR 70 by the row gather's own rule, not for their width (3,072 is
    24 whole chunks, which the kernel takes through ``rows_as_words``' view):
    ``x [17408, 3072]`` is 102 MiB, which XLA's gather keeps in vector memory
    and moves at 10 ns a row, 0.61 ms a layer where the kernel with its view
    reads 1.71 (``row_gather.tile_rows``; my chip runs, PR 70)."""
    import collections

    from benchmark.roofline import laguna

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # as every entry point compiles (`jaxenv.configure_compile_cache`): locations of one frame, under
    # which an instruction is named after the jitted function it was traced in. The windowed layers'
    # calls are jitted under `sparse_attention.windowed_gqa_attention` for that: on the v5e all nine
    # kernels of a step carried the full layers' name while one jit served both (PR 53)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    cfg, dcfg, lowered = _lowered_step("laguna_s21_prefill_epix10k2m", one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes < 15e9
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = collections.Counter(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1) for line in calls)
    sites = laguna.held_products(cfg["step_tokens"], 10, 3072, 1024, 64, 9, [0], 0.25)["call_sites"]
    expert_layers = dcfg.num_layers - dcfg.num_dense_layers
    # by the jitted function each was traced in: the pass's products `gmm` (what
    # `gmm_ahead_roofline_share.laguna` reads by name), the loop's after the jit the loop stands in,
    # both kernels of the way back after `sum_counted_rows`
    assert names == {"masked_gqa_attention": cfg["layer_types"].count("full_attention"),
                     "windowed_gqa_attention": cfg["layer_types"].count("sliding_attention"),
                     "gmm": sites, "mlp": sites, "sum_counted_rows": 2 * expert_layers,
                     "fused_calibrate": 1}, names
    assert sites == 3 * expert_layers == 24 and names["masked_gqa_attention"] == 3
    assert all("/moe/" in line for line in calls if re.match(r"\s*%(gmm|mlp|sum_counted_rows)", line))
    assert all("/sparse_attn/" in line for line in calls if re.match(r"\s*%masked_gqa", line))
    assert all("/window_attn/" in line for line in calls if re.match(r"\s*%windowed_gqa", line))
    text = compiled.as_text()
    for scope in ("proj", "shared_expert", "mlp", "moe", "sparse_attn", "window_attn"):
        assert f"jit(step)/{scope}/" in text, scope


def _array_sized_moves(entry, floor, opcodes, apart=None):
    """``name type[dims]`` of every instruction of a compiled entry
    computation that only MOVES an array of ``floor`` elements or more: one
    of ``opcodes``, or a copy/bitcast fusion (a ``convolution_bitcast_fusion``
    is a PRODUCT that writes its result in its reader's layout: no move);
    lines that carry ``apart`` (a scope of its own account) left out."""
    moved = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", line)
        if not m or (apart and apart in line):
            continue
        op_name, dtype, dims, opcode = m.groups()
        moves = opcode in opcodes or (
            opcode == "fusion" and ("copy" in op_name or "bitcast" in op_name)
            and "convolution" not in op_name)
        if moves and np.prod([int(x) for x in dims.split(",") if x]) >= floor:
            moved.append(f"{op_name} {dtype}[{dims}]")
    return moved


@pytest.mark.parametrize("name", ["kimi_k2_prefill_epix10k2m", "deepseek_v32_prefill_epix10k2m"])
def test_latent_attention_s_operands_reach_the_kernel_where_their_products_wrote_them(
        name, one_chip, monkeypatch):
    """ONE latent layer (``decoder.latent_attention``) at the cell's
    published widths, batch and 8,704 tokens a sequence, as compiled: in
    the entry computation no ``copy``, ``slice``, ``reshape`` or
    copy/bitcast fusion writes an array of ``T * H * 64`` elements or more
    between the projections' products, ``masked_gqa_attention`` and ``W_o``
    (the indexer's own head-major index queries apart: its scope). On PR
    47's tree this counted ten in kimi's layer, beside a pass that scaled
    and converted the float32 query: the 128-wide query sliced out of a
    float32 ``[T, H*192]`` product, relaid, and transposed head-major
    (three); the rotary query reshaped and copied (two); keys and values
    each relaid and transposed (four); the output transposed back (one);
    and six in dsv32's (the query's slice and relayout, the keys-and-values
    product relaid whole and then a copy each, the output's): 3.4 GB
    written a layer that computed nothing. Since PR 48 the kernel reads q,
    k, v and writes o as column blocks of the products' own token-major
    arrays (k and v of ONE array). Since PR 61 the kernel turns the 64-wide
    rotary query itself, a query tile at a time: ``W_uq``'s rotary product
    writes it float32, unturned, head-major ``[H, 1, T, 64]`` (the kernel's
    operand: ONE array of ``T * H * 64`` elements, where PR 48's tree wrote
    three between that product and the kernel: the float32 product 570 MB,
    the rotary's two float32 halves ``[T, H, 32]`` 1,140 MB in lanes a
    quarter full, the scaled bf16 head-major copy 285 MB), and no float32
    ``[T, H, 32]`` array exists."""
    from psana_ray_tpu.models import decoder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, params = _decoder_cell(name)
    batch, seq = cfg["batch_size"], 8704
    tokens, heads = batch * seq, dcfg.num_heads

    def layer(p, x):
        angles = decoder.rotary_angles(np.arange(seq), dcfg.rope_theta, dcfg.rope_dim // 2,
                                       yarn=dcfg.rope_yarn)
        return decoder.latent_attention(p, x, jnp.tile(angles, (batch, 1)), batch, dcfg, angles)

    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip),
                        (params["layers"][1], S((tokens, dcfg.hidden_size), BF16)))
    text = jax.jit(layer).lower(*args).compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(r"^\s*(?:ROOT )?%masked_gqa_attention[.\d]* = ", entry, re.M)) == 1
    assert f"[{tokens},{heads * dcfg.head_dim}]" not in entry  # no product of whole [nope | rope] heads
    moved = _array_sized_moves(entry, tokens * heads * 64, ("copy", "slice", "reshape"), "/indexer/")
    assert not moved, moved
    # the rotary query: no half of it is ever an array, and what the kernel reads is what the
    # product wrote (the indexer's index queries have as many elements in dsv32: its scope apart)
    dr = dcfg.qk_rope_head_dim
    assert f"f32[{tokens},{heads},{dr // 2}]" not in entry and "multiply_subtract_fusion" not in entry
    rotary = [f"{m.group(1)} {dtype}[{dims}]" for line in entry.splitlines() if "/indexer/" not in line
              for m in [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) [\w\-]+\(", line)] if m
              for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", m.group(2))
              if np.prod([int(x) for x in dims.split(",")]) == tokens * heads * dr]
    assert rotary == [f"%convolution_bitcast_fusion f32[{heads},1,{tokens},{dr}]"], rotary


def test_every_latent_layer_of_kimi_s_step_hands_the_kernel_what_its_product_wrote(
        one_chip, monkeypatch):
    """The WHOLE served step of ``kimi_k2_prefill_epix10k2m``, compiled for
    the described v5e (a minute): what the layer alone (above) cannot see.
    With ``W_uq``'s rotary product written two-dimensional and reshaped
    (PR 61's first form) the layer alone and layer 0 of the step compiled
    to ONE product writing the kernel's operand, and layers 1-6 of the
    step to a COLUMN-major product ``f32[17408,4096]{0,1}`` and a ``copy``
    of it into ``[17408,64,1,64]`` (8.4 ms of the step on the chip, under
    ``latent_attn``): here every one of the seven kernels' rotary query is,
    through bitcasts alone, a product's own result; the angle tables are
    made once a step, and no float32 half of a rotary query exists."""

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, lowered = _lowered_step("kimi_k2_prefill_epix10k2m", one_chip)
    text = lowered.compile().as_text()
    entry = text[text.index("ENTRY"):]
    made = {m.group(1): (m.group(2), line) for line in entry.splitlines()
            for m in [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\(", line)] if m}
    tokens, heads, dr = cfg["batch_size"] * 8704, dcfg.num_heads, dcfg.qk_rope_head_dim
    kernels = [line for name, (_, line) in made.items() if name.startswith("%masked_gqa_attention")]
    assert len(kernels) == dcfg.num_layers == 7
    for line in kernels:
        operands = re.findall(r"%[\w.\-]+", line.split("custom-call(")[1].split(")")[0])
        rotary = [o for o in operands if f"f32[{heads},1,{tokens},{dr}]" in made[o][1]]
        assert len(rotary) == 1, operands
        name = rotary[0]
        while made[name][0] == "bitcast":
            name = re.search(r"bitcast\((%[\w.\-]+)\)", made[name][1]).group(1)
        assert made[name][0] == "fusion" and "convolution" in name, made[name][1][:200]
    assert f"f32[{tokens},{heads},{dr // 2}]" not in entry
    assert sum("jit(turn_tables)" in line for _, line in made.values()) == 2  # [cos|cos], [sin|sin]


@pytest.mark.parametrize("name,kind", [
    ("laguna_s21_prefill_epix10k2m", "sliding_attention"),
    ("laguna_s21_prefill_epix10k2m", "full_attention"),
    ("ouro_2p6b_prefill_epix10k2m", "full_attention")], ids=["laguna-sliding", "laguna-full", "ouro"])
def test_laguna_s_grouped_heads_reach_the_kernel_where_their_products_wrote_them(
        name, kind, one_chip, monkeypatch):
    """ONE attention layer (``decoder._attention``) as compiled: laguna's
    windowed one at 72 query heads and its full one at 48 (8 key heads of
    128, two sequences of 8,704, the output gated), and ONE layer
    application of the looped reader's (16 heads on 16 key heads, two
    sequences of 2,304, ``[4608, 2048]``): one kernel, its output the
    token-major ``[B, 1, S, H*128]`` that ``W_o`` reads, and between
    ``W_q``'s and ``W_k``'s products and ``W_o`` NOTHING of ``T * H * 64``
    elements or more that only moves: no ``copy``, ``transpose``,
    ``reshape``, ``convert``, ``broadcast`` or copy/bitcast fusion. On PR
    57's tree a windowed layer held q's head-major copy (bf16
    ``[2,8,9,8704,128]``) and THREE float32 passes over o on the way back
    with the gate broadcast to ``[T, H, 128]`` beside them; PR 58 left the
    rotary's own: the two 64-lane halves of every head sliced out of the
    float32 product into ``f32[T, H, 64]`` copies (fifteen a step in
    laguna's, 21-26 ms; 768 in the looped reader's, 49.7 ms) and turned in
    lane-padded passes. Since PR 63 the kernel's q and k ARE the products'
    results, float32 and unturned, through bitcasts alone, and the kernel
    turns them by the step's two tables (``jit(turn_tables)``, ``[T,
    128]`` float32 each): no float32 half of a head exists. (A layer ALONE
    copies its input and its result, the entry computation's parameter and
    root, into the layout its neighbours would have given them: those two
    of the looped reader's are not between the products and ``W_o``.)"""
    from psana_ray_tpu.models import decoder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, params = _decoder_cell(name)
    batch, seq, i = cfg["batch_size"], cfg["sequence_tokens"], cfg["layer_types"].index(kind)
    tokens, heads = batch * seq, dcfg.heads(i)
    sliding = kind == "sliding_attention"
    assert (heads, tokens) == {"laguna-sliding": (72, 17408), "laguna-full": (48, 17408),
                               "ouro-full": (16, 4608)}[name.split("_")[0] + "-" + kind.split("_")[0]]

    def layer(p, x):
        if sliding:
            angles = decoder.rotary_angles(np.arange(seq), dcfg.sliding_rope_theta, dcfg.head_dim // 2)
        else:
            angles = decoder.rotary_angles(np.arange(seq), dcfg.rope_theta, dcfg.rope_dim // 2,
                                           yarn=dcfg.rope_yarn)
        return decoder._attention(p, x, jnp.tile(angles, (batch, 1)), None, batch, dcfg,
                                  dcfg.sliding_window if sliding else 0)[0]

    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip),
                        (params["layers"][i], S((tokens, dcfg.hidden_size), BF16)))
    text = jax.jit(layer).lower(*args).compile().as_text()
    entry = text[text.index("ENTRY"):]
    made = {m.group(1): (m.group(2), line) for line in entry.splitlines()
            for m in [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\(", line)] if m}
    kernel = "windowed_gqa_attention" if sliding else "masked_gqa_attention"
    calls = [line for made_by, (_, line) in made.items() if made_by.startswith("%" + kernel)]
    assert len(calls) == 1 and f" = bf16[{batch},1,{seq},{heads * dcfg.head_dim}]" in calls[0]
    moved = _array_sized_moves(entry, tokens * heads * 64,
                               ("copy", "transpose", "reshape", "convert", "broadcast"))

    def at_the_edge(move):  # the entry's root, or a copy of one of its parameters
        line = made[move.split()[0]][1]
        source = re.search(r"copy\((%[\w.\-]+)\)", line)
        return line.lstrip().startswith("ROOT") or bool(source) and "parameter(" in made[source.group(1)][1]

    assert [m for m in moved if not at_the_edge(m)] == [], moved
    assert len(moved) == (2 if name.startswith("ouro") else 0), moved
    # the kernel's q and k: each, through bitcasts alone, a product's own float32 result
    operands = re.findall(r"%[\w.\-]+", calls[0].split("custom-call(")[1].split(")")[0])
    for operand, columns in ((operands[2], heads), (operands[3], dcfg.num_kv_heads)):
        assert f" = f32[" in made[operand][1], made[operand][1][:200]
        while made[operand][0] == "bitcast":
            operand = re.search(r"bitcast\((%[\w.\-]+)\)", made[operand][1]).group(1)
        kind_of, line = made[operand]
        assert kind_of == "fusion" and "/dot_general" in line and (
            f"f32[{tokens},{columns * dcfg.head_dim}]" in line), line[:300]
    assert f"f32[{tokens},{heads},{dcfg.head_dim // 2}]" not in entry and "multiply_subtract_fusion" not in entry
    assert sum("jit(turn_tables)" in line and f"f32[{tokens},{dcfg.head_dim}]" in line.split(" fusion(")[0]
               for _, line in made.values()) == 2  # [cos | cos | 1], [-sin | sin | 0]


@pytest.mark.parametrize("kind,heads,window,parts,products", [
    ("full", 48, None, 3, 2 * 3 * 2), ("windowed", 72, 512, 1, 4 * 1 * 2)])
def test_laguna_s_stacked_calls_compile_with_their_rows_in_parts(kind, heads, window, parts, products,
                                                                   one_chip, monkeypatch):
    """Laguna's two calls ALONE at the published sizes (2 x 8,704 tokens, 8
    key heads of 128, q and k float32 for the kernel to turn, the gate a
    head), as the step makes them since PR 75: a grid step's stacked group
    cut into ``parts`` runs of whole heads (``parts_a_step``: three parts of
    TWO heads at the full layers' 512 x 1,088; the windowed ones' nine heads
    at 256 x 512 stay ONE product, their four branches leave room for two
    parts and nine has no half), the body's products two a part and branch
    (two branches, four under the window), the scratch what it was (``m``,
    ``l``, ``acc`` and the turned query tile, stacked: a part is a slice of
    each), and Mosaic takes the written order within ``_VMEM_LIMIT``. Compile
    seconds for the described v5e here, the parent's one stacked product ->
    three parts, lowering included (PR 75): full 8.8 -> 6.3; on the chip,
    first call, 6.5 -> 7.0 (the windowed call at three parts 4.2 -> 5.8)."""
    from psana_ray_tpu.parallel import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, s, g, d = 2, 8704, 8, 128
    bq, bk = sa.causal_tiles(s, heads // g, 1088, 1088, window, d)
    assert (bq, bk) == ((256, 512) if window else (512, 1088))
    assert sa.parts_a_step(heads // g, bq, bk, window=window) == parts

    def fn(q, k, v, cos, sin, gate):
        attend = sa.windowed_gqa_attention if window else sa.masked_gqa_attention
        return attend(q, k, v, num_kv_heads=g, block_q=1088, block_k=1088, interpret=False,
                      out_gate=gate, turn=(cos, sin), turn_width=d if window else d // 2,
                      q_scale=d ** -0.5, **({"window": window} if window else {}))

    table = S((b * s, d), F32)
    operands = (S((b, s, heads * d), F32), S((b, s, g * d), F32), S((b, s, g * d), BF16), table, table,
                S((b, s, heads), F32))
    (call,) = _pallas_calls(jax.make_jaxpr(fn)(*operands).jaxpr)

    def count(jaxpr, name):  # through the branches' conds
        return sum((eqn.primitive.name == name) + sum(
            count(getattr(inner, "jaxpr", inner), name) for value in eqn.params.values()
            for inner in (value if isinstance(value, (tuple, list)) else (value,))
            if hasattr(getattr(inner, "jaxpr", inner), "eqns")) for eqn in jaxpr.eqns)

    assert count(call.params["jaxpr"], "dot_general") == products
    rows = heads // g * bq
    assert [a.shape for a in call.params["grid_mapping"].scratch_avals] == [
        (rows, 1), (rows, 1), (rows, d), (rows, d)]
    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip), operands)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert ("windowed_gqa_attention" if window else "masked_gqa_attention") in text


def test_keye_s_selection_attention_operands_reach_the_kernel_with_two_pads_and_no_new_copy(
        one_chip, monkeypatch):
    """ONE attention layer of keye's (``decoder._attention``: 32 heads on 4
    key heads of 128, the indexer's selection, one sequence of 34,304) as
    compiled since PR 68 (``-k reach_the_kernel``'s count for this layer):
    ONE ``masked_gqa_attention`` call on ``[1, S, .]`` operands under the
    mask ``[268, 16, 128, 2176]``, its output the token-major ``[1, 1, S,
    4096]`` that ``W_o``'s product reads as it is. Between ``W_q``'s product
    and ``W_o`` what only moves ``S * 512`` elements or more is the PARENT's
    two relayouts — XLA turns q and k with the tokens in the lanes
    (``{0,2,1}``) and copies each row-major for the kernel, 281 + 35 MB a
    layer (ROADMAP S13: ``_kernel_turns`` refuses a selection) — and this
    PR's two pads of k and v to the mask's sixteen whole key tiles (35.6 MB
    each). No head-major copy of q (the group's token-major block is stacked
    in the kernel), nothing of o."""
    from psana_ray_tpu.models import decoder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, dcfg, params = _decoder_cell("keye_vl2_prefill_epix10k2m")
    seq, heads, patch = KEYE_S, dcfg.num_heads, cfg["patch"]
    pos = decoder.frame_positions(PANELS, H // patch, W // patch, cfg["prompt_tokens"])
    assert len(pos) == seq and cfg["batch_size"] == 1

    def layer(p, x):
        angles = decoder.rotary_angles(pos, dcfg.rope_theta, dcfg.rope_dim // 2, dcfg.mrope_section)
        idx = decoder.rotary_angles(np.arange(seq), dcfg.rope_theta, dcfg.indexer_head_dim // 2)
        return decoder._attention(p, x, angles, idx, 1, dcfg)[0]

    args = jax.tree.map(lambda a: S(a.shape, a.dtype, sharding=one_chip),
                        (params["layers"][1], S((seq, dcfg.hidden_size), BF16)))
    text = jax.jit(layer).lower(*args).compile().as_text()
    entry = text[text.index("ENTRY"):]
    made = {m.group(1): (m.group(2), line) for line in entry.splitlines()
            for m in [re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\(", line)] if m}
    calls = [line for made_by, (_, line) in made.items() if made_by.startswith("%masked_gqa_attention")]
    assert len(calls) == 1 and f" = bf16[1,1,{seq},{heads * dcfg.head_dim}]" in calls[0]
    assert "s8[268,16,128,2176]" in calls[0]
    kv = dcfg.num_kv_heads * dcfg.head_dim
    moved = _array_sized_moves(entry, seq * kv, ("copy", "transpose", "reshape", "convert", "pad"),
                               "/indexer/")  # (its head-major index queries: its scope's account)
    index_halves = f"f32[{seq},{dcfg.indexer_heads},{dcfg.indexer_head_dim // 2}]"  # and their rotary
    moved = sorted(m.split(" ", 1)[1] for m in moved if index_halves not in m)
    assert moved == sorted([f"bf16[1,1,{seq},{heads * dcfg.head_dim}]", f"bf16[1,{seq},{kv}]",
                            f"bf16[1,{16 * 2176},{kv}]", f"bf16[1,{16 * 2176},{kv}]"]), moved
    # W_o's product reads what the kernel wrote
    root = next(line for _, line in made.values() if line.lstrip().startswith("ROOT"))
    assert "/dot_general" in root and calls[0].split(" = ")[0].strip() in root, root[:300]


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


_LAYER_TEXT = {}  # an expert layer's compiled text (25 s each), for the tests that read it


def _expert_layer_text(case, one_chip, monkeypatch):
    if case not in _LAYER_TEXT:
        fn, arg_shapes, *_ = case()
        _LAYER_TEXT[case] = _compile_case(fn, arg_shapes, one_chip, monkeypatch).as_text()
    return _LAYER_TEXT[case]


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
    text = _expert_layer_text(case, one_chip, monkeypatch)
    routed = [line for line in text.splitlines() if "/moe_route/" in line]
    assert len(routed) > 20  # the scope reaches the compiled text
    by_data = [line.strip()[:160] for line in routed
               if re.search(r" (scatter|gather|custom-call)\(", line) or "TopK" in line]
    assert not by_data, by_data
    sorts = [line for line in routed if re.search(r" sort\(", line)]
    assert 1 <= len(sorts) <= 2, sorts
    for line in sorts:  # each an argsort of the T * k slots: keys and their places, one axis
        dims = {d for _, d in _SHAPE.findall(line.split(" sort(")[0])}
        assert dims == {str(tokens * k)}, line[:200]
    spread = [sorted(dims) for dims in ((tokens, k, experts), (tokens * k, experts), (tokens, k * experts))]
    sized = [line.strip()[:160] for line in text.splitlines()
             for _, dims in _SHAPE.findall(line.split(", metadata=")[0])
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

    text = _expert_layer_text(_ling3_experts, one_chip, monkeypatch)
    tokens = LING3_B * LING3_S
    ahead = moe.rows_ahead(tokens * 8, 128, 512)
    rows_gathered = [int(_SHAPE.search(line).group(2).split(",")[0]) for line in text.splitlines()
                     if " gather(" in line and "/moe_experts/" in line and "/while/" not in line
                     and _SHAPE.search(line).group(2).endswith(",2560")]
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
    text = _expert_layer_text(case, one_chip, monkeypatch)
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


@pytest.mark.parametrize("d", [0, 1, 2, 3])
@pytest.mark.parametrize("r", [1, 2, 4])
def test_local_maxima_cuts_its_phases_without_a_gather(r, d):
    """``x[ry::b, rx::b]`` traces to the ``gather`` primitive, and on the
    TPU each phase then costs a row fetch a row (PR 41: 1.35 ms of nine
    gathers a step); ``lax.slice`` is the static form. Runs on the CPU."""
    from psana_ray_tpu.models.peaks import _local_maxima

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    logits = S((3, 48 // r, 64 // r, r * r), F32)
    names = set(primitives(jax.make_jaxpr(lambda x: _local_maxima(x, 0.5, d, d + 1, r))(logits).jaxpr))
    assert "slice" in names and "gather" not in names


# -- the compile cache can be placed from outside ---------------------------

def test_compile_cache_honours_the_environment(cache_setting, monkeypatch, tmp_path):
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; code set no directory over it
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_the_checkout(cache_setting, monkeypatch):
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert configure_compile_cache() == want  # fixed: no pid, time or tmp name
    assert jax.config.jax_compilation_cache_dir == want


def test_kernel_program_is_the_same_from_any_call_stack(one_chip, monkeypatch):
    """A Pallas kernel's serialized module must not carry its callers'
    Python stack, or one step compiled from two entry points gets two
    persistent-cache keys (seen on the v5e: CLI child vs. script)."""
    from psana_ray_tpu.utils.jaxenv import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")  # set no dir here
    configure_compile_cache()
    step, arg_shapes, _ = _calib(jnp.uint16)
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in arg_shapes]

    def through_another_caller(*a):
        return step(*a)

    texts = []
    for fn in (step, through_another_caller):
        jax.clear_caches()  # else the second lowering reuses the first trace
        fn.__name__ = "step"  # the module is named after the function
        texts.append(jax.jit(fn).lower(*args).as_text())
    assert "tpu_custom_call" in texts[0]
    assert texts[0] == texts[1]


# -- chip_smoke.py refuses to pass off the chip -----------------------------

def _run(argv, **env_extra):
    env = dict(os.environ, **env_extra)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        argv, capture_output=True, text=True, timeout=300, env=env, cwd=REPO
    )


def test_chip_smoke_fails_at_the_device_check_on_cpu():
    out = _run([sys.executable, "chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "JAX found no accelerator" in out.stderr  # there, not before


def test_chip_smoke_failed_child_fails_the_run(tmp_path):
    """A phase's child that exits non-zero ends the run non-zero — no
    try/except lets a failed phase reach the result line."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    with pytest.raises(SystemExit) as e:
        chip_smoke.run_child(
            "serve", [sys.executable, "-c", "raise SystemExit(3)"],
            str(tmp_path / "serve.log"),
        )
    assert e.value.code == 1


def test_producer_cli_never_imports_jax():
    """A producer must not be able to hold the chip: the CLI runs to its
    EOS with ``jax`` absent from ``sys.modules``."""
    code = (
        "import sys; from psana_ray_tpu.producer import main; "
        "main(['--detector_name', 'smoke_a', '--num_events', '4', '--calib']); "
        "assert 'jax' not in sys.modules, 'producer imported jax'; print('JAXFREE')"
    )
    out = _run([sys.executable, "-c", code])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "JAXFREE" in out.stdout
