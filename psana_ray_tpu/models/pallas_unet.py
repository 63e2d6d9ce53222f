"""Fused Pallas inference path for PeakNet-TPU encoder levels.

The pallas_resnet.py recipe applied to the U-Net (round-2 VERDICT item):
one ``pallas_call`` per encoder level running ConvBlock (two 3x3 convs,
each with folded-affine + SiLU epilogues) plus the strided downsample
conv — activations stay in VMEM across all three convs, weights live in
VMEM scratch loaded once per batch (TPU grids are sequential), the 3x3s
are nine shifted MXU matmuls with f32 accumulation, and the stride-2 conv
reads 2x2 polyphase planes (strided vector slices do not lower on Mosaic;
the plane extraction is the proven trick from pallas_resnet.py).

What is fused and what stays XLA — and why:

- **enc level 1, enc level 2, bottleneck**: fused here. At PeakNet-TPU's
  packed geometry (epix10k2M: 88x96x128, 44x48x256, 22x24x512) the whole
  panel + pad buffers + polyphase planes + resident weights fit the
  ~16 MB VMEM budget — this is precisely what the space-to-depth redesign
  (models/unet_tpu.py) buys; the classic full-res model could never do
  this.
- **enc level 0 and the decoder**: XLA. Level 0's 176x192x64 activations
  need three+ whole-panel buffers whose 64->128 lane padding doubles
  them past VMEM, and a decoder level's output is twice the extent of
  its input: a whole-panel kernel would hold both. XLA runs the decoder
  within a quarter of the MXU's peak already (measured on the v5e, PR
  37), its up-convolution on the low-resolution map (``unet.upconv2x``,
  shared with the flax model): the fusion win there is marginal against
  the Mosaic-complexity risk.

``peaknet_tpu_fused_infer`` is the drop-in equivalent of
``PeakNetUNetTPU(norm='frozen').apply`` — equivalence is tested in
interpret mode on CPU (tests/test_pallas_unet.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from psana_ray_tpu.models.pallas_resnet import (
    _VMEM_BUDGET,
    _downsample,
    _pad_to,
    _pick_chunk,
    _up,
    _ypad_dims,
)
from psana_ray_tpu.models.unet import upconv2x
from psana_ray_tpu.models.unet_tpu import depth_to_space, space_to_depth

_BF16 = jnp.bfloat16


def _conv_block_kernel(
    x_h, w1_h, w2_h, wd_h_or_s1, *rest, cin, f, h, w, down, cr, cpp
):
    """ConvBlock (+ optional stride-2 downsample) for one grid step.

    Ref order: x, w1, w2, [wd], s1, b1, s2, b2, skip_out, [down_out],
    then scratch: x_v, xp_v, y1p_v, skip_v, w1_v, w2_v, [wd_v, skpp_v,
    pp_v, down_v], sem.
    """
    if down:
        wd_h = wd_h_or_s1
        (s1, b1, s2, b2, skip_h, down_h,
         x_v, xp_v, y1p_v, skip_v, w1_v, w2_v,
         wd_v, skpp_v, pp_v, down_v, sem) = rest
    else:
        s1 = wd_h_or_s1
        (b1, s2, b2, skip_h,
         x_v, xp_v, y1p_v, skip_v, w1_v, w2_v, sem) = rest
        wd_h = wd_v = skpp_v = pp_v = down_v = down_h = None

    b = pl.program_id(0)

    @pl.when(b == 0)
    def _load_weights():
        pairs = ((w1_h, w1_v), (w2_h, w2_v))
        if down:
            pairs += ((wd_h, wd_v),)
        for src, dst in pairs:
            cp = pltpu.make_async_copy(src, dst, sem)
            cp.start()
            cp.wait()

    cp = pltpu.make_async_copy(x_h.at[b], x_v, sem)
    cp.start()
    cp.wait()

    # zero-bordered pad buffers: 3x3 taps never branch on boundaries
    xp_v[:] = jnp.zeros_like(xp_v)
    y1p_v[:] = jnp.zeros_like(y1p_v)
    if down:
        skpp_v[:] = jnp.zeros_like(skpp_v)

    def _fill_xp(i, carry):
        r0 = i * cr
        xp_v[pl.ds(1 + r0, cr), 1:1 + w] = x_v[pl.ds(r0, cr)]
        return carry

    jax.lax.fori_loop(0, h // cr, _fill_xp, 0, unroll=False)

    # conv1 + affine + silu -> y1 pad buffer
    def _y1_body(i, carry):
        r0 = i * cr
        acc = jnp.zeros((cr * w, f), jnp.float32)
        for t in range(9):
            dy, dx = divmod(t, 3)
            patch = xp_v[pl.ds(r0 + dy, cr), dx:dx + w]
            acc += jnp.dot(
                patch.reshape(cr * w, cin), w1_v[t],
                preferred_element_type=jnp.float32,
            )
        y1 = jax.nn.silu(acc * s1[:] + b1[:]).astype(_BF16)
        y1p_v[pl.ds(1 + r0, cr), 1:1 + w] = y1.reshape(cr, w, f)
        return carry

    jax.lax.fori_loop(0, h // cr, _y1_body, 0, unroll=False)

    # conv2 + affine + silu -> skip (plain buffer for the DMA out, and the
    # stride-2 pad buffer for the downsample taps)
    def _y2_body(i, carry):
        r0 = i * cr
        acc = jnp.zeros((cr * w, f), jnp.float32)
        for t in range(9):
            dy, dx = divmod(t, 3)
            patch = y1p_v[pl.ds(r0 + dy, cr), dx:dx + w]
            acc += jnp.dot(
                patch.reshape(cr * w, f), w2_v[t],
                preferred_element_type=jnp.float32,
            )
        y2 = jax.nn.silu(acc * s2[:] + b2[:]).astype(_BF16).reshape(cr, w, f)
        skip_v[pl.ds(r0, cr)] = y2
        if down:
            skpp_v[pl.ds(1 + r0, cr), 1:1 + w] = y2
        return carry

    jax.lax.fori_loop(0, h // cr, _y2_body, 0, unroll=False)

    cp = pltpu.make_async_copy(skip_v, skip_h.at[b], sem)
    cp.start()
    cp.wait()

    if down:
        # 2x2 polyphase planes of the skip pad buffer, then the stride-2
        # conv's taps are plain slices of the phase planes (pallas_resnet
        # stride-2 pattern; SAME pad for k=3,s=2 is (0,1) -> off=1)
        hp2, wp2 = h // 2 + 2, w // 2 + 2

        def _pp_body(i, carry):
            r0 = i * cpp
            for a in (0, 1):
                for c in (0, 1):
                    raw = skpp_v[pl.ds(a + 2 * r0, 2 * cpp), c:c + 2 * wp2]
                    pp_v[a, c, pl.ds(r0, cpp)] = _downsample(raw, 2, cpp, wp2, f)
            return carry

        jax.lax.fori_loop(0, hp2 // cpp, _pp_body, 0, unroll=False)

        ho, wo = h // 2, w // 2

        def _down_body(i, carry):
            ro = i * cr
            rows = min(cr, ho)  # cr chosen to divide ho below
            acc = jnp.zeros((rows * wo, f), jnp.float32)
            for t in range(9):
                dy, dx = divmod(t, 3)
                ar, radd = (dy + 1) % 2, (dy + 1) // 2
                ac, cadd = (dx + 1) % 2, (dx + 1) // 2
                patch = pp_v[ar, ac, pl.ds(ro + radd, rows), cadd:cadd + wo]
                acc += jnp.dot(
                    patch.reshape(rows * wo, f), wd_v[t],
                    preferred_element_type=jnp.float32,
                )
            down_v[pl.ds(ro, rows)] = acc.astype(_BF16).reshape(rows, wo, f)
            return carry

        jax.lax.fori_loop(0, ho // min(cr, ho), _down_body, 0, unroll=False)

        cp = pltpu.make_async_copy(down_v, down_h.at[b], sem)
        cp.start()
        cp.wait()


def fused_conv_block(
    x: jax.Array,           # [B, h, w, cin] — h, w even; w multiple of 8
    w1: jax.Array,          # [3, 3, cin, f]
    a1: Tuple[jax.Array, jax.Array],  # (scale [f], bias [f]) f32
    w2: jax.Array,          # [3, 3, f, f]
    a2: Tuple[jax.Array, jax.Array],
    wd: Optional[jax.Array] = None,  # [3, 3, f, f] stride-2 downsample
    interpret: Optional[bool] = None,
):
    """One U-Net encoder level as a single pallas_call: ConvBlock
    (conv3x3 -> affine -> silu, twice) + optional stride-2 conv.

    Returns ``skip [B, h, w, fp]`` (and ``down [B, h/2, w/2, fp]`` when
    ``wd`` is given) with channels zero-padded to the 128-lane quantum —
    chain levels in padded form; zero-padded channels x zero weight rows
    keep the padding numerically exact.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bsz, h, w, cin_x = x.shape
    cin_t, f_t = w1.shape[2], w1.shape[3]
    down = wd is not None
    # w % 8: Mosaic sublane quantum for the in-kernel vector slices;
    # even h only matters for the stride-2 polyphase extraction
    if w % 8 or (down and h % 2):
        raise ValueError(
            f"need w % 8 == 0{' and even h (stride-2 level)' if down else ''}, "
            f"got {h}x{w}"
        )
    # the input must be w1's true channel count, or that count already
    # zero-padded to the lane quantum (the inter-level chaining form) —
    # anything else would silently convolve against zero weight rows
    if cin_x != cin_t and cin_x != _up(cin_t, 128):
        raise ValueError(
            f"input has {cin_x} channels but w1 expects {cin_t} "
            f"(or its 128-padded form {_up(cin_t, 128)})"
        )

    cin = _up(cin_x, 128)
    f = _up(f_t, 128)
    x = _pad_to(x.astype(_BF16), 3, cin)
    w1p = _pad_to(_pad_to(w1.astype(_BF16).reshape(9, cin_t, f_t), 1, cin), 2, f)
    w2p = _pad_to(_pad_to(w2.astype(_BF16).reshape(9, f_t, f_t), 1, f), 2, f)
    s1 = _pad_to(a1[0].astype(jnp.float32).reshape(1, f_t), 1, f)
    b1 = _pad_to(a1[1].astype(jnp.float32).reshape(1, f_t), 1, f)
    s2 = _pad_to(a2[0].astype(jnp.float32).reshape(1, f_t), 1, f)
    b2 = _pad_to(a2[1].astype(jnp.float32).reshape(1, f_t), 1, f)
    operands = [x, w1p, w2p]
    if down:
        wdp = _pad_to(_pad_to(wd.astype(_BF16).reshape(9, f_t, f_t), 1, f), 2, f)
        operands.append(wdp)
    operands += [s1, b1, s2, b2]

    ypr, ypc = _ypad_dims(h, w, 2)
    hp2, wp2 = h // 2 + 2, w // 2 + 2
    fixed = (
        h * w * cin * 2                # x_v
        + (h + 2) * (w + 2) * cin * 2  # xp_v
        + (h + 2) * (w + 2) * f * 2    # y1p_v
        + h * w * f * 2                # skip_v
        + w1p.size * 2 + w2p.size * 2
    )
    if down:
        fixed += (
            w2p.size * 2  # wd_v scratch is allocated at w2p.shape
            + ypr * ypc * f * 2
            + 4 * hp2 * wp2 * f * 2
            + (h // 2) * (w // 2) * f * 2
        )
    budget = max(256 * 1024, _VMEM_BUDGET - fixed)
    # one fori iteration's live set: f32 accumulator + bf16 patch/result
    cr = _pick_chunk(h, w * (4 * f + 6 * max(cin, f)), budget)
    if down:
        cr = min(cr, h // 2)
        while (h % cr) or ((h // 2) % cr):
            cr -= 1
        cpp = _pick_chunk(hp2, wp2 * f * 48, budget)
    else:
        cpp = 1

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_specs = [any_spec] * (4 if down else 3) + [vmem] * 4

    out_shape = [jax.ShapeDtypeStruct((bsz, h, w, f), _BF16)]
    if down:
        out_shape.append(jax.ShapeDtypeStruct((bsz, h // 2, w // 2, f), _BF16))

    scratch = [
        pltpu.VMEM((h, w, cin), _BF16),
        pltpu.VMEM((h + 2, w + 2, cin), _BF16),
        pltpu.VMEM((h + 2, w + 2, f), _BF16),
        pltpu.VMEM((h, w, f), _BF16),
        pltpu.VMEM(w1p.shape, _BF16),
        pltpu.VMEM(w2p.shape, _BF16),
    ]
    if down:
        scratch += [
            pltpu.VMEM(w2p.shape, _BF16),
            pltpu.VMEM((ypr, ypc, f), _BF16),
            pltpu.VMEM((2, 2, hp2, wp2, f), _BF16),
            pltpu.VMEM((h // 2, w // 2, f), _BF16),
        ]
    scratch.append(pltpu.SemaphoreType.DMA)

    kernel = functools.partial(
        _conv_block_kernel, cin=cin, f=f, h=h, w=w, down=down, cr=cr, cpp=cpp
    )
    out = pl.pallas_call(
        kernel,
        grid=(bsz,),
        in_specs=in_specs,
        out_specs=[any_spec] * len(out_shape),
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return tuple(out) if down else (out[0], None)


# ---------------------------------------------------------------------------
# Full-network fused inference (kernels for the inner levels, XLA for the
# rest — see module docstring for the split rationale).
# ---------------------------------------------------------------------------


def _xla_conv3x3(x, kernel, stride=1):
    return jax.lax.conv_general_dilated(
        x, kernel.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _xla_affine_silu(x, aff):
    scale, bias = aff
    return jax.nn.silu(x * scale.astype(x.dtype) + bias.astype(x.dtype))


def _block_params(p, name):
    bp = p[name]
    return (
        bp["Conv_0"]["kernel"],
        (bp["FrozenAffine_0"]["scale"], bp["FrozenAffine_0"]["bias"]),
        bp["Conv_1"]["kernel"],
        (bp["FrozenAffine_1"]["scale"], bp["FrozenAffine_1"]["bias"]),
    )


def peaknet_tpu_fused_infer(
    variables,
    x: jax.Array,
    features: Sequence[int] = (64, 128, 256, 512),
    s2d: int = 2,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused-forward equivalent of
    ``PeakNetUNetTPU(features, norm='frozen').apply(variables, x)``.

    ``x``: [N, H, W, C_in]; returns per-pixel logits [N, H, W, classes].
    """
    from flax.core import meta

    p = meta.unbox(variables)["params"]
    n_enc = len(features) - 1

    y = space_to_depth(x, s2d).astype(_BF16)

    # encoder level 0: XLA (see module docstring)
    w1, a1, w2, a2 = _block_params(p, "ConvBlock_0")
    y = _xla_affine_silu(_xla_conv3x3(y, w1), a1)
    y = _xla_affine_silu(_xla_conv3x3(y, w2), a2)
    skips = [y]
    y = _xla_conv3x3(y, p["Conv_0"]["kernel"], stride=2)

    # inner encoder levels + bottleneck: fused kernels, channel-padded form
    f_pads = {}
    for lvl in range(1, n_enc):
        w1, a1, w2, a2 = _block_params(p, f"ConvBlock_{lvl}")
        skip, y = fused_conv_block(
            y, w1, a1, w2, a2, wd=p[f"Conv_{lvl}"]["kernel"],
            interpret=interpret,
        )
        f_pads[lvl] = features[lvl]
        skips.append(skip)
    w1, a1, w2, a2 = _block_params(p, f"ConvBlock_{n_enc}")
    y, _ = fused_conv_block(y, w1, a1, w2, a2, wd=None, interpret=interpret)
    y = y[..., : features[-1]]  # back to true channel width for the decoder

    # decoder: XLA
    for i, (f_lvl, skip) in enumerate(zip(reversed(features[:-1]), reversed(skips))):
        lvl = n_enc - 1 - i
        if lvl in f_pads:
            skip = skip[..., : features[lvl]]
        u = upconv2x(y, p[f"Conv_{n_enc + i}"]["kernel"])
        mb = p[f"MergeBlock_{i}"]
        z = _xla_conv3x3(u, mb["merge_up"]["kernel"]) + _xla_conv3x3(
            skip, mb["merge_skip"]["kernel"]
        )
        z = jax.nn.silu(
            z * mb["FrozenAffine_0"]["scale"].astype(z.dtype)
            + mb["FrozenAffine_0"]["bias"].astype(z.dtype)
        )
        z = _xla_conv3x3(z, mb["Conv_0"]["kernel"])
        y = jax.nn.silu(
            z * mb["FrozenAffine_1"]["scale"].astype(z.dtype)
            + mb["FrozenAffine_1"]["bias"].astype(z.dtype)
        )

    logits = (
        y.astype(jnp.float32) @ p["logits"]["kernel"][0, 0]
        + p["logits"]["bias"]
    )
    return depth_to_space(logits, s2d)
