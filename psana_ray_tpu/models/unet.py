"""PeakNet-style U-Net for Bragg-peak segmentation, flax.linen, TPU-first.

BASELINE config 3: "PeakNet (U-Net) Bragg-peak segmentation on epix10k2M
frames" — the serial-crystallography workload the reference's stale
packaging metadata reveals ("Save PeakNet inference results to CXI",
reference ``setup.py:11``; keyword SFX at ``setup.py:15``).

Encoder/decoder with skip connections; downsampling by strided conv,
upsampling by nearest resize + 3x3 conv (no learned transposed conv, so
no checkerboarding), computed on the low-resolution map (``upconv2x``);
GroupNorm + SiLU; bfloat16 compute / float32 params; per-pixel logit
output. Input is panel-as-batch NHWC (``heads.panels_to_nhwc(..,"batch")``)
so one compiled program serves any panel count.

Spatial constraint: H and W must be divisible by 2**(len(features)-1) —
one stride-2 level per non-bottleneck feature entry (epix10k2M 352x384
with the default 4 features: 8 | 352 and 8 | 384 -> OK; enforced with a
clear error at the door).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from psana_ray_tpu.models.resnet import _conv, _conv_kernel_init, _norm

Dtype = Any


def _fold_taps(k: jax.Array, axis: int) -> jax.Array:
    """Three taps along ``axis`` -> the four they make over a 2x nearest
    upsample written as zero-stuffing: ``[w0, w0+w1, w1+w2, w2]``."""
    w0, w1, w2 = jnp.moveaxis(k, axis, 0)
    return jnp.stack([w0, w0 + w1, w1 + w2, w2], axis=axis)


def upconv2x(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """``conv3x3_SAME(upsample2x_nearest(x), kernel)`` computed on the
    LOW-resolution map: ``[N, H, W, Cin]`` and the ``[3, 3, Cin, Cout]``
    kernel -> ``[N, 2H, 2W, Cout]``; the upsampled tensor is never
    written.

    A 2x nearest upsample repeats every pixel 2x2, so the nine taps of
    the window fall on only 2x2 distinct low pixels: for output row
    2i+a the window's rows are low rows (i-1, i, i) for a = 0 and
    (i, i, i+1) for a = 1, and taps that meet the same low row can be
    summed first. Written once for both phases: the upsample is
    zero-stuffing (``lhs_dilation`` 2) followed by a 2x2 box, and box
    and kernel compose into ONE 4x4 kernel, ``[w0, w0+w1, w1+w2, w2]``
    along each axis (:func:`_fold_taps`). Each output pixel then meets
    2x2 non-zero inputs: 16 tap-products per low pixel where
    upsample-then-convolve does 36. The TPU's convolution skips the
    stuffed zeros and writes every phase in place (measured on the v5e,
    PR 37: the three decoder levels run at 85-97% of the MXU's peak on
    16/36 of the work; four 2x2 or two 2x3 phase convolutions
    interleaved in XLA pay two extra passes a level and gain nothing).

    Exact, borders included: the padding of 2 around the stuffed map is
    the zero ``SAME`` puts around the upsampled one. The taps are summed
    in float32 and cast once, as ``nn.Conv`` casts its kernel. Linear in
    ``x`` and ``kernel``, so training differentiates through it.
    """
    k4 = _fold_taps(_fold_taps(kernel.astype(jnp.float32), 0), 1)
    return jax.lax.conv_general_dilated(
        x, k4.astype(x.dtype), (1, 1), ((2, 2), (2, 2)), lhs_dilation=(2, 2),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


class UpConv2x(nn.Module):
    """The decoder's "upsample 2x, then 3x3 convolution" as
    :func:`upconv2x`, over the parameter ``nn.Conv`` would hold:
    ``kernel [3, 3, Cin, features]``, float32, ``conv_axes``. Name it
    ``Conv_<n>`` where the ``nn.Conv`` it stands for would auto-name
    itself: checkpoints and every reader of the tree go by that path."""

    features: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", _conv_kernel_init, (3, 3, x.shape[-1], self.features), jnp.float32
        )
        return upconv2x(x.astype(self.dtype), kernel)


class ConvBlock(nn.Module):
    features: int
    dtype: Dtype = jnp.bfloat16
    norm: str = "group"

    @nn.compact
    def __call__(self, x):
        x = _conv(self.features, (3, 3), (1, 1), self.dtype)(x)
        x = nn.silu(_norm(self.dtype, self.features, kind=self.norm)(x))
        x = _conv(self.features, (3, 3), (1, 1), self.dtype)(x)
        return nn.silu(_norm(self.dtype, self.features, kind=self.norm)(x))


class MergeBlock(nn.Module):
    """Decoder block: merge the upsampled path with its skip, then a
    ConvBlock tail. The classic ``conv(concat([up, skip]))`` is
    numerically identical to ``conv_a(up) + conv_b(skip)`` with the
    kernel split along its input-channel axis — but the split form skips
    materializing the doubled-width concat tensor (a pure HBM copy XLA
    does not elide; ~3 ms per high-res level at epix10k2M scale)."""

    features: int
    dtype: Dtype = jnp.bfloat16
    norm: str = "group"

    @nn.compact
    def __call__(self, up, skip):
        y = _conv(self.features, (3, 3), (1, 1), self.dtype, name="merge_up")(up)
        y = y + _conv(self.features, (3, 3), (1, 1), self.dtype, name="merge_skip")(skip)
        y = nn.silu(_norm(self.dtype, self.features, kind=self.norm)(y))
        y = _conv(self.features, (3, 3), (1, 1), self.dtype)(y)
        return nn.silu(_norm(self.dtype, self.features, kind=self.norm)(y))


class PeakNetUNet(nn.Module):
    """U-Net: ``[N, H, W, C_in] -> [N, H, W, num_classes]`` logits.

    ``norm='group'`` for training (row-independent, no running stats);
    ``norm='frozen'`` for streaming inference with folded statistics —
    the same convention as :class:`psana_ray_tpu.models.resnet.ResNetClassifier`.
    """

    features: Sequence[int] = (32, 64, 128, 256)
    num_classes: int = 1  # peak / not-peak
    dtype: Dtype = jnp.bfloat16
    norm: str = "group"

    @nn.compact
    def __call__(self, x):
        n, h, w, _ = x.shape
        # the decoder doubles exactly: an odd extent at any level would
        # surface as an opaque shape mismatch in MergeBlock, so fail at
        # the door with the actual constraint (round-2 ADVICE)
        quantum = 2 ** (len(self.features) - 1)
        if h % quantum or w % quantum:
            raise ValueError(
                f"PeakNetUNet needs H, W divisible by {quantum} "
                f"({len(self.features) - 1} stride-2 levels); got {h}x{w} — "
                f"pad the panels or reduce depth"
            )
        x = x.astype(self.dtype)
        skips = []
        # encoder
        for i, f in enumerate(self.features[:-1]):
            x = ConvBlock(f, dtype=self.dtype, norm=self.norm)(x)
            skips.append(x)
            x = _conv(f, (3, 3), (2, 2), self.dtype)(x)  # strided downsample
        # bottleneck
        x = ConvBlock(self.features[-1], dtype=self.dtype, norm=self.norm)(x)
        # decoder
        for j, (f, skip) in enumerate(zip(reversed(self.features[:-1]), reversed(skips))):
            x = UpConv2x(f, dtype=self.dtype, name=f"Conv_{len(skips) + j}")(x)
            x = MergeBlock(f, dtype=self.dtype, norm=self.norm)(x, skip)
        # per-pixel logits in f32
        return nn.Conv(
            self.num_classes,
            (1, 1),
            dtype=jnp.float32,
            param_dtype=jnp.float32,
            kernel_init=nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal"),
            name="logits",
        )(x)
