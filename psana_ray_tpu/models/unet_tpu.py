"""PeakNet-TPU: the MXU-shaped redesign of the Bragg-peak U-Net.

BASELINE config 3 is "PeakNet (U-Net) Bragg-peak segmentation" — the
reference has no model code at all (its consumers are opaque torch loops,
SURVEY.md §2), so the architecture is ours to design, and
:class:`psana_ray_tpu.models.unet.PeakNetUNet`'s classic full-resolution
(32, 64, 128, 256) layout is hostile to the TPU's 128x128 MXU: its
level-0 convs contract K=9·32 onto N=32 output channels, capping the
systolic array at ~25% utilization no matter how the convs are fused, and
its full-res activations (352x384x32) blow the ~16 MB VMEM budget for
whole-panel kernel fusion.

This variant keeps the same capability (per-pixel peak logits over
epix10k2M panels, U-Net encoder/decoder with skips, comparable parameter
count and receptive field) but moves the spatial/channel trade to where
the MXU wants it:

- **space-to-depth stem** (2x2 pixel unshuffle): the network runs at half
  resolution with 4x input channels — an exact relayout, no information
  loss, and the standard TPU/GPU idiom for small-channel image heads;
- **features (64, 128, 256, 512)**: every conv contracts K = 9·64 .. 9·512
  with N >= 64 — 50-100% MXU shapes instead of 6-25%;
- **depth-to-space logits head**: a 1x1 conv emits ``s2d² · num_classes``
  channels at packed resolution, unshuffled back to one logit per ORIGINAL
  pixel — per-pixel segmentation output is preserved exactly;
- max activation is 176x192x64 (bf16 ≈ 4.3 MB): small enough that
  whole-panel-resident fused kernels (the pallas_resnet.py recipe) become
  possible without halo-streaming, where the classic model's full-res
  352x384 levels could never fit VMEM.

Same conventions as the classic model: strided-conv downsampling, 2x
nearest upsample + 3x3 convolution computed on the low-resolution map
(``unet.upconv2x``), split-weight skip merge, GroupNorm + SiLU for
training (``norm='group'``), folded :class:`FrozenAffine` statistics for
streaming inference (``norm='frozen'``), bf16 compute / f32 params.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from psana_ray_tpu.models.resnet import _conv
from psana_ray_tpu.models.unet import ConvBlock, MergeBlock, UpConv2x

Dtype = Any


def space_to_depth(x: jax.Array, r: int) -> jax.Array:
    """[N, H, W, C] -> [N, H/r, W/r, r*r*C] (exact pixel unshuffle)."""
    n, h, w, c = x.shape
    if h % r or w % r:
        raise ValueError(
            f"space_to_depth needs H, W divisible by {r}; got {h}x{w}"
        )
    x = x.reshape(n, h // r, r, w // r, r, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // r, w // r, r * r * c)


def depth_to_space(x: jax.Array, r: int) -> jax.Array:
    """[N, H, W, r*r*C] -> [N, H*r, W*r, C] (inverse of space_to_depth)."""
    n, h, w, c = x.shape
    if c % (r * r):
        raise ValueError(f"depth_to_space needs C divisible by {r * r}; got {c}")
    x = x.reshape(n, h, w, r, r, c // (r * r))
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h * r, w * r, c // (r * r))


class PeakNetUNetTPU(nn.Module):
    """U-Net ``[N, H, W, C_in] -> [N, H, W, num_classes]`` logits.

    H and W must be divisible by ``s2d * 2**(len(features) - 1)``
    (epix10k2M 352x384 with the defaults: 16 | 352 and 16 | 384 — OK).
    """

    features: Sequence[int] = (64, 128, 256, 512)
    num_classes: int = 1
    dtype: Dtype = jnp.bfloat16
    norm: str = "group"
    s2d: int = 2

    @nn.compact
    def __call__(self, x, packed: bool = False):
        """``packed=True`` returns the head's output as it is computed,
        ``[N, H/s2d, W/s2d, s2d²·num_classes]`` (what
        ``find_peaks(..., s2d=s2d)`` reads): the same numbers, not
        unshuffled."""
        n, h, w, _ = x.shape
        quantum = self.s2d * 2 ** (len(self.features) - 1)
        if h % quantum or w % quantum:
            raise ValueError(
                f"PeakNetUNetTPU needs H, W divisible by {quantum} "
                f"(s2d={self.s2d} x {len(self.features) - 1} stride-2 levels); "
                f"got {h}x{w} — pad the panels or reduce depth"
            )
        # One named scope per level (enc0.., bottleneck, dec<level>, head):
        # metadata on the compiled ops, so a device trace finds a level
        # again whatever the fusions are numbered. Parameter names and
        # values do not depend on it.
        x = space_to_depth(x, self.s2d).astype(self.dtype)
        skips = []
        # encoder
        for i, f in enumerate(self.features[:-1]):
            with jax.named_scope(f"enc{i}"):
                x = ConvBlock(f, dtype=self.dtype, norm=self.norm)(x)
                skips.append(x)
                x = _conv(f, (3, 3), (2, 2), self.dtype)(x)  # strided downsample
        with jax.named_scope("bottleneck"):
            x = ConvBlock(self.features[-1], dtype=self.dtype, norm=self.norm)(x)
        # decoder
        levels = range(len(skips) - 1, -1, -1)
        for i, f, skip in zip(levels, reversed(self.features[:-1]), reversed(skips)):
            with jax.named_scope(f"dec{i}"):
                # Conv_<n>: the name the nn.Conv it replaces gave itself
                x = UpConv2x(f, dtype=self.dtype, name=f"Conv_{2 * len(skips) - 1 - i}")(x)
                x = MergeBlock(f, dtype=self.dtype, norm=self.norm)(x, skip)
        # logits for every ORIGINAL pixel: s2d²·classes channels at packed
        # resolution, unshuffled back out — f32 like the classic head
        with jax.named_scope("head"):
            y = nn.Conv(
                self.num_classes * self.s2d * self.s2d,
                (1, 1),
                dtype=jnp.float32,
                param_dtype=jnp.float32,
                kernel_init=nn.initializers.variance_scaling(
                    1.0, "fan_in", "truncated_normal"
                ),
                name="logits",
            )(x)
            return y if packed else depth_to_space(y, self.s2d)
