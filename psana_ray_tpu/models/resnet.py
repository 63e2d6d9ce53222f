"""ResNet-v1.5 classifiers (ResNet-50 flagship) in flax.linen, TPU-first.

BASELINE config 4: "ResNet-50 diffraction hit/miss classifier, batched
120 Hz stream". Design choices for TPU:

- NHWC layout; channel counts are multiples of 128 at the deep stages, so
  convs tile the MXU exactly;
- bfloat16 compute, float32 params (`dtype` vs `param_dtype`);
- GroupNorm instead of BatchNorm: streaming inference sees padded tail
  batches (infeed/batcher.py) whose zero rows would poison batch
  statistics; GroupNorm is row-independent, so padding rows can't leak —
  and there's no running-stats state to checkpoint/sync across hosts;
- logical axis names on every param (via flax's logical partitioning
  metadata) so parallel/sharding.ShardingRules can pjit the model with
  channel-TP without the model knowing about meshes.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any

# logical axis names: ("height","width") for conv kernels' spatial dims,
# channels_in/out for the matmul dims TP shards
conv_axes = ("height", "width", "channels_in", "channels_out")


_conv_kernel_init = nn.with_logical_partitioning(
    nn.initializers.variance_scaling(2.0, "fan_out", "normal"), conv_axes
)


def _conv(features, kernel, strides, dtype, name=None):
    return nn.Conv(
        features,
        kernel,
        strides=strides,
        padding="SAME",
        use_bias=False,
        dtype=dtype,
        param_dtype=jnp.float32,
        kernel_init=_conv_kernel_init,
        name=name,
    )


class FrozenAffine(nn.Module):
    """Per-channel scale + bias — the inference form of a normalization
    layer whose statistics are constants (BatchNorm folding). On TPU this
    fuses into the preceding conv's epilogue, where a data-dependent
    GroupNorm costs a full extra HBM pass (~10 ms per layer at epix10k2M
    scale, measured); 53 norm layers of ResNet-50 dominate the forward
    otherwise. Use ``norm='frozen'`` for streaming inference with trained
    constants; ``norm='group'`` for training."""

    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, ("channels_out",)),
            (self.features,),
            jnp.float32,
        )
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros, ("channels_out",)),
            (self.features,),
            jnp.float32,
        )
        return x * scale.astype(x.dtype) + bias.astype(x.dtype)


def _norm(dtype, features, name=None, kind: str = "group"):
    if kind == "frozen":
        return FrozenAffine(features, dtype=dtype, name=name)
    if kind in ("batch", "batch_eval"):
        # BatchNorm with running statistics: the TRAINABLE form whose
        # checkpoints fold exactly into FrozenAffine for the fused serving
        # kernels (models/fold.py) — eval-mode BatchNorm IS an affine with
        # constants from running stats. 'batch' = training (per-batch
        # stats, running stats updated via the mutable 'batch_stats'
        # collection); 'batch_eval' = inference on running stats (used by
        # the fold equivalence tests). Caveat vs GroupNorm: batch stats
        # see every row, so train on FULL batches only (drop/skip padded
        # tails — examples/train_peaknet.py --norm batch does).
        return nn.BatchNorm(
            use_running_average=(kind == "batch_eval"),
            momentum=0.9,
            epsilon=1e-5,
            dtype=dtype,
            param_dtype=jnp.float32,
            scale_init=nn.with_logical_partitioning(nn.initializers.ones, ("channels_out",)),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("channels_out",)),
            name=name,
        )
    # aim for 32 channels/group (torchvision GroupNorm default), degrading
    # to the largest group size that divides narrow layers
    return nn.GroupNorm(
        num_groups=None,
        group_size=math.gcd(32, features),
        dtype=dtype,
        param_dtype=jnp.float32,
        scale_init=nn.with_logical_partitioning(nn.initializers.ones, ("channels_out",)),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("channels_out",)),
        name=name,
    )


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck (ResNet-v1.5: stride on the 3x3)."""

    features: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Dtype = jnp.bfloat16
    norm: str = "group"

    @nn.compact
    def __call__(self, x):
        residual = x
        y = _conv(self.features, (1, 1), (1, 1), self.dtype)(x)
        y = nn.silu(_norm(self.dtype, self.features, kind=self.norm)(y))
        y = _conv(self.features, (3, 3), self.strides, self.dtype)(y)
        y = nn.silu(_norm(self.dtype, self.features, kind=self.norm)(y))
        y = _conv(self.features * 4, (1, 1), (1, 1), self.dtype)(y)
        y = _norm(self.dtype, self.features * 4, kind=self.norm)(y)
        if residual.shape != y.shape:
            residual = _conv(self.features * 4, (1, 1), self.strides, self.dtype,
                             name="proj")(residual)
            residual = _norm(self.dtype, self.features * 4, name="proj_norm", kind=self.norm)(residual)
        return nn.silu(y + residual)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 block (ResNet-18/34)."""

    features: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Dtype = jnp.bfloat16
    norm: str = "group"

    @nn.compact
    def __call__(self, x):
        residual = x
        y = _conv(self.features, (3, 3), self.strides, self.dtype)(x)
        y = nn.silu(_norm(self.dtype, self.features, kind=self.norm)(y))
        y = _conv(self.features, (3, 3), (1, 1), self.dtype)(y)
        y = _norm(self.dtype, self.features, kind=self.norm)(y)
        if residual.shape != y.shape:
            residual = _conv(self.features, (1, 1), self.strides, self.dtype,
                             name="proj")(residual)
            residual = _norm(self.dtype, self.features, name="proj_norm", kind=self.norm)(residual)
        return nn.silu(y + residual)


class ResNetClassifier(nn.Module):
    """Generic ResNet over NHWC inputs (any channel count = panel count)."""

    stage_sizes: Sequence[int]
    block: Callable = BottleneckBlock
    num_classes: int = 2
    width: int = 64
    dtype: Dtype = jnp.bfloat16
    norm: str = "group"

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        x = _conv(self.width, (7, 7), (2, 2), self.dtype, name="stem")(x)
        x = nn.silu(_norm(self.dtype, self.width, name="stem_norm", kind=self.norm)(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block(self.width * 2**i, strides=strides, dtype=self.dtype, norm=self.norm)(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(
            self.num_classes,
            dtype=jnp.float32,  # logits in f32 for stable softmax/loss
            param_dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal"),
                # "classes" replicates: num_classes (often 2) is too small
                # to split over the model axis
                ("channels_in", "classes"),
            ),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("classes",)),
            name="head",
        )(x)
        return x


ResNet50 = partial(ResNetClassifier, stage_sizes=(3, 4, 6, 3), block=BottleneckBlock)
ResNet18 = partial(ResNetClassifier, stage_sizes=(2, 2, 2, 2), block=BasicBlock)
