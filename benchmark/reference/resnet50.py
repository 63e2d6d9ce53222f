"""Plain float32 ResNet forward pass: the reference for
``resnet50_hit_epix10k2m``.

He et al. (arXiv:1512.03385), Table 1, 50-layer column: a 7x7/2 stem,
3x3/2 max pool, four stages of bottleneck blocks (1x1 -> 3x3 -> 1x1, the
last four times as wide; 3-4-6-3 blocks; stage widths 256/512/1024/2048),
global average pool, a dense head. Straight ``lax.conv`` over the
program's parameter tree in float32 at ``Precision.HIGHEST``.

``compute=jnp.bfloat16`` gives the precision yardstick: the same pass
with both operands of every convolution rounded to bfloat16 first and
float32 accumulation — what "bfloat16 compute" promises, and no less.

Departures from the paper, the program's own and listed in the
configuration's ``assumed``: the stride of a down-sampling block sits on
its 3x3 convolution (v1.5); normalisation is the folded per-channel
affine; the activation is SiLU; the input has the detector's panel count
as channels; two classes.
"""

import jax
import jax.numpy as jnp


def _conv(x, kernel, compute, stride=1):
    return jax.lax.conv_general_dilated(
        x.astype(compute).astype(jnp.float32), kernel.astype(compute).astype(jnp.float32),
        (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )


def _affine(x, p):
    return x * p["scale"] + p["bias"]


def _bottleneck(x, p, stride, compute):
    y = jax.nn.silu(_affine(_conv(x, p["Conv_0"]["kernel"], compute), p["FrozenAffine_0"]))
    y = jax.nn.silu(_affine(_conv(y, p["Conv_1"]["kernel"], compute, stride), p["FrozenAffine_1"]))
    y = _affine(_conv(y, p["Conv_2"]["kernel"], compute), p["FrozenAffine_2"])
    if "proj" in p:
        x = _affine(_conv(x, p["proj"]["kernel"], compute, stride), p["proj_norm"])
    return jax.nn.silu(y + x)


def forward(params, x, stage_sizes, compute=jnp.float32):
    """``x [B,H,W,C]`` float32 (panels as channels) -> logits ``[B,classes]``
    (pooling and the head stay float32 whatever ``compute``)."""
    x = x.astype(jnp.float32)
    x = jax.nn.silu(_affine(_conv(x, params["stem"]["kernel"], compute, 2), params["stem_norm"]))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    idx = 0
    for i, n_blocks in enumerate(stage_sizes):
        for j in range(n_blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            x = _bottleneck(x, params[f"BottleneckBlock_{idx}"], stride, compute)
            idx += 1
    feat = jnp.mean(x, axis=(1, 2))
    return jnp.matmul(feat, params["head"]["kernel"], precision=jax.lax.Precision.HIGHEST) + params["head"]["bias"]
