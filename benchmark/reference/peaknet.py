"""Plain float32 PeakNet-TPU forward pass: the reference for the
``peaknet_sfx_*`` configurations.

The U-Net of ``psana_ray_tpu/models/unet_tpu.py`` written out in straight
``jax.numpy`` / ``lax.conv`` over the same parameter tree, float32
throughout, no kernels, no batching tricks: 2x2 space-to-depth stem;
encoder levels of two 3x3 convolutions (each followed by the folded
per-channel affine and SiLU) and a stride-2 3x3 convolution down;
a bottleneck block; decoder levels of nearest 2x upsampling, a 3x3
convolution, the skip merge (sum of two 3x3 convolutions), affine + SiLU,
a 3x3 convolution, affine + SiLU; a 1x1 head with bias emitting s2d^2
channels, unshuffled to one logit per original pixel. Convolutions run at
``Precision.HIGHEST``: on a TPU a float32 convolution otherwise runs in
lower precision.

``compute=jnp.bfloat16`` gives the precision yardstick: the same pass
with both operands of every convolution rounded to bfloat16 first and
float32 accumulation — what "bfloat16 compute" promises, and no less.

Departures from Wang et al. (arXiv:2303.15301), all the program's own and
listed in the configuration's ``assumed``: the space-to-depth stem and
depth-to-space head, widths (64,128,256,512), folded normalisation.
"""

import jax
import jax.numpy as jnp


def _conv(x, kernel, compute, stride=1):
    return jax.lax.conv_general_dilated(
        x.astype(compute).astype(jnp.float32), kernel.astype(compute).astype(jnp.float32),
        (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )


def _affine_silu(x, p):
    return jax.nn.silu(x * p["scale"] + p["bias"])


def _block(x, p, compute):
    x = _affine_silu(_conv(x, p["Conv_0"]["kernel"], compute), p["FrozenAffine_0"])
    return _affine_silu(_conv(x, p["Conv_1"]["kernel"], compute), p["FrozenAffine_1"])


def _space_to_depth(x, r):
    n, h, w, c = x.shape
    x = x.reshape(n, h // r, r, w // r, r, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // r, w // r, r * r * c)


def _depth_to_space(x, r):
    n, h, w, c = x.shape
    x = x.reshape(n, h, w, r, r, c // (r * r))
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h * r, w * r, c // (r * r))


def forward(params, x, s2d: int, compute=jnp.float32):
    """``x [N,H,W,1]`` float32 calibrated panels -> logits ``[N,H,W,1]``
    (the head stays float32 whatever ``compute``, as the model has it)."""
    levels = sum(1 for k in params if k.startswith("ConvBlock_"))
    x = _space_to_depth(x.astype(jnp.float32), s2d)
    skips = []
    for i in range(levels - 1):
        x = _block(x, params[f"ConvBlock_{i}"], compute)
        skips.append(x)
        x = _conv(x, params[f"Conv_{i}"]["kernel"], compute, stride=2)
    x = _block(x, params[f"ConvBlock_{levels - 1}"], compute)
    for j, skip in enumerate(reversed(skips)):
        x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
        x = _conv(x, params[f"Conv_{levels - 1 + j}"]["kernel"], compute)
        m = params[f"MergeBlock_{j}"]
        y = _conv(x, m["merge_up"]["kernel"], compute) + _conv(skip, m["merge_skip"]["kernel"], compute)
        y = _affine_silu(y, m["FrozenAffine_0"])
        x = _affine_silu(_conv(y, m["Conv_0"]["kernel"], compute), m["FrozenAffine_1"])
    y = _conv(x, params["logits"]["kernel"], jnp.float32) + params["logits"]["bias"]
    return _depth_to_space(y, s2d)
