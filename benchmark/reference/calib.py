"""Plain float32 detector calibration: the reference the fused Pallas
kernel is held to. ``where(mask, (raw - pedestal) / gain - baseline, 0)``
with the per-panel baseline the mean of the good background pixels
(``|x| < threshold`` and unmasked) — the standard LCLS chain with the
mean common-mode algorithm. Straight ``jax.numpy``; nothing of the
program is imported."""

import jax.numpy as jnp


def calibrate(raw, pedestal, gain, mask, threshold: float):
    """``raw [B,P,H,W]`` (any integer or float type), constants
    ``[P,H,W]`` -> float32 ``[B,P,H,W]``."""
    x = (raw.astype(jnp.float32) - pedestal.astype(jnp.float32)) / gain.astype(jnp.float32)
    good = mask != 0
    background = jnp.logical_and(jnp.abs(x) < threshold, good)
    total = jnp.sum(jnp.where(background, x, 0.0), axis=(-2, -1), keepdims=True)
    count = jnp.sum(background.astype(jnp.float32), axis=(-2, -1), keepdims=True)
    baseline = total / jnp.maximum(count, 1.0)
    return jnp.where(good, x - baseline, 0.0)
