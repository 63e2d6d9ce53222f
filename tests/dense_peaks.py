"""The DENSE form of ``find_peaks``, kept as the tests' oracle: the
local-maximum test at full resolution with the raster index carried as an
array, then ``lax.top_k`` over the raster-flat map (equal scores come out
lower raster index first). ``models/peaks.py`` ran exactly this until its
TopK was cut down to one candidate per block; whatever it does now has to
return the same ``(yx, score, n)``, element for element and in this order."""

import jax
import jax.numpy as jnp


def dense_local_maxima(logits, threshold: float, min_distance: int):
    """``(is_peak [N,H,W] bool, prob [N,H,W] f32)`` of ``[N,H,W]`` logits."""
    n_, h, w = logits.shape
    prob = jax.nn.sigmoid(logits.astype(jnp.float32))
    d = min_distance
    idx = jnp.arange(h * w, dtype=jnp.int32).reshape(1, h, w)
    pprob = jnp.pad(prob, ((0, 0), (d, d), (d, d)), constant_values=-jnp.inf)
    pidx = jnp.pad(idx, ((0, 0), (d, d), (d, d)), constant_values=h * w)
    beaten = jnp.zeros(prob.shape, dtype=bool)
    for dy in range(-d, d + 1):
        for dx in range(-d, d + 1):
            if dy == 0 and dx == 0:
                continue
            sp = pprob[:, d + dy : d + dy + h, d + dx : d + dx + w]
            si = pidx[:, d + dy : d + dy + h, d + dx : d + dx + w]
            beaten |= (sp > prob) | ((sp == prob) & (si < idx))
    return (prob >= threshold) & ~beaten, prob


def dense_find_peaks(logits, max_peaks: int = 128, threshold: float = 0.5, min_distance: int = 1):
    if logits.ndim == 4:
        logits = logits[..., 0]
    n_, h, w = logits.shape
    is_peak, prob = dense_local_maxima(logits, threshold, min_distance)
    flat_score = jnp.where(is_peak, prob, 0.0).reshape(n_, h * w)
    k = min(max_peaks, h * w)  # a map smaller than the cap: pad, as the outputs are
    score, idx = jax.lax.top_k(flat_score, k)
    score = jnp.pad(score, ((0, 0), (0, max_peaks - k)))
    idx = jnp.pad(idx, ((0, 0), (0, max_peaks - k)))
    valid = score > 0.0
    yy = jnp.where(valid, idx // w, -1).astype(jnp.int32)
    xx = jnp.where(valid, idx % w, -1).astype(jnp.int32)
    yx = jnp.stack([yy, xx], axis=-1)
    return yx, jnp.where(valid, score, 0.0), valid.sum(axis=1).astype(jnp.int32)
