"""Bragg-peak extraction from segmentation logits (device side).

Closes the loop the reference's own packaging names as its mission —
"Save PeakNet inference results to CXI" (reference ``setup.py:11``; SFX
keyword at ``setup.py:15``) — but which exists nowhere in its code.

Pipeline: PeakNet U-Net logits ``[N, H, W, 1]`` -> :func:`find_peaks`
(device-side, jittable: sigmoid threshold + 3x3 local-maximum test +
top-K by score, fixed shapes so pjit never recompiles) -> host-side
:class:`CxiWriter` appending the peak lists per event in the CXI layout
(``/entry_1/result_1/peakXPosRaw`` et al.) that downstream SFX indexing
tools (CrystFEL and friends) consume.

TPU notes: the peak test is pad + unrolled shifted comparisons (integer-
exact tie-breaks), all elementwise — XLA fuses the unrolled window into
one kernel; ``top_k`` gives a FIXED peak-count output (padded, with a
validity count) so a streaming consumer never sees a shape change.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def find_peaks(
    logits: jax.Array,
    max_peaks: int = 128,
    threshold: float = 0.5,
    min_distance: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Extract up to ``max_peaks`` peak centers from ``[N, H, W, 1]`` (or
    ``[N, H, W]``) segmentation logits.

    A pixel is a peak when its probability exceeds ``threshold`` AND it is
    the maximum of its ``(2*min_distance+1)^2`` neighborhood (ties broken
    toward the first in raster order, matching the classic local-max rule).

    Returns ``(yx, score, n)``: ``yx [N, max_peaks, 2]`` int32 row/col
    (padded entries are (-1,-1)), ``score [N, max_peaks]`` f32 probability
    (padded 0), ``n [N]`` int32 valid count. Fixed shapes — jit/pjit safe.
    """
    if logits.ndim == 4:
        logits = logits[..., 0]
    n_, h, w = logits.shape
    # two named scopes, metadata only: ``nms`` (the local-max test) and
    # ``topk`` (the K best per row) are found again by name in a device
    # trace, whatever XLA numbers its fusions
    with jax.named_scope("nms"):
        is_peak, prob = _local_maxima(logits, h, w, threshold, min_distance)
    with jax.named_scope("topk"):
        flat_score = jnp.where(is_peak, prob, 0.0).reshape(n_, h * w)
        score, idx = jax.lax.top_k(flat_score, max_peaks)
        valid = score > 0.0
        yy = jnp.where(valid, idx // w, -1).astype(jnp.int32)
        xx = jnp.where(valid, idx % w, -1).astype(jnp.int32)
        yx = jnp.stack([yy, xx], axis=-1)
        return yx, jnp.where(valid, score, 0.0), valid.sum(axis=1).astype(jnp.int32)


def _local_maxima(logits, h: int, w: int, threshold: float, min_distance: int):
    """``(is_peak [N,H,W] bool, prob [N,H,W] f32)`` of ``[N,H,W]`` logits."""
    prob = jax.nn.sigmoid(logits.astype(jnp.float32))
    # Local-max test with exact raster-order tie-break: a pixel survives
    # unless some window neighbor beats it on (prob, earlier raster index).
    # Unrolled shifted comparisons (static (2d+1)^2-1 slices, XLA fuses the
    # whole stack into one elementwise kernel) — exact where a float
    # "prob - idx*eps" key would lose the tie-break to f32 rounding near 1.
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


def peak_metrics(
    pred_yx: np.ndarray,
    pred_n: np.ndarray,
    truth: Sequence[np.ndarray],
    tolerance: float = 3.0,
    min_amplitude: float = 0.0,
) -> dict:
    """Recall / precision of predicted peaks against planted ground truth.

    ``pred_yx [N, max_peaks, 2]`` / ``pred_n [N]`` are :func:`find_peaks`
    outputs in panel-as-batch layout (row i = one panel); ``truth`` is one
    ``[n, 4]`` array of ``(panel, cy, cx, amplitude)`` rows PER PANEL-ROW
    of the predictions (pre-split by panel — see
    ``SyntheticSource.event_with_truth`` for the per-event form).

    Greedy one-to-one matching: each truth peak claims the nearest
    still-unclaimed prediction within ``tolerance`` pixels. ``recall`` =
    matched truth / truth, ``precision`` = matched predictions /
    predictions. ``min_amplitude`` drops truth peaks too weak for the
    label policy under evaluation (sub-threshold plants are unknowable to
    a model trained on thresholded labels); predictions that land on an
    IGNORED plant are excluded from the precision denominator too — a
    correct detection of a weak plant is neither a hit nor a false
    positive (the standard ignore-region convention of detection
    metrics)."""

    def _claim(centers, preds, taken):
        claimed = 0
        for cy, cx in centers:
            d = np.hypot(preds[:, 0] - cy, preds[:, 1] - cx)
            d[taken] = np.inf
            j = int(np.argmin(d))
            if d[j] <= tolerance:
                taken[j] = True
                claimed += 1
        return claimed

    n_truth = n_matched = n_pred = 0
    for i, t in enumerate(truth):
        k = int(pred_n[i])
        preds = np.asarray(pred_yx[i][:k], np.float32)
        t = np.asarray(t, np.float32).reshape(-1, 4)
        scored = t[:, 3] >= min_amplitude
        n_truth += int(scored.sum())
        if k == 0:
            continue
        taken = np.zeros(k, bool)
        n_matched += _claim(t[scored][:, 1:3], preds, taken)
        ignored_claims = _claim(t[~scored][:, 1:3], preds, taken)
        n_pred += k - ignored_claims
    return {
        "recall": n_matched / max(n_truth, 1),
        "precision": n_matched / max(n_pred, 1),
        "n_truth": n_truth,
        "n_pred": n_pred,
        "n_matched": n_matched,
    }


def split_truth_by_panel(truth: np.ndarray, n_panels: int) -> list:
    """One event's ``[n, 4] (panel, cy, cx, amp)`` truth -> per-panel list
    (panel-as-batch layout, matching ``panels_to_nhwc(.., 'batch')``)."""
    truth = np.asarray(truth, np.float32).reshape(-1, 4)
    return [truth[truth[:, 0] == p] for p in range(n_panels)]


# Host-side CXI layer (writer, readers, merge tool): moved to the
# jax-free :mod:`psana_ray_tpu.cxi` so the merge CLI and analysis-host
# readers need no jax/flax import; re-exported here for compatibility.
from psana_ray_tpu.cxi import (  # noqa: E402,F401
    CxiWriter,
    PeakSet,
    merge_cxi,
    merge_cxi_main,
    read_cxi_peaks,
    read_cxi_peaksets,
    unpad_peaks,
)
