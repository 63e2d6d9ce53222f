"""Bragg-peak extraction from segmentation logits (device side).

Closes the loop the reference's own packaging names as its mission —
"Save PeakNet inference results to CXI" (reference ``setup.py:11``; SFX
keyword at ``setup.py:15``) — but which exists nowhere in its code.

Pipeline: PeakNet U-Net logits ``[N, H, W, 1]`` -> :func:`find_peaks`
(device-side, jittable: sigmoid threshold + local-maximum test +
top-K by score, fixed shapes so pjit never recompiles) -> host-side
:class:`CxiWriter` appending the peak lists per event in the CXI layout
(``/entry_1/result_1/peakXPosRaw`` et al.) that downstream SFX indexing
tools (CrystFEL and friends) consume.

What ``top_k`` runs over, and why that is exact. A pixel survives the
local-maximum test only if no neighbour within Chebyshev distance
``min_distance`` = d beats it on (probability, earlier raster index), a
strict total order: of two pixels within d of each other one beats the
other, so two survivors are never within d, so every ``(d+1) x (d+1)``
block of the map holds AT MOST ONE. ``top_k`` therefore runs over one
candidate per block (15,104 a panel row for epix10k2M at d = 2, not
135,168 pixels) and loses nothing; equal scores are then put back in
raster order, so the peaks, their scores and their ORDER are those of
``top_k`` over the whole raster-flat map (``tests/dense_peaks.py``).

TPU notes. Every access of the test is a STATIC slice (``lax.slice``, or a
reshape of major axes and a plain slice), never a strided ``jnp`` index:
``x[ry::b, rx::b]`` traces to a gather, and XLA then fetches each phase a
row at a time (nine gathers of 15,600 rows a step on the epix10k2M cell,
1.35 ms, behind 1.73 ms of ``depth_to_space``, relayout and pad passes:
my chip runs, PR 41). The plain form (:func:`_local_maxima`) is unrolled
shifted comparisons with static tie-breaks, elementwise on per-block
slabs, batch on the lanes; XLA fuses the window and the block reduction
into one kernel (0.55 ms there), but only behind copies that bring the
map into that shape. Where the logits come PACKED from a space-to-depth
head (``s2d`` > 1) and the TPU takes the shape, the test is
``ops/peak_nms.packed_local_maxima``: one Pallas pass over the map in the
layout the head wrote it in (0.34 ms). Both leave one candidate per block
with the batch on the lanes, the operand layout XLA's TopK runs fastest on
(1.48 ms; batch on the sublanes 2.99); ``top_k`` gives a FIXED peak-count
output (padded, with a validity count) so a streaming consumer never sees
a shape change.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from psana_ray_tpu.ops import peak_nms


def find_peaks(
    logits: jax.Array,
    max_peaks: int = 128,
    threshold: float = 0.5,
    min_distance: int = 1,
    s2d: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Extract up to ``max_peaks`` peak centers from ``[N, H, W, 1]`` (or
    ``[N, H, W]``) segmentation logits.

    A pixel is a peak when its probability reaches ``threshold`` AND it is
    the maximum of its ``(2*min_distance+1)^2`` neighborhood (ties broken
    toward the first in raster order, matching the classic local-max rule).
    The ``max_peaks`` best come out by descending score, equal scores lower
    raster index first. ``top_k`` sees one candidate per
    ``(min_distance+1)^2`` block — a block holds at most one peak (module
    docstring), so the result is that of ``top_k`` over every pixel.

    ``s2d = r > 1`` takes the logits PACKED, ``[N, H/r, W/r, r*r]`` as a
    space-to-depth head computes them (``PeakNetUNetTPU.apply(...,
    packed=True)``: channel ``a*r + c`` of packed pixel ``(i, j)`` is pixel
    ``(r*i + a, r*j + c)``), and reads them in that layout: the
    full-resolution map is never formed. The result is, element for
    element, that of ``find_peaks(depth_to_space(logits, r))``; ``r`` is a
    static property of the model that made the logits, not a mode. On the
    TPU, whole lane tiles of packed rows go through one kernel
    (``ops/peak_nms``); everything else through the plain form below.

    Returns ``(yx, score, n)``: ``yx [N, max_peaks, 2]`` int32 row/col
    (padded entries are (-1,-1)), ``score [N, max_peaks]`` f32 probability
    (padded 0), ``n [N]`` int32 valid count. Fixed shapes — jit/pjit safe.
    """
    if s2d == 1 and logits.ndim == 4:
        logits = logits[..., 0]
    elif s2d > 1 and (logits.ndim != 4 or logits.shape[-1] != s2d * s2d):
        raise ValueError(
            f"packed logits are [N, H/{s2d}, W/{s2d}, {s2d * s2d}] (one class); "
            f"got {logits.shape}"
        )
    n_, w = logits.shape[0], logits.shape[2] * s2d
    # two named scopes, metadata only: ``nms`` (the local-max test, down to
    # one candidate per block) and ``topk`` (the K best per row) are found
    # again by name in a device trace, whatever XLA numbers its fusions
    with jax.named_scope("nms"):
        if jax.default_backend() == "tpu" and peak_nms.takes(logits.shape, s2d, min_distance):
            cand, where = peak_nms.packed_local_maxima(
                jax.nn.sigmoid(logits.astype(jnp.float32)),
                threshold=threshold, d=min_distance, r=s2d,
            )
        else:
            cand, where = _local_maxima(logits, threshold, min_distance, min_distance + 1, s2d)
    # whole super-blocks: where lcm(s2d, block) does not divide H or W, a
    # few more blocks than ceil(H / block) x ceil(W / block), all empty. A
    # row of ``cand`` is a row of blocks; its blocks stand in the order the
    # test leaves them in (not left to right where s2d > 1), which nothing
    # below depends on
    hb, wb = cand.shape[1:]
    with jax.named_scope("topk"):
        k = min(max_peaks, hb * wb)
        top, cidx = jax.lax.top_k(cand.reshape(n_, hb * wb), k)
        top_where = jnp.take_along_axis(where.reshape(n_, hb * wb), cidx, axis=1)
        # ``top_k`` orders equal scores by block-grid index, the dense form
        # by raster index; the two disagree only inside one row of blocks
        # (raster order there is by in-block row first). Every equal score
        # left out lies at or after the last kept candidate, so the kept set
        # can be wrong only in THAT candidate's block row: take the row
        # whole in place of its kept members and sort by (score, raster).
        top_by = cidx // wb
        cut_by = top_by[:, -1:]
        in_row = (jnp.arange(hb, dtype=jnp.int32)[None] == cut_by)[:, :, None]
        row_score = jnp.where(in_row, cand, 0.0).max(axis=1)
        row_where = jnp.where(in_row, where, 0).max(axis=1)
        score = jnp.concatenate([jnp.where(top_by == cut_by, 0.0, top), row_score], axis=1)
        raster = jnp.concatenate([top_where, row_where], axis=1)
        neg, raster = jax.lax.sort((-score, raster), dimension=1, num_keys=2)
        short = max(max_peaks - (k + wb), 0)
        score = jnp.pad(-neg[:, :max_peaks], ((0, 0), (0, short)))
        raster = jnp.pad(raster[:, :max_peaks], ((0, 0), (0, short)))
        valid = score > 0.0
        yy = jnp.where(valid, raster // w, -1).astype(jnp.int32)
        xx = jnp.where(valid, raster % w, -1).astype(jnp.int32)
        yx = jnp.stack([yy, xx], axis=-1)
        return yx, jnp.where(valid, score, 0.0), valid.sum(axis=1).astype(jnp.int32)


def _local_maxima(logits, threshold: float, d: int, b: int, r: int = 1):
    """``(score [N,Hb,Wb] f32, where [N,Hb,Wb] int32)`` of ``[N,H,W]`` logits
    (``r = 1``) or of the same map packed ``[N, H/r, W/r, r*r]``: each
    ``b x b`` block's surviving pixel — its probability and its raster
    index ``y*W + x`` (both 0 where the block has none). ``b = 1`` is the
    full-resolution map; ``b = d + 1`` loses nothing (module docstring).
    The grid is whole SUPER-BLOCKS of ``L = lcm(r, b)`` pixels a side:
    ``Hb = ceil(H / L) * L / b``; a block beyond the frame is empty. Row
    ``(L/b)*m + p`` holds block row ``p`` of super-block row ``m``, and in
    it block column ``q`` of every super-block, then the next ``q``.

    A pixel survives when its probability reaches ``threshold`` and no
    neighbour in its ``(2d+1)^2`` window beats it on (probability, earlier
    raster index): an earlier neighbour wins a tie, a later one does not —
    static per offset, exact where a float "prob - idx*eps" key would lose
    the tie-break to f32 rounding near 1.

    Every access is a static slice (module docstring). Row ``y = r*i + a``
    lies at packed row ``i``, sub-pixel ``a``; block row ``I = (L/b)*m + p``
    and in-block place ``iy`` put a neighbour at ``y + dy = L*m + (b*p + iy
    + dy)``: packed row ``(L/r)*m + (b*p + iy + dy) // r``, sub-pixel ``(b*p
    + iy + dy) % r``, and the same along x. So the packed map falls into
    ``(L/r)^2 * r^2`` PHASES (packed row and column mod ``L/r``, sub-pixel),
    cut once, and every neighbour of every place of every block is a
    contiguous slab of one phase, shifted by whole super-blocks."""
    n_, hp, wp = logits.shape[:3]
    h, w = hp * r, wp * r
    sup = math.lcm(r, b)  # L: a super-block is whole blocks AND whole packed pixels
    lr, nb = sup // r, sup // b
    mh, mw = -(-h // sup), -(-w // sup)
    # a border of ``edge`` packed pixels of -inf: out-of-frame neighbours
    # beat nothing and, where H or W is padded up to whole super-blocks,
    # never survive. ``reach``: whole super-blocks a neighbour may lie on.
    edge = -(-d // r)
    off = edge * r
    reach = (sup - 1 + d + off) // r // lr
    qh, qw = mh + reach, mw + reach
    # batch last: a slab is [rows, columns, N], the test elementwise on it
    prob = jax.nn.sigmoid(logits.astype(jnp.float32)).reshape(n_, hp, wp, r, r)
    pprob = jnp.pad(
        prob.transpose(1, 2, 3, 4, 0),
        ((edge, qh * lr - hp - edge), (edge, qw * lr - wp - edge), (0, 0), (0, 0), (0, 0)),
        constant_values=-jnp.inf,
    ).reshape(qh, lr, qw, lr, r, r, n_)

    @functools.cache
    def phase(qy, qx, a, c):  # lax.slice, never a strided jnp index: that is a gather
        lo = (0, qy, 0, qx, a, c, 0)
        hi = (qh, qy + 1, qw, qx + 1, a + 1, c + 1, n_)
        return jax.lax.slice(pprob, lo, hi).reshape(qh, qw, n_)

    @functools.cache
    def at(ty, tx):  # padded pixel (ty + L*m, tx + L*n) of every super-block (m, n)
        (py, a), (px, c) = divmod(ty, r), divmod(tx, r)
        sy, sx = py // lr, px // lr
        return phase(py % lr, px % lr, a, c)[sy : sy + mh, sx : sx + mw]

    corner = (
        jnp.arange(mh, dtype=jnp.int32)[:, None] * w + jnp.arange(mw, dtype=jnp.int32)
    )[:, :, None] * sup
    scores, wheres = [], []
    for p in range(nb):
        for q in range(nb):
            score = jnp.zeros((mh, mw, n_), jnp.float32)
            where = jnp.zeros((mh, mw, n_), jnp.int32)
            for iy in range(b * p, b * p + b):
                for ix in range(b * q, b * q + b):
                    cen = at(off + iy, off + ix)
                    beaten = jnp.zeros(cen.shape, dtype=bool)
                    for dy in range(-d, d + 1):
                        for dx in range(-d, d + 1):
                            if (dy, dx) != (0, 0):
                                sp = at(off + iy + dy, off + ix + dx)
                                beaten |= (sp >= cen) if (dy, dx) < (0, 0) else (sp > cen)
                    s = jnp.where((cen >= threshold) & ~beaten, cen, 0.0)
                    # at most one place of a block survives: max IS that one's score
                    score = jnp.maximum(score, s)
                    where = jnp.where(s > 0.0, corner + (iy * w + ix), where)
            scores.append(score)
            wheres.append(where)

    def grid(planes):  # [L/b * L/b] of [Mh, Mw, N] -> [N, Mh * L/b, L/b * Mw]
        return jnp.stack(planes, axis=1).transpose(3, 0, 1, 2).reshape(n_, mh * nb, nb * mw)

    return grid(scores), grid(wheres)


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
