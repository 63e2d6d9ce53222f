"""Fused Pallas calibration kernel: pedestal + gain + common-mode + mask.

The XLA path (:func:`psana_ray_tpu.ops.calib.calibrate`) materializes the
intermediate ``(raw - ped) / gain`` between the baseline reduction and its
application. This kernel fuses reduce-and-apply per panel inside VMEM.

Layout: panels are flattened to a ``[B*P, H, W]`` axis; each panel is
processed in ``nt`` row-tiles over a two-phase inner grid —

    grid = (P, B, 2, nt)   # phases: 0 = accumulate sum/count, 1 = apply

(panel-major so one panel's calibration constants keep their block index
across all B frames and stream from HBM once per batch)

with the running ``(sum, count)`` carried in SMEM scratch across grid steps
(TPU grids execute sequentially, so scratch persists per panel). When a
whole panel fits in VMEM (epix10k2M: 352x384 f32 = 528 KB -> nt == 1) the
phase-1 revisit hits the same block index, so Pallas skips the re-fetch DMA
and the kernel is a true single pass over HBM.

Tile heights are multiples of 32 rows (the u8 mask's sublane quantum) that
divide H exactly — out-of-range rows would corrupt the reduction.

On non-TPU backends the kernel runs in Pallas interpret mode, which keeps
the CPU test suite meaningful against the XLA reference implementation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# per-operand VMEM budget: 5 operands x double buffering should stay well
# under the ~16 MB scoped limit
_VMEM_TILE_BUDGET_BYTES = 10 * 1024 * 1024


def _pick_tile_rows(h: int, w: int, itemsize: int = 4) -> int:
    """Largest tile height that is a multiple of 32, divides h, and keeps
    5 double-buffered operand blocks inside the VMEM budget."""
    budget_rows = _VMEM_TILE_BUDGET_BYTES // (5 * 2 * w * itemsize)
    best = None
    for hb in range(32, h + 1, 32):
        if h % hb == 0 and hb <= budget_rows:
            best = hb
    if best is None:
        # h has no suitable multiple-of-32 divisor; fall back to the largest
        # divisor under budget (may be sublane-padded, still correct)
        for hb in range(1, h + 1):
            if h % hb == 0 and hb <= budget_rows:
                best = hb
    return best or min(h, max(1, budget_rows))


def _calib_kernel(raw_ref, ped_ref, gain_ref, mask_ref, out_ref, acc_ref, *, threshold: float):
    # compute stays in the raw dtype (f32); only the final store narrows
    # when out_dtype demotes (bf16 for model consumers halves the write)
    phase = pl.program_id(2)
    tile = pl.program_id(3)
    x = (raw_ref[0] - ped_ref[0]) / gain_ref[0]
    good_pix = mask_ref[0] != 0

    @pl.when(jnp.logical_and(phase == 0, tile == 0))
    def _reset():
        acc_ref[0] = 0.0
        acc_ref[1] = 0.0

    @pl.when(phase == 0)
    def _accumulate():
        bg = jnp.logical_and(jnp.abs(x) < threshold, good_pix)
        acc_ref[0] += jnp.sum(jnp.where(bg, x, jnp.zeros((), x.dtype)))
        acc_ref[1] += jnp.sum(bg.astype(x.dtype))
        out_ref[0] = jnp.zeros_like(x).astype(out_ref.dtype)  # keep the output block defined

    @pl.when(phase == 1)
    def _apply():
        baseline = acc_ref[0] / jnp.maximum(acc_ref[1], 1.0)
        out_ref[0] = jnp.where(good_pix, x - baseline, jnp.zeros((), x.dtype)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("threshold", "interpret", "out_dtype"))
def fused_calibrate(
    raw: jax.Array,
    pedestal: jax.Array,
    gain: jax.Array,
    mask: jax.Array,
    threshold: float = 10.0,
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """One-pass calibration: ``where(mask, (raw-ped)/gain - cm, 0)`` with the
    mean-algorithm common mode of :func:`calib.common_mode`.

    ``raw``: ``[B, P, H, W]`` (or ``[P, H, W]``, auto-batched);
    ``pedestal``/``gain``: ``[P, H, W]`` float; ``mask``: ``[P, H, W]``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    squeeze = raw.ndim == 3
    if squeeze:
        raw = raw[None]
    # promote integer ADUs to float — demoting the calibration constants
    # would truncate them (and integer SMEM accumulators would overflow)
    if not jnp.issubdtype(raw.dtype, jnp.floating):
        raw = raw.astype(jnp.float32)
    b, p, h, w = raw.shape
    pedestal = pedestal.astype(raw.dtype)
    gain = gain.astype(raw.dtype)

    hb = _pick_tile_rows(h, w, raw.dtype.itemsize)
    nt = h // hb

    flat_raw = raw.reshape(b * p, h, w)

    # grid order (panel, batch, ...): all B frames of one panel run
    # consecutively, so the panel's pedestal/gain/mask blocks keep the
    # same index across B steps and Pallas skips their re-fetch — the
    # calibration constants stream from HBM once per BATCH, not once per
    # frame (they are 2.25x the raw frame's bytes; this is the difference
    # between ~480 GB/s effective and the HBM roofline)
    def frame_idx(j, ib, phase, t):
        del phase
        return (ib * p + j, t, 0)

    def panel_idx(j, ib, phase, t):
        del ib, phase
        return (j, t, 0)

    out = pl.pallas_call(
        functools.partial(_calib_kernel, threshold=float(threshold)),
        grid=(p, b, 2, nt),
        in_specs=[
            pl.BlockSpec((1, hb, w), frame_idx),
            pl.BlockSpec((1, hb, w), panel_idx),
            pl.BlockSpec((1, hb, w), panel_idx),
            pl.BlockSpec((1, hb, w), panel_idx),
        ],
        out_specs=pl.BlockSpec((1, hb, w), frame_idx),
        out_shape=jax.ShapeDtypeStruct((b * p, h, w), out_dtype or raw.dtype),
        scratch_shapes=[pltpu.SMEM((2,), raw.dtype)],
        interpret=interpret,
        name="fused_calibrate",  # the kernel's name in a device trace
    )(flat_raw, pedestal, gain, mask)
    out = out.reshape(b, p, h, w)
    return out[0] if squeeze else out
