"""Fused Pallas inference path for ResNet bottleneck stages.

Why this exists: the flax ResNet-50 forward is ~110 HLO ops, each
round-tripping its activations through HBM. Each bottleneck block here is
ONE ``pallas_call`` fusing conv1x1 -> affine -> silu -> conv3x3(stride) ->
affine -> silu -> conv1x1 -> affine -> (+residual/projection) -> silu, so
the whole network is ~20 kernels instead of ~110 ops and a block's
intermediates never leave VMEM.

Kernel design (TPU-first, see /opt/skills/guides/pallas_guide.md):
- grid over the batch; per step the frame's activations are DMA'd
  HBM->VMEM once, all compute happens in VMEM, one DMA writes the result;
- weights live in VMEM *scratch*, DMA'd from HBM only on the first grid
  step (TPU grids are sequential, scratch persists across steps) — no
  per-step weight traffic and no double-buffer blowup for stage-4's 11 MB
  of weights;
- the 3x3 conv is nine shifted matmuls accumulated in f32 (no im2col
  materialization); all matmuls are MXU-shaped [rows, Cin] @ [Cin, Cout]
  in bfloat16 with f32 accumulation;
- strided (s=2) taps use a reshape + mask + sum downsample —
  ``vector.extract_strided_slice`` does not lower on TPU Mosaic and lane
  slicing requires 128-alignment, so plain ``y[::2, ::2]`` is not an
  option inside a kernel;
- row-chunked compute bounds the f32 accumulators so each kernel's VMEM
  footprint stays under the ~16 MB budget (stage-4 first block is the
  tight one: ~14 MB of weights + activations).

Numerics match ``ResNetClassifier(norm='frozen')`` (inference-form affine
normalization) to bfloat16 tolerance; equivalence is tested on CPU in
interpret mode (tests/test_pallas_resnet.py).

The reference has no model code at all (its consumers are opaque torch
loops, SURVEY.md §2); this is the TPU realization of BASELINE config 4.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BF16 = jnp.bfloat16
# leave headroom under the ~16 MB/core VMEM for compiler-managed buffers
_VMEM_BUDGET = 13 * 1024 * 1024


def _downsample(a: jax.Array, s: int, r: int, c: int, ch: int) -> jax.Array:
    """``a[::s, ::s]`` for ``a = [s*r, s*c, ch]`` via reshape+mask+sum
    (strided vector slices do not lower on Mosaic; summing against zeros
    is exact)."""
    if s == 1:
        return a
    a = a.reshape(r, s, s * c, ch)
    rowsel = jax.lax.broadcasted_iota(jnp.int32, (1, s, 1, 1), 1) == 0
    a = jnp.sum(jnp.where(rowsel, a, jnp.zeros((), a.dtype)), axis=1)
    a = a.reshape(r, c, s, ch)
    colsel = jax.lax.broadcasted_iota(jnp.int32, (1, 1, s, 1), 2) == 0
    return jnp.sum(jnp.where(colsel, a, jnp.zeros((), a.dtype)), axis=2)


def _pick_chunk(n_rows: int, bytes_per_row: int, budget: int) -> int:
    """Largest divisor of ``n_rows`` whose f32 accumulator fits ``budget``."""
    best = 1
    for c in range(1, n_rows + 1):
        if n_rows % c == 0 and c * bytes_per_row <= budget:
            best = c
    return best


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def _col_mask(a: jax.Array, rows: int, cols_buf: int, cols_true: int, ch: int):
    """Zero columns >= cols_true of ``a = [rows, cols_buf, ch]``."""
    if cols_buf == cols_true:
        return a
    keep = jax.lax.broadcasted_iota(jnp.int32, (1, cols_buf, 1), 1) < cols_true
    return jnp.where(keep, a, jnp.zeros((), a.dtype))


def _ypad_dims(h: int, wib: int, s: int):
    """y1 pad-buffer extents. At stride 2 the buffer carries two extra
    rows/cols so the 2x2 polyphase extraction (which reads rows a + 2*r,
    r < h/2+2, a in {0,1}) stays in bounds."""
    extra = 2 if s == 2 else 0
    return h + s + 1 + extra, wib + s + 1 + extra


def _bottleneck_kernel(
    *refs, cin, f, cout, h, wi, wib, w_dma, stride, proj, cr, cro, cpp=1, emit="full"
):
    """See module docstring. Alignment note: sliced HBM<->VMEM DMAs require
    the last dim to be a multiple of 128 and the second-to-last a multiple
    of 8 (Mosaic tiling), so channel dims are zero-padded to 128 and width
    dims to 8 — with zeroed affine rows on padded channels and explicit
    column masks, padding is numerically exact, not approximate.

    ``emit='full'`` runs the whole block; ``emit='y2'`` is the FRONT half
    of a split block (conv1x1 -> affine -> silu -> conv3x3 -> affine ->
    silu, output y2) used when a block's resident weights don't fit VMEM
    alongside its activations (stage-4 projection block: w1+w2+w3+wp is
    ~12 MB); :func:`_back_kernel` finishes (1x1 + residual + silu)."""
    s = stride
    ho, wo = h // s, wi // s  # true output extents
    wo_buf = _up(wo, 8)
    refs = list(refs)
    sem = refs.pop()
    pp_v = refs.pop() if s == 2 else None  # polyphase planes scratch
    if emit == "y2":
        (x_h, w1_h, w2_h, s1, b1, s2, b2, out_h,
         x_v, w1_v, w2_v, y1p_v, out_v) = refs
        w3_h = wp_h = w3_v = wp_v = s3 = b3 = sp = bp = None
    elif proj:
        (x_h, w1_h, w2_h, w3_h, wp_h, s1, b1, s2, b2, s3, b3, sp, bp, out_h,
         x_v, w1_v, w2_v, w3_v, wp_v, y1p_v, out_v) = refs
    else:
        (x_h, w1_h, w2_h, w3_h, s1, b1, s2, b2, s3, b3, out_h,
         x_v, w1_v, w2_v, w3_v, y1p_v, out_v) = refs
        wp_h = wp_v = sp = bp = None

    b = pl.program_id(0)

    @pl.when(b == 0)
    def _load_weights():
        pairs = ((w1_h, w1_v), (w2_h, w2_v))
        if emit == "full":
            pairs += ((w3_h, w3_v),) + (((wp_h, wp_v),) if proj else ())
        for src, dst in pairs:
            cp = pltpu.make_async_copy(src, dst, sem)
            cp.start()
            cp.wait()

    if wib > w_dma:  # buffer wider than the incoming array: zero the slack
        x_v[:] = jnp.zeros((h, wib, cin), _BF16)
    cp = pltpu.make_async_copy(x_h.at[b], x_v.at[:, 0:w_dma], sem)
    cp.start()
    cp.wait()

    # y1 = silu(affine1(x @ w1)), written into a zero-bordered pad buffer
    # so the 3x3 taps never branch on boundaries. XLA SAME padding for a
    # 3-tap kernel is (1,1) at stride 1 but (0,1) at stride 2 (pad_total =
    # (Ho-1)*s + k - H); `off` shifts the tap origin accordingly, and the
    # buffer carries extra trailing rows/cols so strided tap slices (which
    # over-read rows/cols the downsample or column mask discards) stay in
    # bounds.
    #
    # Row-chunk loops are lax.fori_loop, not Python-unrolled: Mosaic's
    # scoped-vmem stack allocator charges each unrolled iteration's
    # temporaries separately (an unrolled stage-3 block blows the 16 MB
    # limit), while a fori body's stack is reused across iterations. The
    # dynamic chunk offsets index the LEADING (row) dim of 3D VMEM refs —
    # untiled, so no sublane/lane alignment constraint applies.
    off = 0 if s == 1 else 1
    ypr, ypc = _ypad_dims(h, wib, s)
    y1p_v[:] = jnp.zeros((ypr, ypc, f), _BF16)

    def _y1_body(i, carry):
        r0 = i * cr
        xa = x_v[pl.ds(r0, cr)]  # [cr, wib, cin]
        acc = jnp.dot(
            xa.reshape(cr * wib, cin), w1_v[:], preferred_element_type=jnp.float32
        )
        y1 = jax.nn.silu(acc * s1[:] + b1[:]).astype(_BF16)
        # cols >= wi would otherwise hold silu(bias) != 0 and leak into the
        # 3x3 taps at the true right edge — mask them to honor SAME padding
        y1 = _col_mask(y1.reshape(cr, wib, f), cr, wib, wi, f)
        y1p_v[pl.ds(1 + r0, cr), 1:1 + wib] = y1
        return carry

    jax.lax.fori_loop(0, h // cr, _y1_body, 0, unroll=False)

    if s == 2:
        # 2x2 polyphase split of the pad buffer: pp[a, c][r, q] =
        # y1p[2r + a, 2q + c]. Built ONCE (4 strided extractions); every
        # strided tap then reads a PLAIN slice of its phase plane instead
        # of re-running the reshape-mask-sum downsample per tap (10x per
        # block: measured 2x on the stride-2 projection blocks).
        hp2, wp2 = h // 2 + 2, wib // 2 + 2

        def _pp_body(i, carry):
            # all four phases inside ONE loop body: separate per-phase
            # loops would each be charged their own scoped-vmem stack
            r0 = i * cpp
            for a in (0, 1):
                for c in (0, 1):
                    raw = y1p_v[pl.ds(a + 2 * r0, 2 * cpp), c:c + 2 * wp2]
                    pp_v[a, c, pl.ds(r0, cpp)] = _downsample(raw, 2, cpp, wp2, f)
            return carry

        jax.lax.fori_loop(0, hp2 // cpp, _pp_body, 0, unroll=False)

    # conv3x3(stride) + affine + silu, conv1x1 + affine, residual, silu —
    # chunked over output rows to bound the f32 accumulators
    def _out_body(i, carry):
        ro = i * cro
        acc2 = jnp.zeros((cro * wo_buf, f), jnp.float32)
        for t in range(9):
            dy, dx = divmod(t, 3)
            if s == 1:
                patch = y1p_v[pl.ds(ro + dy, cro), dx:dx + wo_buf]
            else:
                ar, radd = (dy + off) % 2, (dy + off) // 2
                ac, cadd = (dx + off) % 2, (dx + off) // 2
                patch = pp_v[ar, ac, pl.ds(ro + radd, cro), cadd:cadd + wo_buf]
            acc2 += jnp.dot(
                patch.reshape(cro * wo_buf, f), w2_v[t],
                preferred_element_type=jnp.float32,
            )
        y2 = jax.nn.silu(acc2 * s2[:] + b2[:]).astype(_BF16)
        if emit == "y2":
            y2m = _col_mask(y2.reshape(cro, wo_buf, f), cro, wo_buf, wo, f)
            out_v[pl.ds(ro, cro)] = y2m
            return carry
        y3 = jnp.dot(y2, w3_v[:], preferred_element_type=jnp.float32)
        y3 = y3 * s3[:] + b3[:]
        if proj:
            xs = _downsample(
                x_v[pl.ds(s * ro, s * cro), 0:s * wo_buf], s, cro, wo_buf, cin
            )
            res = jnp.dot(
                xs.reshape(cro * wo_buf, cin), wp_v[:],
                preferred_element_type=jnp.float32,
            )
            res = res * sp[:] + bp[:]
        else:
            xr = x_v[pl.ds(ro, cro), 0:wo_buf]
            if cin != cout:
                # toy configs only (cout < 128-lane pad): unaligned lane
                # slice — fine in interpret mode, unsupported by Mosaic.
                # Real ResNet-50 identity blocks always have cin == cout.
                xr = jax.lax.slice(xr, (0, 0, 0), (cro, wo_buf, cout))
            res = xr.reshape(cro * wo_buf, cout).astype(jnp.float32)
        out = jax.nn.silu(y3 + res).astype(_BF16)
        out = _col_mask(out.reshape(cro, wo_buf, cout), cro, wo_buf, wo, cout)
        out_v[pl.ds(ro, cro)] = out
        return carry

    jax.lax.fori_loop(0, ho // cro, _out_body, 0, unroll=False)

    cp = pltpu.make_async_copy(out_v, out_h.at[b], sem)
    cp.start()
    cp.wait()


def _back_kernel(
    *refs, cin, f, cout, h, wib, w_dma, stride, proj, ho, wo, wo_buf, cb
):
    """Back half of a split bottleneck: y2 @ w3 -> affine -> (+ residual /
    projection) -> silu. Resident weights here are only w3 (+wp), so the
    two halves each fit VMEM where the fused kernel cannot."""
    s = stride
    if proj:
        (y2_h, x_h, w3_h, wp_h, s3, b3, sp, bp, out_h,
         y2_v, x_v, w3_v, wp_v, out_v, sem) = refs
    else:
        (y2_h, x_h, w3_h, s3, b3, out_h, y2_v, x_v, w3_v, out_v, sem) = refs
        wp_h = wp_v = sp = bp = None

    b = pl.program_id(0)

    @pl.when(b == 0)
    def _load_weights():
        for src, dst in ((w3_h, w3_v),) + (((wp_h, wp_v),) if proj else ()):
            cp = pltpu.make_async_copy(src, dst, sem)
            cp.start()
            cp.wait()

    if wib > w_dma:
        x_v[:] = jnp.zeros((h, wib, cin), _BF16)
    cp = pltpu.make_async_copy(x_h.at[b], x_v.at[:, 0:w_dma], sem)
    cp.start()
    cp.wait()
    cp = pltpu.make_async_copy(y2_h.at[b], y2_v, sem)
    cp.start()
    cp.wait()

    def _body(i, carry):
        ro = i * cb
        y2c = y2_v[pl.ds(ro, cb)].reshape(cb * wo_buf, f)
        y3 = jnp.dot(y2c, w3_v[:], preferred_element_type=jnp.float32)
        y3 = y3 * s3[:] + b3[:]
        if proj:
            xs = _downsample(
                x_v[pl.ds(s * ro, s * cb), 0:s * wo_buf], s, cb, wo_buf, cin
            )
            res = jnp.dot(
                xs.reshape(cb * wo_buf, cin), wp_v[:],
                preferred_element_type=jnp.float32,
            )
            res = res * sp[:] + bp[:]
        else:
            xr = x_v[pl.ds(ro, cb), 0:wo_buf]
            if cin != cout:
                xr = jax.lax.slice(xr, (0, 0, 0), (cb, wo_buf, cout))
            res = xr.reshape(cb * wo_buf, cout).astype(jnp.float32)
        out = jax.nn.silu(y3 + res).astype(_BF16)
        out = _col_mask(out.reshape(cb, wo_buf, cout), cb, wo_buf, wo, cout)
        out_v[pl.ds(ro, cb)] = out
        return carry

    jax.lax.fori_loop(0, ho // cb, _body, 0, unroll=False)

    cp = pltpu.make_async_copy(out_v, out_h.at[b], sem)
    cp.start()
    cp.wait()


def _pad_to(a: jax.Array, axis: int, target: int) -> jax.Array:
    if a.shape[axis] == target:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, target - a.shape[axis])
    return jnp.pad(a, pads)


def fused_bottleneck(
    x: jax.Array,   # [B, H, W_dma, Cin] — W_dma multiple of 8; cols >= w_true zero
    w1: jax.Array,  # [cin, f]        bf16
    w2: jax.Array,  # [9, f, f]       bf16 (3x3 taps row-major)
    w3: jax.Array,  # [f, 4f]         bf16
    affines,        # (s1,b1,s2,b2,s3,b3[,sp,bp]) each [1, ch] f32
    wp: Optional[jax.Array] = None,  # [cin, 4f] bf16 when projecting
    stride: int = 1,
    w_true: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One bottleneck block as a single pallas_call. Returns
    ``[B, H/s, up(w_true/s, 8), 4f]`` with columns past ``w_true/s`` zero
    (carry ``w_true`` through a chain of blocks; see resnet_fused_infer)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bsz, h, w_dma, cin_x = x.shape
    wi = w_true if w_true is not None else w_dma
    cin_true, f_true = w1.shape
    cout = w3.shape[1]
    proj = wp is not None
    s = stride
    ho, wo = h // s, wi // s
    wo_buf = _up(wo, 8)
    wib = max(_up(wi, 8), s * wo_buf)
    assert w_dma <= wib and w_dma % 8 == 0, (w_dma, wib)

    # zero-pad channel dims to the 128-lane quantum (exact: padded weight
    # rows/affine entries are zero, padded activations masked in-kernel)
    cin = _up(cin_x, 128)
    f = _up(f_true, 128)
    x = _pad_to(x.astype(_BF16), 3, cin)
    w1 = _pad_to(_pad_to(w1, 0, cin), 1, f)
    w2 = _pad_to(_pad_to(w2, 1, f), 2, f)
    w3 = _pad_to(w3, 0, f)
    s1, b1, s2, b2, s3, b3, *rest = affines
    s1, b1 = _pad_to(s1, 1, f), _pad_to(b1, 1, f)
    s2, b2 = _pad_to(s2, 1, f), _pad_to(b2, 1, f)
    affines = (s1, b1, s2, b2, s3, b3, *rest)
    if proj:
        wp = _pad_to(wp, 0, cin)

    ypr, ypc = _ypad_dims(h, wib, s)
    hp2, wp2 = h // 2 + 2, wib // 2 + 2  # polyphase plane extents (s == 2)
    pp_bytes = 4 * hp2 * wp2 * f * 2 if s == 2 else 0
    fixed = (
        h * wib * cin * 2
        + ypr * ypc * f * 2
        + pp_bytes
        + ho * wo_buf * cout * 2
        + w1.size * 2 + w2.size * 2 + w3.size * 2
        + (wp.size * 2 if proj else 0)
    )
    # per-row live-set estimates for one fori iteration (f32 accumulator +
    # bf16 activation temps in the y1 loop; acc2/y3/res f32s + patch/out
    # bf16 temps in the out loop) — the loop body's stack is reused across
    # iterations, so only ONE iteration's temps must fit the budget
    budget = max(256 * 1024, _VMEM_BUDGET - fixed)
    cr = _pick_chunk(h, wib * f * 8, budget)
    cro = _pick_chunk(ho, wo_buf * (8 * f + 10 * cout), budget)
    # x4: all four polyphase extractions run in one loop body
    cpp = _pick_chunk(hp2, wp2 * f * 48, budget) if s == 2 else 1

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)

    if fixed > _VMEM_BUDGET - (1 << 20):
        # resident weights + activations can't share VMEM with any useful
        # temp budget (stage-4 projection: w1+w2+w3+wp ~12 MB): run the
        # block as TWO kernels, each holding only its half of the weights
        front = functools.partial(
            _bottleneck_kernel,
            cin=cin, f=f, cout=cout, h=h, wi=wi, wib=wib, w_dma=w_dma,
            stride=s, proj=proj, cr=cr, cro=cro, cpp=cpp, emit="y2",
        )
        front_scratch = [
            pltpu.VMEM((h, wib, cin), _BF16),
            pltpu.VMEM(w1.shape, _BF16),
            pltpu.VMEM(w2.shape, _BF16),
            pltpu.VMEM((ypr, ypc, f), _BF16),
            pltpu.VMEM((ho, wo_buf, f), _BF16),
        ]
        if s == 2:
            front_scratch.append(pltpu.VMEM((2, 2, hp2, wp2, f), _BF16))
        front_scratch.append(pltpu.SemaphoreType.DMA)
        y2 = pl.pallas_call(
            front,
            grid=(bsz,),
            in_specs=[any_spec] * 3 + [vmem] * 4,
            out_specs=any_spec,
            out_shape=jax.ShapeDtypeStruct((bsz, ho, wo_buf, f), _BF16),
            scratch_shapes=front_scratch,
            interpret=interpret,
            name="bottleneck_front",
        )(x, w1, w2, s1, b1, s2, b2)

        back_fixed = (
            ho * wo_buf * f * 2 + h * wib * cin * 2 + ho * wo_buf * cout * 2
            + w3.size * 2 + (wp.size * 2 if proj else 0)
        )
        cb = _pick_chunk(
            ho,
            wo_buf * (2 * f + 10 * cout),
            max(256 * 1024, _VMEM_BUDGET - back_fixed),
        )
        back = functools.partial(
            _back_kernel,
            cin=cin, f=f, cout=cout, h=h, wib=wib, w_dma=w_dma,
            stride=s, proj=proj, ho=ho, wo=wo, wo_buf=wo_buf, cb=cb,
        )
        back_ops = [y2, x, w3] + ([wp] if proj else [])
        back_ops += [s3, b3, *rest] if proj else [s3, b3]
        back_scratch = [
            pltpu.VMEM((ho, wo_buf, f), _BF16),
            pltpu.VMEM((h, wib, cin), _BF16),
            pltpu.VMEM(w3.shape, _BF16),
        ]
        if proj:
            back_scratch.append(pltpu.VMEM(wp.shape, _BF16))
        back_scratch += [
            pltpu.VMEM((ho, wo_buf, cout), _BF16),
            pltpu.SemaphoreType.DMA,
        ]
        return pl.pallas_call(
            back,
            grid=(bsz,),
            in_specs=[any_spec] * (4 if proj else 3) + [vmem] * (4 if proj else 2),
            out_specs=any_spec,
            out_shape=jax.ShapeDtypeStruct((bsz, ho, wo_buf, cout), _BF16),
            scratch_shapes=back_scratch,
            interpret=interpret,
            name="bottleneck_back",
        )(*back_ops)

    kernel = functools.partial(
        _bottleneck_kernel,
        cin=cin, f=f, cout=cout, h=h, wi=wi, wib=wib, w_dma=w_dma,
        stride=s, proj=proj, cr=cr, cro=cro, cpp=cpp,
    )
    n_aff = 8 if proj else 6
    in_specs = [any_spec] * (5 if proj else 4) + [vmem] * n_aff
    operands = [x, w1, w2, w3] + ([wp] if proj else [])
    operands += list(affines)

    scratch = [
        pltpu.VMEM((h, wib, cin), _BF16),
        pltpu.VMEM(w1.shape, _BF16),
        pltpu.VMEM(w2.shape, _BF16),
        pltpu.VMEM(w3.shape, _BF16),
    ]
    if proj:
        scratch.append(pltpu.VMEM(wp.shape, _BF16))
    scratch += [
        pltpu.VMEM((ypr, ypc, f), _BF16),
        pltpu.VMEM((ho, wo_buf, cout), _BF16),
    ]
    if s == 2:
        scratch.append(pltpu.VMEM((2, 2, hp2, wp2, f), _BF16))
    scratch.append(pltpu.SemaphoreType.DMA)

    return pl.pallas_call(
        kernel,
        grid=(bsz,),
        in_specs=in_specs,
        out_specs=any_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, ho, wo_buf, cout), _BF16),
        scratch_shapes=scratch,
        interpret=interpret,
        name="bottleneck",
    )(*operands)


def _affine_pair(p, ch):
    return (
        p["scale"].astype(jnp.float32).reshape(1, ch),
        p["bias"].astype(jnp.float32).reshape(1, ch),
    )


def _block_params(bp):
    """Extract one BottleneckBlock's arrays from its flax param subtree
    (``ResNetClassifier(norm='frozen')`` layout, models/resnet.py)."""
    w1 = bp["Conv_0"]["kernel"].astype(_BF16)  # [1,1,cin,f]
    w2 = bp["Conv_1"]["kernel"].astype(_BF16)  # [3,3,f,f]
    w3 = bp["Conv_2"]["kernel"].astype(_BF16)  # [1,1,f,4f]
    cin, f = w1.shape[2], w1.shape[3]
    cout = w3.shape[3]
    w1 = w1.reshape(cin, f)
    w2 = w2.reshape(9, f, f)
    w3 = w3.reshape(f, cout)
    aff = (
        *_affine_pair(bp["FrozenAffine_0"], f),
        *_affine_pair(bp["FrozenAffine_1"], f),
        *_affine_pair(bp["FrozenAffine_2"], cout),
    )
    wp = None
    if "proj" in bp:
        wp = bp["proj"]["kernel"].astype(_BF16).reshape(cin, cout)
        aff = aff + _affine_pair(bp["proj_norm"], cout)
    return w1, w2, w3, aff, wp


def resnet_fused_infer(
    variables,
    x: jax.Array,
    stage_sizes=(3, 4, 6, 3),
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused-forward equivalent of
    ``ResNetClassifier(stage_sizes, norm='frozen').apply(variables, x)``.

    Stem, pool, and head stay XLA (a handful of ops); every bottleneck
    block is one pallas_call. ``x``: [B, H, W, C] (NHWC panels, see
    models/heads.panels_to_nhwc).
    """
    from flax.core import meta

    # the fused stage pipeline needs every strided stage's input to keep
    # >= 2 rows (stem+pool divide by 4, each stage after the first by 2;
    # a 1-row input to a stride-2 stage means 0-row polyphase planes ->
    # bogus kernel slices), so fall back to the plain flax forward below
    # that — those shapes are toy/test geometries, not detector panels
    min_extent = 4 * 2 ** (len(stage_sizes) - 1)
    if x.shape[1] < min_extent or x.shape[2] < min_extent:
        from psana_ray_tpu.models.resnet import ResNetClassifier

        pp = meta.unbox(variables)["params"]
        model = ResNetClassifier(
            stage_sizes=stage_sizes,
            num_classes=pp["head"]["kernel"].shape[-1],
            width=pp["stem"]["kernel"].shape[-1],
            norm="frozen",
        )
        return model.apply(variables, x)

    p = meta.unbox(variables)["params"]
    x = x.astype(_BF16)

    # Named scopes (stem, stage1.., head) are metadata on the compiled
    # ops: a device trace finds a stage again by name.
    # stem: conv7x7/2 + affine + silu + maxpool3x3/2 (XLA; ~4 ops)
    with jax.named_scope("stem"):
        y = jax.lax.conv_general_dilated(
            x, p["stem"]["kernel"].astype(_BF16), (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        y = y * p["stem_norm"]["scale"].astype(_BF16) + p["stem_norm"]["bias"].astype(_BF16)
        y = jax.nn.silu(y)
        y = jax.lax.reduce_window(
            y, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
        )

        # width alignment for the kernels' DMA constraints: pad W to a
        # multiple of 8 once here; blocks carry (and re-zero) the padding
        # thereafter
        w_true = y.shape[2]
        y = _pad_to(y, 2, _up(w_true, 8))

    idx = 0
    for i, n_blocks in enumerate(stage_sizes):
        with jax.named_scope(f"stage{i + 1}"):
            for j in range(n_blocks):
                stride = 2 if (i > 0 and j == 0) else 1
                w1, w2, w3, aff, wp = _block_params(p[f"BottleneckBlock_{idx}"])
                y = fused_bottleneck(
                    y, w1, w2, w3, aff, wp=wp, stride=stride, w_true=w_true,
                    interpret=interpret,
                )
                w_true //= stride
                idx += 1

    # GAP over TRUE extent: padded columns are exactly zero, so a sum over
    # the buffer divided by h*w_true equals the unpadded mean
    with jax.named_scope("head"):
        feat = jnp.sum(y.astype(jnp.float32), axis=(1, 2)) / (y.shape[1] * w_true)
        return feat @ p["head"]["kernel"] + p["head"]["bias"]
