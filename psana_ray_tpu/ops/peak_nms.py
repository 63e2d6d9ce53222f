"""The local-maximum test of ``find_peaks`` on PACKED probabilities, in
one pass over the map as the head wrote it.

A space-to-depth head (``PeakNetUNetTPU``, ``s2d = r``) leaves its
probabilities as ``[N, H/r, W/r, r*r]``, and on the TPU in the layout
``{0,3,2,1:T(4,128)}``: both packed axes major, the ``r*r`` sub-pixels of
a packed pixel on the sublanes, the batch on the lanes. That is this
kernel's operand ``[H/r, W/r, r*r, N]`` as it stands (a bitcast), so the
full-resolution map, its padded copy and its phases are never written:
the 138 MB of the epix10k2M cell at batch 16 cross HBM once, and 31 MB
of candidates come back.

The test is ``models.peaks._local_maxima``'s — a pixel survives when its
probability reaches the threshold and no neighbour within Chebyshev
distance ``d`` beats it on (probability, earlier raster index) — in the
same static arithmetic: full-resolution row ``y = r*i + a`` lives at
packed row ``i``, sub-row ``a``, so a neighbour ``dy`` away is sub-row
``(a + dy) % r`` of packed row ``i + (a + dy) // r``, and the same along
x. A grid step takes ``k`` rows of super-blocks (``L = lcm(r, d + 1)``
pixels a side) of one lane tile of the batch, with a halo of ``ceil(d /
r)`` packed rows:

1. each packed row is split into its ``r*r`` sub-pixel PLANES ``[W/r,
   lanes]`` by sublane-strided reads of the ``[rows, W/r * r*r, lanes]``
   view (rows outside the frame read as -inf);
2. per row and plane, the maxima over the ``d`` pixels to the left and to
   the right (sublane rolls of the neighbouring planes, -inf rolled in at
   the frame's edge) and over the whole row window;
3. per row and plane, the maxima of (2)'s row windows over the ``d`` rows
   above and below — row shifts are addressing — give "the best EARLIER
   neighbour" (rows above, then left) and "the best LATER one"; the pixel
   survives unless the first ``>=`` it or the second ``>`` it, which is
   the pairwise test's OR, exactly (``max`` of floats is exact);
4. a block holds at most one survivor, so a block's candidate is the
   ``max`` over its ``(d+1)^2`` places: over rows by addressing, over
   columns by sublane-strided reads (stride ``L / r``) of a small
   scratch, which also drops the packed columns down to one per block.

Candidates leave as ``[rows of super-blocks, L/b, L/b * W/L, N]``: block
row ``(m, p)``, and within it block column ``q`` of every super-block,
then the next ``q`` — not left to right. ``find_peaks`` asks nothing of
the order within a block row (it sorts by raster index).

Off the TPU the kernel runs in Pallas interpret mode (tests).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANES = 128
_ROWS = 12  # packed rows a grid step, about: the halo's two rows are then a seventh of the work
_CHUNK = 64  # packed columns tested at a time: seven live planes of 8 vregs


class _Geometry(NamedTuple):
    b: int  # block side, d + 1
    sup: int  # L, a super-block's side: whole blocks AND whole packed pixels
    lr: int  # packed rows (columns) a super-block, L / r
    nb: int  # blocks a super-block's side, L / b
    mh: int  # rows of super-blocks over the frame
    mw: int  # columns of super-blocks
    edge: int  # packed rows of halo, ceil(d / r)
    k: int  # rows of super-blocks a grid step


def _geometry(hp: int, wp: int, r: int, d: int) -> _Geometry:
    b = d + 1
    sup = math.lcm(r, b)
    lr, nb = sup // r, sup // b
    mh, mw = -(-hp * r // sup), -(-wp * r // sup)
    edge = -(-d // r)
    per = max(edge, 1)  # the halo is fetched in blocks of `edge` rows: whole ones a step
    k = -(-min(max(1, _ROWS // lr), mh) // per) * per
    return _Geometry(b, sup, lr, nb, mh, mw, edge, k)


def _kernel(*refs, r, d, hp, wp, threshold):
    from jax.experimental.pallas import tpu as pltpu

    # lax primitives on operands of one shape, index arithmetic included:
    # every jnp call is a jitted helper, traced afresh at each start of a
    # process before any compile cache is asked (PERF.md, PR 41)
    b, sup, lr, nb, _, mw, edge, k = _geometry(hp, wp, r, d)
    r2, th, w = r * r, k * lr, r * wp
    if edge:
        main, above, below, score_ref, where_ref, x_s, row_s, lft_s, rgt_s, as_s, au_s = refs
    else:
        main, score_ref, where_ref, x_s, row_s, lft_s, rgt_s, as_s, au_s = refs
    lanes = main.shape[-1]
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    tile = pl.program_id(1)
    best = functools.partial(functools.reduce, lax.max)

    # 1. planes: x_s[a*r + c, t] is sub-pixel (a, c) of local packed row t
    ninf = lax.full((wp, lanes), -jnp.inf, jnp.float32)

    def planes(src, rows, to, first):
        flat = src.reshape(rows, wp * r2, lanes)

        def one(t, carry):
            g = lax.add(first, t)
            inside = lax.bitwise_and(lax.ge(g, i32(0)), lax.lt(g, i32(hp)))
            for s in range(r2):
                v = flat[t, pl.ds(s, wp, stride=r2), :] if r2 > 1 else flat[t]
                x_s[s, lax.add(t, i32(to))] = lax.select(inside, v, ninf)
            return carry

        lax.fori_loop(0, rows, one, 0)

    planes(main, th, edge, lax.mul(tile, i32(th)))
    if edge:
        planes(above, edge, 0, lax.sub(lax.mul(tile, i32(th)), i32(edge)))
        planes(below, edge, edge + th, lax.mul(lax.add(tile, i32(1)), i32(th)))

    # 2. per row: the best of the d pixels to the left, to the right, and of the window
    col = lax.broadcasted_iota(jnp.int32, (wp, lanes), 0)
    fits = {pc: lax.bitwise_and(lax.ge(col, i32(-pc)), lax.lt(col, i32(wp - pc)))
            for pc in range(-edge, edge + 1) if pc}

    def beside(row, c, dx):  # the row's pixels dx to the side of sub-column c's
        pc, cc = divmod(c + dx, r)
        if pc == 0:
            return row[cc]
        return lax.select(fits[pc], pltpu.roll(row[cc], (-pc) % wp, 0), ninf)

    def windows(t, carry):
        for a in range(r):
            row = [x_s[a * r + c, t] for c in range(r)]
            for c in range(r):
                left = best([beside(row, c, -s) for s in range(1, d + 1)])
                right = best([beside(row, c, s) for s in range(1, d + 1)])
                lft_s[a * r + c, t] = left
                rgt_s[a * r + c, t] = right
                row_s[a * r + c, t] = best([left, row[c], right])
        return carry

    if d:
        lax.fori_loop(0, th + 2 * edge, windows, 0)

    # 3 + 4. per row of super-blocks: the test, then one candidate a block.
    # The loops over column chunks, sub-columns and block rows are the
    # device's, not Python's (an unrolled copy is traced once more); the
    # rows and sub-rows of a super-block are unrolled, so that a block's
    # candidate stays in registers until its last place is tested.
    wq = as_s.shape[2]
    chunk = next((c for c in range(min(_CHUNK, wp), 7, -8) if wp % c == 0), wp)
    zero, none = lax.full((chunk, lanes), 0.0, jnp.float32), lax.full((chunk, lanes), 0, jnp.int32)
    bar = lax.full((chunk, lanes), threshold, jnp.float32)
    place = lax.mul(lax.broadcasted_iota(jnp.int32, (mw, lanes), 0), i32(sup))
    empty, nowhere = lax.full((mw, lanes), 0.0, jnp.float32), lax.full((mw, lanes), 0, jnp.int32)
    if wq > wp:  # columns past the frame hold no survivor
        as_s[:, :, wp:, :] = lax.full((nb, r, wq - wp, lanes), 0.0, jnp.float32)
        au_s[:, :, wp:, :] = lax.full((nb, r, wq - wp, lanes), 0, jnp.int32)

    def super_row(m, carry):
        def test(i, carry):  # sub-column c of one chunk of packed columns
            j, c = lax.div(i, i32(r)), lax.rem(i, i32(r))
            cols = pl.ds(pl.multiple_of(lax.mul(j, i32(chunk)), chunk), chunk)
            plane = [lax.add(c, i32(a * r)) for a in range(r)]
            top = lax.add(lax.mul(m, i32(lr)), i32(edge))
            kept = {}
            for py in range(lr):
                for a in range(r):
                    p, u = divmod(r * py + a, b)
                    t = lax.add(top, i32(py))
                    cen = x_s[plane[a], t, cols]
                    alive = lax.ge(cen, bar)
                    if d:
                        def rows(dys):  # sub-row (a + dy) % r of packed row t + (a + dy) // r
                            return [row_s[plane[(a + dy) % r], lax.add(top, i32(py + (a + dy) // r)), cols]
                                    for dy in dys]

                        early = best(rows(range(-d, 0)) + [lft_s[plane[a], t, cols]])
                        late = best(rows(range(1, d + 1)) + [rgt_s[plane[a], t, cols]])
                        alive = lax.bitwise_and(alive, lax.bitwise_not(
                            lax.bitwise_or(lax.ge(early, cen), lax.gt(late, cen))))
                    sc, at = kept.get(p, (zero, none))
                    # at most one place of a block survives: max IS that one's score
                    kept[p] = (lax.max(sc, lax.select(alive, cen, zero)),
                               lax.select(alive, lax.full_like(none, u), at))
            for p, (sc, at) in kept.items():
                as_s[p, c, cols] = sc
                au_s[p, c, cols] = at
            return carry

        lax.fori_loop(0, wp // chunk * r, test, 0)

        def block_row(p, carry):
            for q in range(nb):
                sc, uu, vv = empty, nowhere, nowhere
                for v in range(b):
                    px, c = divmod(b * q + v, r)
                    every = pl.ds(px, mw, stride=lr) if lr > 1 else pl.ds(px, mw)
                    got = as_s[p, c, every]
                    hit = lax.gt(got, empty)
                    uu = lax.select(hit, au_s[p, c, every], uu)
                    vv = lax.select(hit, lax.full_like(vv, v), vv)
                    sc = lax.max(sc, got)
                # raster index (L*(tile*k + m) + b*p + u) * W + L*n + b*q + v
                y = lax.add(lax.mul(lax.add(lax.mul(tile, i32(k)), m), i32(sup)), lax.mul(p, i32(b)))
                raster = lax.add(lax.mul(lax.add(uu, y), i32(w)), lax.add(place, lax.add(vv, i32(b * q))))
                score_ref[m, p, q * mw:(q + 1) * mw] = sc
                where_ref[m, p, q * mw:(q + 1) * mw] = lax.select(lax.gt(sc, empty), raster, nowhere)
            return carry

        lax.fori_loop(0, nb, block_row, 0)
        return carry

    lax.fori_loop(0, k, super_row, 0)


def takes(shape: Tuple[int, ...], r: int, d: int) -> bool:
    """Does Mosaic take packed probabilities of this ``[N, H/r, W/r,
    r*r]`` shape? Whole lane tiles of the batch, whole sublane tiles of
    packed columns and of a block row's candidates per ``q``;
    ``find_peaks`` leaves the rest to its plain form."""
    if r == 1 or len(shape) != 4:
        return False
    n, hp, wp, _ = shape
    mw = _geometry(hp, wp, r, d).mw
    return n % _LANES == 0 and wp % 8 == 0 and mw % 8 == 0


@functools.partial(jax.jit, static_argnames=("threshold", "d", "r", "interpret"))
def packed_local_maxima(prob, *, threshold: float, d: int, r: int,
                        interpret: Optional[bool] = None):
    """``prob [N, H/r, W/r, r*r]`` float32 probabilities, packed ->
    ``(score [N, Hb, Wb] f32, where [N, Hb, Wb] int32)``: what
    ``models.peaks._local_maxima`` gives for block ``d + 1``, the
    candidates of a block row in the order of the module docstring."""
    from jax.experimental.pallas import tpu as pltpu

    n, hp, wp, r2 = prob.shape
    b, sup, lr, nb, mh, mw, edge, k = _geometry(hp, wp, r, d)
    th = k * lr
    lanes = _LANES if n % _LANES == 0 else n  # a partial tile: interpret mode only
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block = (wp, r2, lanes)
    specs = [pl.BlockSpec((th, *block), lambda j, i: (i, 0, 0, j))]
    if edge:
        per, last = th // edge, (hp - 1) // edge
        specs += [
            pl.BlockSpec((edge, *block), lambda j, i: (jnp.maximum(i * per - 1, 0), 0, 0, j)),
            pl.BlockSpec((edge, *block), lambda j, i: (jnp.minimum((i + 1) * per, last), 0, 0, j)),
        ]
    rows = th + 2 * edge
    out = pl.BlockSpec((k, nb, nb * mw, lanes), lambda j, i: (i, 0, 0, j))
    x = prob.transpose(1, 2, 3, 0)
    score, where = pl.pallas_call(
        functools.partial(_kernel, r=r, d=d, hp=hp, wp=wp, threshold=threshold),
        grid=(n // lanes, -(-mh // k)),
        in_specs=specs,
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((mh, nb, nb * mw, n), jnp.float32),
                   jax.ShapeDtypeStruct((mh, nb, nb * mw, n), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((r2, rows, wp, lanes), jnp.float32) for _ in range(4)]
        + [pltpu.VMEM((nb, r, lr * mw, lanes), jnp.float32),
           pltpu.VMEM((nb, r, lr * mw, lanes), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                                             vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret,
        name="peak_nms",
    )(*[x] * len(specs))
    return tuple(a.reshape(mh * nb, nb * mw, n).transpose(2, 0, 1) for a in (score, where))
