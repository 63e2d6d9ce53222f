"""Manifold-constrained hyper-connections' two mixes, each in one pass.

Where a model's residual path is ``n`` streams wide (``hc_mult``: DeepSeek-AI,
"mHC: Manifold-Constrained Hyper-Connections", arXiv:2512.24880, after
"Hyper-Connections", arXiv:2409.19606) the stream is ``X [T, n*D]``, a
token's ``n`` rows of ``D`` side by side, and a branch ``F`` neither reads its
own input nor is added onto it. With the branch's ``phi [n*D, n*(n + 2)]``
(``[pre | post | res]``, the last row-major), three scalars ``alpha`` and a
bias ``b [n*(n + 2)]``:

    x~ = X / sqrt(mean(X^2) + norm_eps)                 over all n*D channels, float32
    h = alpha * (x~ phi) + b                            n*(n + 2) numbers a token, float32
    H_pre = sigmoid(h_pre)      H_post = 2 sigmoid(h_post)
    M = exp(clip(h_res, lo, hi)), then `iters` times: every row over its sum + eps, every
        column over its sum + eps                       H_res = M: doubly stochastic to rounding
    u = sum_j H_pre[j] X_j                              :func:`hyper_in`  (reads n*D, writes D)
    y = F(u)
    X'_i = sum_j H_res[i, j] X_j + H_post[i] y          :func:`hyper_out` (reads (n+1)*D, writes n*D)

By its bytes that is two passes over the widest array of the step a branch.
Left to XLA as written it is several (a float32 copy of ``X`` for the norm, a
product whose ``n*(n + 2)`` columns fill a fifth of a lane tile, a ``[T, n,
n]`` loop of ``2 * iters`` reductions with the token on the SUBLANES, two
broadcasts), so each mix is ONE Pallas kernel over a tile of rows:

- :func:`hyper_in` reads the tile once: the statistic a STRIP of sixteen rows
  at a time (a row's squares summed lane tile onto lane tile into a scratch
  one lane tile wide, reduced once), the product ``X phi`` (``phi`` laid out
  over 128 columns, a group of ``n`` numbers at every eighth: the product
  costs the MXU what 24 columns would), the scale a row AFTER the product
  (``x~ phi = (X phi) / rms``: nothing normed is written), then the ``[rows,
  128]`` numbers TRANSPOSED so that the sigmoids and the Sinkhorn run with the
  TOKENS on the lanes (``n`` arrays ``[n, rows]``: a row's sum a sublane
  reduce, a column's an elementwise sum), transposed back, written as ``mix
  [T, 128]`` float32 for the way back, and ``u``, again a strip at a time;
- :func:`hyper_out` reads ``X``, ``y`` and ``mix`` and writes ``X'``, the sums
  in float32, rounded once, a strip of rows by a chunk of 512 columns at a
  time (:func:`_strips`: a strip's float32 values stay in registers).

On the v5e at ``[17408, 14336]`` bf16 (my chip runs, PR 78): ``hyper_in`` 1.00
ms and ``hyper_out`` 1.74 alone, 0.94 and 1.69 in the step (83.6% and 81.3% of
what their bytes take at 819 GB/s); a tile of 256 rows reads the same, 64 rows
10% slower on the way in; left to XLA as written 7.17 + 6.12 ms.

``mix``'s columns (:func:`columns`): ``H_pre`` at 0, ``H_post`` at 8, row
``i`` of ``H_res`` at ``16 + 8 i``, and at ``8 (n + 2)`` the token's DEFECT:
the largest distance of a row or column sum of its ``H_res`` from 1, what
says that the constraint held (:func:`sum_defect`). ``iters``, ``eps``,
``norm_eps`` and the clamp are statics of the kernel.

Off the TPU both run in Pallas interpret mode (tests, rehearsals).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LANES = 128  # `mix`'s width: one lane tile
GROUP = 8  # a group of n mixing numbers starts at every eighth column: a sublane tile once transposed
BLOCK_ROWS = 128  # a tile's rows: at 14,336 bf16 columns 3.7 MB of X a buffer
_STRIP = 16  # rows a turn of a kernel's walk down its tile: one packed bfloat16 sublane tile
_CHUNK = 512  # columns a step of that walk: a strip's chunk of every stream is 8 float32 registers
_VMEM_LIMIT = 96 * 1024 * 1024


def columns(streams: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Where ``mix [T, 128]`` holds ``(H_pre [n], H_post [n], H_res [n, n],
    the defect)``: column indices."""
    n = streams
    if not 2 <= n <= GROUP or GROUP * (n + 3) > LANES:
        raise ValueError(f"{n} streams are not built: 2 to {GROUP} are")
    j = np.arange(n)
    return j, GROUP + j, 2 * GROUP + GROUP * j[:, None] + j[None, :], GROUP * (n + 2)


def lay_out(phi, alpha, bias, streams: int):
    """The branch's ``phi [n*D, n*(n + 2)]``, ``alpha [3]`` and ``bias [n*(n +
    2)]`` over ``mix``'s 128 columns -> ``(phi [n*D, 128] in its type, scale
    [1, 128], bias [1, 128] float32)``, zeros where ``mix`` holds nothing."""
    n = streams
    pre, post, res, _ = columns(n)
    at = np.concatenate([pre, post, res.ravel()])
    scale = jnp.repeat(alpha.astype(jnp.float32), np.asarray([n, n, n * n]),
                       total_repeat_length=n * (n + 2))
    wide = jnp.zeros((phi.shape[0], LANES), phi.dtype).at[:, at].set(phi)
    return (wide, jnp.zeros((1, LANES), jnp.float32).at[0, at].set(scale),
            jnp.zeros((1, LANES), jnp.float32).at[0, at].set(bias.astype(jnp.float32)))


def _strips(rows: int, body):
    """``body(at)`` over the tile's rows a STRIP at a time (sixteen: one packed
    bfloat16 sublane tile), so that a strip's float32 values live in registers
    from the load to the store: taken whole, a tile's every intermediate goes
    through VMEM (``ops/short_conv.py`` found the same)."""
    strip = _STRIP if rows % _STRIP == 0 else GROUP

    def turn(i, carry):
        body(pl.ds(pl.multiple_of(i * strip, strip), strip))
        return carry

    jax.lax.fori_loop(0, rows // strip, turn, 0)


def _cuts(width: int):
    """``(start, size)`` of the column chunks a strip is walked in."""
    size = _CHUNK if width % _CHUNK == 0 else width
    return [(c, size) for c in range(0, width, size)]


def _mix_in_kernel(x_ref, phi_ref, scale_ref, bias_ref, u_ref, mix_ref, ss_ref, *, streams, iters,
                   eps, norm_eps, clamp):
    n, d, rows = streams, u_ref.shape[1], x_ref.shape[0]

    def squares(at):  # the statistic: a row's squares summed lane tile onto lane tile, in float32
        acc = jnp.zeros((at.size, LANES), jnp.float32)
        for c, size in _cuts(n * d):
            xf = x_ref[at, c:c + size].astype(jnp.float32)
            sq = xf * xf
            if size % LANES:  # (a width of no whole lane tiles: a test's)
                acc = acc + jnp.sum(sq, axis=-1, keepdims=True) / LANES
            else:
                acc = functools.reduce(jnp.add, [sq[:, at_:at_ + LANES] for at_ in range(0, size, LANES)], acc)
        ss_ref[at, :] = acc

    _strips(rows, squares)
    ss = jnp.sum(ss_ref[...], axis=-1, keepdims=True)
    h = jnp.dot(x_ref[...], phi_ref[...], preferred_element_type=jnp.float32)  # x phi: [rows, 128]
    h = h * jax.lax.rsqrt(ss / (n * d) + norm_eps) * scale_ref[...] + bias_ref[...]  # alpha (x~ phi) + b
    ht = h.T  # [128, rows]: the tokens on the lanes
    pre = jax.nn.sigmoid(ht[0:n])
    post = 2.0 * jax.nn.sigmoid(ht[GROUP:GROUP + n])
    m = tuple(jnp.exp(jnp.clip(ht[(2 + i) * GROUP:(2 + i) * GROUP + n], clamp[0], clamp[1]))
              for i in range(n))  # row i of M: [n, rows], its columns on the sublanes

    def normed(_, m):
        m = tuple(r / (jnp.sum(r, axis=0, keepdims=True) + eps) for r in m)  # every row
        over = functools.reduce(jnp.add, m) + eps  # every column's sum
        return tuple(r / over for r in m)

    m = jax.lax.fori_loop(0, iters, normed, m)
    by_row = [jnp.abs(jnp.sum(r, axis=0, keepdims=True) - 1.0) for r in m]
    by_col = jnp.max(jnp.abs(functools.reduce(jnp.add, m) - 1.0), axis=0, keepdims=True)
    defect = functools.reduce(jnp.maximum, by_row + [by_col])  # [1, rows]

    def group(a):  # a's rows at the head of a group of eight
        return jnp.concatenate([a, jnp.zeros((GROUP - a.shape[0], rows), jnp.float32)], axis=0)

    held = [group(a) for a in (pre, post, *m, defect)]
    held.append(jnp.zeros((LANES - GROUP * len(held), rows), jnp.float32))
    mix_ref[...] = jnp.concatenate(held, axis=0).T  # [rows, 128]: the tokens on the sublanes again

    def read(at):  # what the branch reads: u = sum_j H_pre[j] x_j
        mix = mix_ref[at, :]
        for c, size in _cuts(d):
            u = mix[:, 0:1] * x_ref[at, c:c + size].astype(jnp.float32)
            for j in range(1, n):
                u = u + mix[:, j:j + 1] * x_ref[at, j * d + c:j * d + c + size].astype(jnp.float32)
            u_ref[at, c:c + size] = u.astype(u_ref.dtype)

    _strips(rows, read)


def _mix_out_kernel(x_ref, y_ref, mix_ref, o_ref, *, streams):
    n, d = streams, y_ref.shape[1]

    def write(at):
        mix = mix_ref[at, :]
        for c, size in _cuts(d):
            y = y_ref[at, c:c + size].astype(jnp.float32)
            xs = [x_ref[at, j * d + c:j * d + c + size].astype(jnp.float32) for j in range(n)]
            for i in range(n):
                first = (2 + i) * GROUP
                acc = mix[:, GROUP + i:GROUP + i + 1] * y
                for j in range(n):
                    acc = acc + mix[:, first + j:first + j + 1] * xs[j]
                o_ref[at, i * d + c:i * d + c + size] = acc.astype(o_ref.dtype)

    _strips(x_ref.shape[0], write)


def _rows(t: int, block_rows: int) -> int:
    """A tile's rows: ``block_rows``, or all of fewer rows (in whole sublane tiles)."""
    return block_rows if t >= block_rows else -(-t // GROUP) * GROUP


@functools.partial(jax.jit, static_argnames=("streams", "iters", "eps", "norm_eps", "clamp",
                                             "block_rows", "interpret"))
def hyper_in(x, phi, alpha, bias, *, streams: int, iters: int, eps: float, norm_eps: float,
             clamp: Tuple[float, float], block_rows: int = BLOCK_ROWS,
             interpret: Optional[bool] = None):
    """``x [T, n*D]`` and a branch's ``phi [n*D, n*(n + 2)]``, ``alpha [3]``,
    ``bias [n*(n + 2)]`` -> ``(u [T, D] in x's type, mix [T, 128] float32)``:
    what the branch reads, and the mixing numbers for the way back
    (:func:`columns`). Any ``T``: the last tile may be ragged."""
    from jax.experimental.pallas import tpu as pltpu

    t, wide = x.shape
    d = wide // streams
    if wide != streams * d or phi.shape != (wide, streams * (streams + 2)):
        raise ValueError(f"hyper_in: {x.shape} is not {streams} streams under phi {phi.shape}")
    rows = _rows(t, block_rows)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    whole = pl.BlockSpec((1, LANES), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_mix_in_kernel, streams=streams, iters=iters, eps=eps, norm_eps=norm_eps,
                          clamp=clamp),
        grid=(pl.cdiv(t, rows),),
        in_specs=[pl.BlockSpec((rows, wide), lambda i: (i, 0)),
                  pl.BlockSpec((wide, LANES), lambda i: (0, 0)), whole, whole],
        out_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((t, d), x.dtype),
                   jax.ShapeDtypeStruct((t, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32)],  # a row's squares, a lane tile wide
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="hyper_in",
    )(x, *lay_out(phi, alpha, bias, streams))


@functools.partial(jax.jit, static_argnames=("streams", "block_rows", "interpret"))
def hyper_out(x, y, mix, *, streams: int, block_rows: int = BLOCK_ROWS,
              interpret: Optional[bool] = None):
    """``x [T, n*D]``, the branch's output ``y [T, D]`` and :func:`hyper_in`'s
    ``mix [T, 128]`` -> ``x' [T, n*D]`` in x's type: stream ``i`` is ``sum_j
    H_res[i, j] x_j + H_post[i] y``, summed in float32."""
    from jax.experimental.pallas import tpu as pltpu

    t, wide = x.shape
    d = wide // streams
    if y.shape != (t, d) or mix.shape != (t, LANES):
        raise ValueError(f"hyper_out: y {y.shape}, mix {mix.shape} beside x {x.shape}")
    rows = _rows(t, block_rows)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pl.pallas_call(
        functools.partial(_mix_out_kernel, streams=streams),
        grid=(pl.cdiv(t, rows),),
        in_specs=[pl.BlockSpec((rows, wide), lambda i: (i, 0)),
                  pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, wide), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, wide), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="hyper_out",
    )(x, y, mix)


def mixing_numbers(mix, streams: int):
    """``mix [T, 128]`` -> ``(H_pre [T, n], H_post [T, n], H_res [T, n, n])``."""
    pre, post, res, _ = columns(streams)
    return mix[:, pre], mix[:, post], mix[:, res]


def sum_defect(mix, streams: int):
    """The largest distance of a row or column sum of any token's ``H_res``
    from 1: a scalar, float32."""
    return jnp.max(mix[:, columns(streams)[3]])
