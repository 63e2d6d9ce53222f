"""Mamba-1's selective scan (S6, arXiv:2312.00752), token by token in VMEM.

Per channel ``d`` of ``C`` a state ``N`` wide is carried along the sequence,
``h_0 = 0`` at every sequence's first token; with the step ``Delta_t =
softplus(delta_t + dt_bias) [C]`` and ``A [C, N]``, an OPERAND (negative; any
values: nothing here leans on the initialiser's ``A = -(n + 1)``):

    h_t[d, n] = exp(Delta_t[d] A[d, n]) h_{t-1}[d, n] + Delta_t[d] B_t[n] u_t[d]
    y_t[d] = sum_n C_t[n] h_t[d, n] + D[d] u_t[d]                o_t = y_t * silu(z_t)

The decay differs a channel AND a state: it does not leave the sum over the
state, so a chunk is NOT a matrix product (``ops/ssd.py``'s is, because
Mamba-2's decay is one scalar a head) and the matrix unit has nothing to do.
It is a first-order recurrence an ELEMENT: ``C * N`` exponentials, three
multiplications and an addition a token, then a sum over the state. Left to
XLA that is ``T`` dependent steps of a loop or (an associative scan) an array
``[T, C, N]`` float32 in HBM. Here it is one kernel that shares only
``ops/ssd.py``'s skeleton (chunks on a sequential grid axis, a float32 state
in VMEM scratch zeroed at a sequence's first chunk):

- a grid step takes a chunk of ``rows`` tokens by a tile of ``cols`` channels
  and walks the chunk TOKEN BY TOKEN with the tile's state ``[N, cols]`` in
  registers: the STATE's index on the sublanes, channels on the lanes. A
  token's ``Delta`` and ``Delta u`` are rows ``[1, cols]`` of what the chunk's
  elementwise prologue left in VMEM, spread down the sublanes where they are
  read; ``A^T [N, cols]`` is the tile's constant;
- ``B_t[n]`` and ``C_t[n]`` must lie the OTHER way, down the sublanes and the
  same in every lane. Such a turn costs the transpose unit a token and tile;
  the matrix unit, idle here, makes it a chunk: with ``[B | C]`` a token's row
  of a ``[rows, 128]`` operand, ``Rep [128 N, 128]`` (row ``(t, n)`` has a one
  at ``t``) times that block puts token ``t``'s row in rows ``(t, .)``, a mask
  keeps column ``n`` (or ``N + n``) of row ``(t, n)``, and a product with ones
  spreads it over the lanes: exact, since every sum has ONE non-zero term of a
  bf16 value (a float32 operand, the tests', goes as three bf16 parts). Done at a chunk's FIRST channel tile (the tiles are the innermost
  grid axis) and read by all of them: two aligned loads a token;
- the sum over the state is a sum over SUBLANES, a reduction the vector unit
  does by rotations. It is put off: a token leaves ``C_t * h_t`` folded to its
  eight sublanes in VMEM (four stores), and after the walk eight STRIDED loads
  (rows ``s, s + 8, ...``) bring each sublane's ``[rows, cols]`` and seven
  additions sum them: the chunk's ``y`` in the operands' own layout;
- the skip, the gate ``y * silu(z)`` and, where the caller asks for it
  (``keep``), ``y`` itself (with the skip, BEFORE the gate: what a later layer's
  gated memory unit reads) are written where the kernel writes, once.

Operands come bf16 (``delta`` float32: a log-decay's factor is never rounded
to bf16); the state, every exponent, product and sum are float32. Off the TPU
it runs in Pallas interpret mode (tests, rehearsals).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES, SUBLANES = 128, 8
# a grid step's tokens and channels at most (scan_tiles). On the v5e at 2 x 8,704 tokens, 5,120 channels
# over a state of 16, ONE call (my chip run, PR 74): 256 x 512 4.40 ms, 512 x 512 4.72, 512 x 1,024 4.54,
# 512 x 256 4.93, 1,088 x 512 5.09: 2.2 ms a layer and frame where the bytes would take 0.55. At 512
# channels the state [16, 512] is eight registers and A^T eight more
ROWS = 256
COLS = 512
_TURN = 128  # tokens a turn of [B | C] onto the sublanes takes at once (one product's rows / N)
_STRIP = 8  # tokens a turn of the walk's loop takes, unrolled


def scan_tiles(seq_len: int, channels: int) -> tuple:
    """``(rows, cols)`` of a grid step, from the shapes alone: the largest
    multiple of 16 (a packed bf16 sublane tile) that divides ``seq_len`` and is
    at most ``ROWS`` (of 8 where none does, else the one chunk of the sequence:
    the tests' and the rehearsals' short sequences), and the largest multiple of a lane tile that divides
    ``channels`` and is at most ``COLS`` (all of them where none does)."""
    rows = next((r for r in range(min(ROWS, seq_len) // 16 * 16, 0, -16) if seq_len % r == 0), 0)
    rows = rows or next((r for r in range(min(ROWS, seq_len) // 8 * 8, 0, -8) if seq_len % r == 0),
                        seq_len)
    cols = next((c for c in range(min(COLS, channels) // LANES * LANES, 0, -LANES)
                 if channels % c == 0), channels)
    return rows, cols


def _folded(n: int) -> int:
    """The sublanes a token's ``C * h [n, cols]`` is folded to before it is
    stored: a sublane tile where the state is whole tiles of them, else ``n``."""
    return SUBLANES if n % SUBLANES == 0 else n


def _bf16_parts(a):
    """``a`` as bf16 arrays that sum to it exactly: itself where it is bf16 (the
    model's), three parts of a float32 array (the tests' float32 operands)."""
    if a.dtype == jnp.bfloat16:
        return [a]
    hi = a.astype(jnp.bfloat16)
    mid = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return [hi, mid, (a - hi.astype(jnp.float32) - mid.astype(jnp.float32)).astype(jnp.bfloat16)]


def _spread(bc_ref, xb_ref, xc_ref, n: int):
    """``[B | C]`` of the chunk's tokens (``bc_ref [rows, 128]``, ``B`` in
    columns ``[0, n)``, ``C`` in ``[n, 2n)``) turned onto the sublanes:
    ``xb_ref, xc_ref [rows * n, 128]`` float32, row ``(t, i)`` holding
    ``B_t[i]`` (``C_t[i]``) in every lane."""
    rows = bc_ref.shape[0]
    turn = min(_TURN, rows)
    at = jax.lax.broadcasted_iota(jnp.int32, (turn * n, turn), 0) // n
    rep = (at == jax.lax.broadcasted_iota(jnp.int32, (turn * n, turn), 1)).astype(jnp.bfloat16)
    which = jax.lax.broadcasted_iota(jnp.int32, (turn * n, LANES), 0) % n
    lane = jax.lax.broadcasted_iota(jnp.int32, (turn * n, LANES), 1)
    ones = jnp.ones((LANES, LANES), jnp.bfloat16)

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    for k in range(0, rows, turn):
        block = bc_ref[k:k + turn, :]
        if block.shape[0] < turn:  # a chunk's ragged end (short sequences alone)
            block = jnp.concatenate(
                [block, jnp.zeros((turn - block.shape[0], LANES), block.dtype)])
        kept = min(turn, rows - k) * n
        for ref, first in ((xb_ref, 0), (xc_ref, n)):
            spread = 0.0
            for part in _bf16_parts(block):
                own = dot(rep, part)  # row (t, i): token t's [B | C]
                picked = jnp.where(lane == which + first, own, 0.0).astype(jnp.bfloat16)
                spread = spread + dot(picked, ones)
            ref[k * n:k * n + kept, :] = spread[:kept]


def _kernel(u_ref, delta_ref, bc_ref, z_ref, at_ref, skip_ref, bias_ref, o_ref, *rest, n, keep):
    y_ref = rest[0] if keep else None
    state_ref, xb_ref, xc_ref, step_ref, du_ref, part_ref = rest[1 if keep else 0:]
    c, j = pl.program_id(1), pl.program_id(2)
    rows, cols = u_ref.shape
    tiles = cols // LANES if cols % LANES == 0 else 1  # lane tiles of the channel tile
    lanes = cols // tiles
    kept = _folded(n)
    folds = n // kept  # sublane tiles of the state

    @pl.when(c == 0)  # a sequence starts: h_0 = 0
    def _start():
        state_ref[j] = jnp.zeros(state_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)  # a chunk's first channel tile: B and C turned once for all of them
    def _turn():
        _spread(bc_ref, xb_ref, xc_ref, n)

    u = u_ref[...].astype(jnp.float32)
    step = jax.nn.softplus(delta_ref[...] + bias_ref[...])
    step_ref[...] = step
    du_ref[...] = step * u
    a_t = [at_ref[:, l * lanes:(l + 1) * lanes] for l in range(tiles)]
    strip = _STRIP if rows % _STRIP == 0 else 1

    def walk(i, hs):
        hs = list(hs)
        these = pl.ds(pl.multiple_of(i * strip, strip), strip)  # the strip's rows, one aligned load
        steps = [step_ref[these, l * lanes:(l + 1) * lanes] for l in range(tiles)]
        dus = [du_ref[these, l * lanes:(l + 1) * lanes] for l in range(tiles)]
        for k in range(strip):
            t = i * strip + k
            at = pl.ds(pl.multiple_of(t * n, n), n)
            b_t, c_t = xb_ref[at, :lanes], xc_ref[at, :lanes]
            for l in range(tiles):
                here = slice(l * lanes, (l + 1) * lanes)
                h = jnp.exp(steps[l][k:k + 1] * a_t[l]) * hs[l] + b_t * dus[l][k:k + 1]
                hs[l] = h
                p = c_t * h
                part_ref[l, pl.ds(pl.multiple_of(t * kept, kept), kept), :] = sum(
                    p[f * kept:(f + 1) * kept] for f in range(folds))
        return tuple(hs)

    hs = jax.lax.fori_loop(0, rows // strip, walk, tuple(
        state_ref[j, :, l * lanes:(l + 1) * lanes] for l in range(tiles)))
    for l in range(tiles):
        state_ref[j, :, l * lanes:(l + 1) * lanes] = hs[l]
    # (a strided load wants a base 128 lanes wide: a lane tile a slot)
    y = jnp.concatenate([sum(part_ref[l, pl.ds(s, rows, stride=kept), :] for s in range(kept))
                         for l in range(tiles)], axis=1) + skip_ref[...] * u
    if keep:
        y_ref[...] = y.astype(y_ref.dtype)
    z = z_ref[...].astype(jnp.float32)
    o_ref[...] = (y * z * jax.nn.sigmoid(z)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("seq_len", "keep", "rows", "cols", "interpret"))
def selective_scan(u, delta, bc, z, a, skip, dt_bias, *, seq_len: int, keep: bool = False,
                   rows: Optional[int] = None, cols: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """``u [T, C]`` (after its convolution; ``T`` rows being whole sequences of
    ``seq_len``), ``delta [T, C]`` float32 (the step's pre-activation, ``delta
    W_dt``'s product), ``bc [T, 128]`` (``[B | C | 0]``: ``B_t`` in columns
    ``[0, N)``, ``C_t`` in ``[N, 2N)``, as the product with ``W_x``'s columns laid
    so wrote it), ``z [T, C]`` (the gate's), ``a [C, N]`` (``A``, negative),
    ``skip, dt_bias [C]`` -> ``(y + skip u) * silu(z) [T, C]`` in ``u``'s type,
    and with ``keep`` the pair ``(that, y + skip u)``. ``rows`` and ``cols`` a
    grid step's tokens and channels where a test or a timing run sets them
    (None, as the model calls it: :func:`scan_tiles`)."""
    from jax.experimental.pallas import tpu as pltpu

    t, wide = u.shape
    n = a.shape[1]
    tile = scan_tiles(seq_len, wide)
    rows, cols = rows or tile[0], cols or tile[1]
    if (delta.shape != (t, wide) or z.shape != (t, wide) or bc.shape != (t, LANES)
            or a.shape != (wide, n) or 2 * n > LANES or t % seq_len or seq_len % rows
            or wide % cols):
        raise ValueError(f"selective scan: u {u.shape}, delta {delta.shape}, [B | C] {bc.shape}, "
                         f"z {z.shape} and A {a.shape} are not sequences of {seq_len} rows in "
                         f"chunks of {rows} by tiles of {cols} channels over a state of at most "
                         f"{LANES // 2}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    n_chunks, n_tiles = seq_len // rows, wide // cols

    def block(b, c, j):
        return b * n_chunks + c, j

    tile_spec = pl.BlockSpec((rows, cols), block)
    entry = pl.BlockSpec((1, cols), lambda b, c, j: (0, j))
    out = jax.ShapeDtypeStruct((t, wide), u.dtype)
    result = pl.pallas_call(
        functools.partial(_kernel, n=n, keep=keep),
        grid=(t // seq_len, n_chunks, n_tiles),
        in_specs=[tile_spec, tile_spec,
                  pl.BlockSpec((rows, LANES), lambda b, c, j: (b * n_chunks + c, 0)), tile_spec,
                  pl.BlockSpec((n, cols), lambda b, c, j: (0, j)), entry, entry],
        out_specs=[tile_spec] * (2 if keep else 1),
        out_shape=[out] * (2 if keep else 1),
        scratch_shapes=[pltpu.VMEM((n_tiles, n, cols), f32),  # the state: [N, C], a tile a slot
                        pltpu.VMEM((rows * n, LANES), f32), pltpu.VMEM((rows * n, LANES), f32),
                        pltpu.VMEM((rows, cols), f32), pltpu.VMEM((rows, cols), f32),
                        pltpu.VMEM((max(cols // LANES, 1), rows * _folded(n), min(cols, LANES)), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="selective_scan",
    )(u, delta.astype(f32), bc, z, jnp.transpose(a.astype(f32)), skip.astype(f32)[None],
      dt_bias.astype(f32)[None])
    return tuple(result) if keep else result[0]
