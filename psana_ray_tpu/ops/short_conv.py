"""The gates and taps of a gated short convolution, in one pass.

LFM2's operator (``models/decoder.gated_short_conv``) is two matrix
products around elementwise work: with ``[B | C | z] = a W_in``,

    u = B * z        c[t] = sum_j w[:, j] * u[t - (taps - 1) + j]        y = C * c

causal within each sequence (``u`` is zero before its first token), float32
inside. In XLA that was three passes a layer at 34,816 x 2,048 (the
product written as float32 ``[T, 6144]``, ``u`` materialised as float32,
then the taps and the gate: compiled for a described v5e, PR 38) where the
mathematics needs ONE: read ``[B | C | z]`` (bf16), write ``y``.
:func:`gated_conv_taps` is that pass as a Pallas kernel: a tile of rows by a
block of channels at a time, the rows in order, the last rows of ``u``
carried in VMEM from a tile to the next and zeroed where a sequence starts.
Off the TPU it runs in Pallas interpret mode (tests, rehearsals).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_CARRY = 8  # rows of u kept from the previous tile: a sublane tile; taps - 1 of them are read


def _kernel(b_ref, c_ref, z_ref, w_ref, y_ref, carry_ref, *, taps, tiles_per_seq):
    from jax.experimental.pallas import tpu as pltpu

    rows = b_ref.shape[0]

    @pl.when(pl.program_id(1) % tiles_per_seq == 0)  # a sequence starts: nothing lies before it
    def _start():
        carry_ref[...] = jnp.zeros(carry_ref.shape, jnp.float32)

    u = b_ref[...].astype(jnp.float32) * z_ref[...].astype(jnp.float32)
    before = carry_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, before.shape, 0)
    acc = w_ref[taps - 1:taps, :] * u
    for late in range(1, taps):  # tap (taps - 1 - late) meets u[t - late]
        turned = pltpu.roll(u, shift=late, axis=0)  # row r holds u[r - late], the first rows wrapped
        # ... which are the previous tile's last: before[_CARRY - late + r]
        head = jnp.where(row < late, pltpu.roll(before, shift=late, axis=0), turned[:_CARRY])
        acc = acc + w_ref[taps - 1 - late:taps - late, :] * jnp.concatenate(
            [head, turned[_CARRY:]], axis=0)
    y_ref[...] = (c_ref[...].astype(jnp.float32) * acc).astype(y_ref.dtype)
    carry_ref[...] = u[rows - _CARRY:]


def _tile(n: int, want: int, unit: int) -> int:
    """The largest multiple of ``unit`` that divides ``n`` and is at most
    ``want``; ``n`` itself if there is none."""
    return next((t for t in range(min(want, n) // unit * unit, 0, -unit) if n % t == 0), n)


@functools.partial(jax.jit, static_argnames=("seq_len", "block_rows", "interpret"))
def gated_conv_taps(bcz, taps_w, *, seq_len: int, block_rows: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    """``bcz [T, 3*D]`` (``[B | C | z]``, ``T`` rows being whole sequences
    of ``seq_len``, one after the other) and ``taps_w [D, taps]`` ->
    ``y [T, D]`` in ``bcz``'s type: ``C * conv(B * z)``, the convolution
    causal and depthwise, tap ``j`` on ``u[t - (taps - 1) + j]``, zeros
    before each sequence's first row. ``seq_len`` is a multiple of 8."""
    from jax.experimental.pallas import tpu as pltpu

    t, d3 = bcz.shape
    d, taps = taps_w.shape
    if d3 != 3 * d or t % seq_len or seq_len % _CARRY or taps > _CARRY:
        raise ValueError(f"gated_conv_taps: rows {t} x {d3} are not sequences of {seq_len} "
                         f"(a multiple of {_CARRY}) of [B | C | z] for {d} channels, {taps} taps")
    rows = _tile(seq_len, block_rows, _CARRY)
    cols = _tile(d, 512, 128)
    n_col = d // cols
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def part(k):  # B, C or z: the k-th third of bcz's columns
        return pl.BlockSpec((rows, cols), lambda j, i: (i, k * n_col + j))

    return pl.pallas_call(
        functools.partial(_kernel, taps=taps, tiles_per_seq=seq_len // rows),
        grid=(n_col, t // rows),
        in_specs=[part(0), part(1), part(2), pl.BlockSpec((taps, cols), lambda j, i: (0, j))],
        out_specs=pl.BlockSpec((rows, cols), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, d), bcz.dtype),
        scratch_shapes=[pltpu.VMEM((_CARRY, cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gated_conv_taps",
    )(bcz, bcz, bcz, jnp.transpose(taps_w).astype(jnp.float32))
