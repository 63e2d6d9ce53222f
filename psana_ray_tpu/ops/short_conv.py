"""Short causal convolutions over a sequence's rows, each in one pass.

TWO operators, ONE way of reaching the rows before a row. Both run a few
taps (at most eight) of a depthwise convolution down the rows of ``[T, C]``,
``T`` rows being whole sequences one after the other, tap ``j`` on
``u[t - (taps - 1) + j]``, zeros before each sequence's first row, float32
inside, rounded once. Both take a tile of rows by a block of channels at a
time, each operand by ONE aligned load and ONE convert, and reach ``u[t -
late]`` by :func:`_shifted`: the float32 tile turned down by whole-register sublane
rolls, its first rows from the last eight rows before the tile, which ride
in VMEM from a tile to the next and are zeroed where a sequence starts.

:func:`gated_conv_taps` (LFM2's, ``models/decoder.gated_short_conv``): with
``[B | C | z] = a W_in``,

    u = B * z        c[t] = sum_j w[:, j] * u[t - (taps - 1) + j]        y = C * c

In XLA that was three passes a layer at 34,816 x 2,048 (the product written
as float32 ``[T, 6144]``, ``u`` materialised as float32, then the taps and
the gate: compiled for a described v5e, PR 38) where the mathematics needs
ONE: read ``[B | C | z]`` (bf16), write ``y``. Its sum runs from the newest
tap to the oldest.

:func:`conv_silu_taps` (``models/decoder.conv_silu``: ahead of every delta
rule, Ling-3.0's KDA over ``[q | k | v]`` and Olmo-Hybrid's GDN over q, k
and v, and of every state-space scan, Granite-4.0-H's and Nemotron-H's
Mamba-2 over ``[x | B | C]`` with a bias):

    c[t] = sum_j w[:, j] * u[t - (taps - 1) + j] + bias        y = c * 1 / (1 + e^-c)

XLA ran that as ONE loop fusion already (in once, out once), but a
vector-bound one: its body cut four slices out of a padded packed array,
three of them off the sublane tile, and converted each (6.19 ms at 34,816 x
12,288 where the bytes take 2.09, XLA's own cost model 5.40: PR 73). The
kernel converts once and rolls. Its sum runs from the OLDEST tap to the
newest, then the bias, then the SiLU as ``jax.nn.silu`` lowers it, exact
divide and all: the order XLA's form had, so its output is that form's to
the bit (step 1 of PR 73, all five shapes). It walks its tile in strips of
sixteen rows (one packed bfloat16 sublane tile) so that a strip's float32
values live in registers from the load to the store; taken whole, a tile's
every intermediate goes through VMEM (4.37 ms for 2.83). Its tile is 1,088
rows (an eighth of a sequence of 8,704) by 1,024 channels: fewer, longer
grid steps than :func:`gated_conv_taps`' 512 x 512 read 5% faster at every
shape the cells have (PERF.md section 5 has the table).

Off the TPU both run in Pallas interpret mode (tests, rehearsals).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_CARRY = 8  # rows of u kept from the previous tile: a sublane tile; taps - 1 of them are read
_STRIP = 16  # rows a turn of `conv_silu_taps`' walk down its tile: one packed bfloat16 sublane tile


def _shifted(u, before, late: int):
    """The float32 tile ``u [rows, cols]`` shifted down by ``late`` rows
    (row ``r`` holds ``u[r - late]``), its first ``late`` rows the last of
    ``before [_CARRY, cols]``, the eight rows ahead of the tile."""
    from jax.experimental.pallas import tpu as pltpu

    if not late:
        return u
    row = jax.lax.broadcasted_iota(jnp.int32, before.shape, 0)
    turned = pltpu.roll(u, shift=late, axis=0)  # row r holds u[r - late], the first rows wrapped
    # ... which are the previous tile's last: before[_CARRY - late + r]
    head = jnp.where(row < late, pltpu.roll(before, shift=late, axis=0), turned[:_CARRY])
    return jnp.concatenate([head, turned[_CARRY:]], axis=0)


def _start_anew(carry_ref, tiles_per_seq):
    """Zero the carry where a sequence starts: nothing lies before it."""
    @pl.when(pl.program_id(1) % tiles_per_seq == 0)
    def _start():
        carry_ref[...] = jnp.zeros(carry_ref.shape, jnp.float32)


def _kernel(b_ref, c_ref, z_ref, w_ref, y_ref, carry_ref, *, taps, tiles_per_seq):
    rows = b_ref.shape[0]
    _start_anew(carry_ref, tiles_per_seq)
    u = b_ref[...].astype(jnp.float32) * z_ref[...].astype(jnp.float32)
    before = carry_ref[...]
    acc = w_ref[taps - 1:taps, :] * u
    for late in range(1, taps):  # tap (taps - 1 - late) meets u[t - late]: the NEWEST tap first
        acc = acc + w_ref[taps - 1 - late:taps - late, :] * _shifted(u, before, late)
    y_ref[...] = (c_ref[...].astype(jnp.float32) * acc).astype(y_ref.dtype)
    carry_ref[...] = u[rows - _CARRY:]


def _conv_silu_kernel(u_ref, w_ref, *rest, taps, tiles_per_seq, strip):
    *b_ref, y_ref, carry_ref = rest  # the bias [1, cols] where the model has one
    _start_anew(carry_ref, tiles_per_seq)

    def a_strip(i, before):
        at = pl.ds(pl.multiple_of(i * strip, strip), strip)
        u = u_ref[at, :].astype(jnp.float32)
        acc = w_ref[0:1, :] * _shifted(u, before, taps - 1)
        for j in range(1, taps):  # tap j meets u[t - (taps - 1 - j)]: the OLDEST tap first
            acc = acc + w_ref[j:j + 1, :] * _shifted(u, before, taps - 1 - j)
        if b_ref:
            acc = acc + b_ref[0][...]
        y_ref[at, :] = jax.nn.silu(acc).astype(y_ref.dtype)
        return u[strip - _CARRY:]

    carry_ref[...] = jax.lax.fori_loop(0, u_ref.shape[0] // strip, a_strip, carry_ref[...])


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


@functools.partial(jax.jit, static_argnames=("seq_len", "block_rows", "block_cols", "interpret"))
def conv_silu_taps(u, taps_w, bias=None, *, seq_len: int, block_rows: int = 1088,
                   block_cols: int = 1024, interpret: Optional[bool] = None) -> jax.Array:
    """``u [T, C]`` (``T`` rows being whole sequences of ``seq_len``, one
    after the other), ``taps_w [C, taps]`` and ``bias [C]`` or None -> ``y
    [T, C]`` in ``u``'s type: ``silu(conv(u) + bias)``, the convolution
    causal and depthwise, tap ``j`` on ``u[t - (taps - 1) + j]``, zeros
    before each sequence's first row. ``seq_len`` is a multiple of 8; a tile
    is the largest such multiple that divides it, up to ``block_rows``. A
    width past ``block_cols`` is cut into blocks of it, the last one ragged
    where it does not divide (a column is its own convolution)."""
    from jax.experimental.pallas import tpu as pltpu

    t, c = u.shape
    taps = taps_w.shape[1]
    if taps_w.shape[0] != c or t % seq_len or seq_len % _CARRY or not 1 <= taps <= _CARRY:
        raise ValueError(f"conv_silu_taps: rows {t} x {c} are not sequences of {seq_len} "
                         f"(a multiple of {_CARRY}) for taps {taps_w.shape} of at most {_CARRY}")
    rows = _tile(seq_len, block_rows, _CARRY)
    cols = min(c, block_cols)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tile = pl.BlockSpec((rows, cols), lambda j, i: (i, j))
    operands = [u, jnp.transpose(taps_w).astype(jnp.float32)]
    in_specs = [tile, pl.BlockSpec((taps, cols), lambda j, i: (0, j))]
    if bias is not None:
        operands.append(bias.astype(jnp.float32).reshape(1, c))
        in_specs.append(pl.BlockSpec((1, cols), lambda j, i: (0, j)))
    return pl.pallas_call(
        functools.partial(_conv_silu_kernel, taps=taps, tiles_per_seq=seq_len // rows,
                          strip=rows if rows % _STRIP else _STRIP),
        grid=(pl.cdiv(c, cols), t // rows),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((t, c), u.dtype),
        scratch_shapes=[pltpu.VMEM((_CARRY, cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="conv_silu_taps",
    )(*operands)
