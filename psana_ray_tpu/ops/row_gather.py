"""Rows of a matrix by index, each row moved once.

``x[idx]`` for ``x [N, D]`` and ``idx [M]``: the dispatch of the dropless
expert layer (``parallel/moe.dropless_moe``: ``M = T * k`` token slots in
expert order). XLA's gather with ``mode="promise_in_bounds"`` is the
general form (the default mode, ``"fill"``, adds a second pass over the
``[M, D]`` result that selects NaN where an index is out of range). For
bfloat16 rows it is slow besides: in the TPU's ``T(8,128)(2,1)`` layout a
row is HALF of each 32-bit word of a packed row pair, and the gather runs
at 33 ns a row (4.61 ms for 139,264 rows of 2,048, 9.08 for 274,432; as
32-bit rows XLA's takes 3.03, but the two views cost more than that
saves: 19.3 ms in all. My chip runs, PR 39).

:func:`gather_rows` is the Pallas form for such rows. Mosaic refuses a
one-row copy out of a ``[N, D]`` array (a slice of a tiled dimension must
be a multiple of 8 rows), so the rows are read from the ``[N, D/128,
128]`` view, where a row is ONE contiguous block (4 KB at ``D`` 2,048;
the view is one relayout of ``x``, 0.43 ms for 143 MB), by one DMA a row
into VMEM, viewed there as 32-bit words: word ``(s, l)`` of a row holds
its columns ``256 s + l`` and ``256 s + 128 + l``. Unpacking a word is a
shift, a mask and two converts; the block leaves as ``[rows, D]``
bfloat16 in the layout the grouped product reads. The next tile's copies
are issued 16 rows at a time between the unpacking of 16 rows of this
one, so the scalar unit's descriptors and the vector unit's unpacking
share bundles: 2.13-2.22 ms for the 139,264 rows (16 ns a row: what issuing a
row's descriptor costs; 64 rows at a time 2.08, but each unrolled copy
is traced and lowered at every start of a process, and 128 of them cost
a warm start 0.7 s a program; without the overlap 2.46, with a wait a
row 2.59), 4.19 for 274,432; a bfloat16 buffer unpacked by strided
half-word reads 4.75 and 9.36 (my chip runs, PR 39). Off the TPU it runs
in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_ROWS = 1024  # rows a grid step: XLA lays a long int32 vector out in tiles of 1,024, and an SMEM block must match
_CHUNK = 16  # rows unpacked between two bursts of the next tile's copies: a packed bfloat16 tile
_LANES = 128


def _kernel(idx_ref, next_ref, x_ref, o_ref, buf, sem, *, rows, steps):
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    slot = lax.rem(i, 2)
    words = x_ref.bitcast(jnp.uint32)  # [N, a, 128]: a row's sublane pairs, one word each
    a = words.shape[1]

    def fetch(ids, to, r):
        pltpu.make_async_copy(words.at[ids[r]], buf.at[to, r], sem.at[to]).start()

    @pl.when(i == 0)
    def _first_tile():  # once a call: a plain loop, a row a turn
        def one(r, c):
            fetch(idx_ref, 0, r)
            return c

        lax.fori_loop(0, rows, one, 0)

    # one wait for the tile: the semaphore counts bytes, and these are the bytes of `rows` rows
    pltpu.make_async_copy(words.at[pl.ds(0, rows)], buf.at[slot], sem.at[slot]).wait()
    flat = buf.at[slot].reshape(rows * a, _LANES)
    more = i + 1 < steps

    def chunk(q, c):
        first = pl.multiple_of(q * _CHUNK, _CHUNK)

        @pl.when(more)
        def _next_tile():
            for u in range(_CHUNK):
                fetch(next_ref, 1 - slot, first + u)

        for s in range(a):
            w = flat[pl.ds(first * a + s, _CHUNK, stride=a), :]
            low = lax.bitcast_convert_type(w << 16, jnp.float32)
            high = lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000), jnp.float32)
            o_ref[pl.ds(first, _CHUNK), 2 * s * _LANES:(2 * s + 1) * _LANES] = low.astype(o_ref.dtype)
            o_ref[pl.ds(first, _CHUNK), (2 * s + 1) * _LANES:(2 * s + 2) * _LANES] = high.astype(o_ref.dtype)
        return c

    lax.fori_loop(0, rows // _CHUNK, chunk, 0)


def tile_rows(m: int, d: int, dtype) -> int:
    """Rows a grid step of the kernel for ``[m, d]`` rows of ``dtype``; 0
    where the kernel does not take them (:func:`gather_rows` then leaves
    the gather to XLA): bfloat16 rows of whole ``[8, 128]`` word tiles, in
    tiles of 1,024 or, under that, one tile of whole chunks."""
    if jnp.dtype(dtype) != jnp.bfloat16 or d % (16 * _LANES):
        return 0
    if m % _ROWS == 0:
        return _ROWS
    return m if m < _ROWS and m % _CHUNK == 0 else 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(x, idx, *, interpret: Optional[bool] = None) -> jax.Array:
    """``x [N, D]``, ``idx [M]`` int32, every index PROMISED in ``[0, N)``
    -> ``x[idx] [M, D]``, the same bits."""
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    m = idx.shape[0]
    rows = tile_rows(m, d, x.dtype)
    if not rows:
        return x.at[idx].get(mode="promise_in_bounds")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    steps = m // rows
    a = d // (2 * _LANES)
    idx = idx.astype(jnp.int32)
    ids = pl.BlockSpec((rows,), lambda i: (i,), memory_space=pltpu.SMEM)
    ahead = pl.BlockSpec((rows,), lambda i: (jnp.minimum(i + 1, steps - 1),), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, steps=steps),
        grid=(steps,),
        in_specs=[ids, ahead, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((2, rows, a, _LANES), jnp.uint32), pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="row_gather",
    )(idx, idx, x.reshape(n, 2 * a, _LANES))
