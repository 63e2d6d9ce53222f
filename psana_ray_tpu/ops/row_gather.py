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

A row is whole ``[8, 128]`` word tiles only at ``D % 2048 == 0``. At any
other whole number of 128-column chunks (ling3's 2,560, nemotron3's 2,688
with its odd 21st chunk, laguna's 3,072) XLA's reshape gives no view with a
row a block, so the kernel reads ``x`` through :func:`_rows_as_words`' view
(``[N, 12, 128]`` words at all three widths, a pass over ALL of ``x``),
copies a row's REAL word sublanes (10, 11, 12) and unpacks the real
chunks. :func:`tile_rows` is the one place that says who moves a call's
rows, from its shapes and dtype alone. ALONE on the chip (my chip runs, PR
70, ``_chip_archive/pr70/alone*.py``: the step's own indices, ``slot // k``
of the slots sorted held experts first; twenty calls a reading, each read
twice to 0.01 ms; every variant equal to XLA's gather to the bit), ms::

    x, rows out              XLA's gather   view + kernel   the view in a jit of its own
    [34816, 2560], 104,448   4.31 (41 ns)   2.55 (24 ns)    1.38 (0.60 as the step runs it)
    [34816, 2688], 156,672   6.65 (42 ns)   3.53 (23 ns)    1.39
    [17408, 3072],  65,280   0.65 (10 ns)   1.71 (26 ns)    0.73
    [34816, 2048], 139,264   4.64 (33 ns)   2.74 (20 ns)    XLA's reshape, 0.43

XLA's gather has TWO paces, and what sets them is the size of ``x``: 8-10
ns a row where ``x`` is 111.0 MiB or less (``[17408, .]`` at 2,048 to
3,072, ``[18944, 3072]``, ``[22528, 2560]``, ``[8704, 2560]``; a chip's 128
MiB of vector memory less the 16 MiB XLA keeps for its scopes is 112), 40-48
at 112.5 MiB and more (``[19200, 3072]``, ``[23040, 2560]``, every ``[34816,
.]``), whatever ``m``. So laguna's rows stay XLA's (0.61 ms a layer in its
step, where the kernel would take 1.7) and ling3's and nemotron3's go
through the kernel (in their steps 4.24 -> 0.60 + 1.90 and 6.61 -> 0.62 + 2.87
ms a layer, the view and the kernel). Fewer rows out
of ling3's ``x`` (the same ``x``, ``m`` sorted rows; XLA / view + kernel):
69,632 2.85 / 1.91; 52,224 2.14 / 1.60; 34,816 (``m = n``) 1.43 / 1.29;
26,112 0.89 / 1.15; 17,408 0.73 / 0.97; 2,048 (a turn of the held rows'
loop) 0.22 / 0.70; kimi's turn, 2,048 rows of ``[17408, 7168]``, 0.25 /
0.90 (its view alone 1.59): the break-even stands between ``m = 0.75 n``
and ``m = n``. What did NOT pay, each equal to the bit and slower or level:
the buffer read through a 4-D index ``buf[slot, rows, s, :]`` (3.86 for 2.66:
Mosaic lays a row's twelve word sublanes in SIXTEEN, so a FLAT view of a
``[rows, 12, 128]`` buffer reads other words — wrong on the chip, right in
interpret mode — and the buffer is ``[rows, 16, 128]`` with a row copied
into its first sublanes); the words unpacked by integer operations on row
pairs (``(even & 0xFFFF) | (odd << 16)`` bitcast to a packed tile: 2.89 for
2.66, and 2.93 for 2.74 at 2,048: two strided loads of eight sublanes cost
more than the converts); the view made the same way (level). Copying the
real sublanes only (10 of 12) was worth 0.11 ms at 2,560.

:func:`sum_counted_rows` is the way BACK of a holder of a share of the
experts (``parallel/moe._held_rows_ahead``): ``y[t] = sum_j gates[t, j] *
out[back[t, j]]`` over the slots that COUNT, a quarter of them at ling3's
shape. XLA's shapes are static, so its ``k`` gathers of ``[T, D]`` move
every slot's row and a ``where`` throws three of four away (12.9 ms + 2.5
for the sum at 34,816 x 8 slots of 2,560). Here a token tile's counted
slots are listed first (one sort of ``[T k / 1024, 1024]`` int32, 0.04
ms), the scalar unit starts a copy for each of them in whole bursts of 16
(so for up to 15 slots a grid step that do not count: their rows land at
their own places and are selected to zero like any uncounted place), and
a token's sum is made on whole vregs of its own rows (``[12, 128]`` words:
two vregs a slot) and leaves by two strided stores into the ``[T, D]``
float32 tiles: 2.7-2.9 ms, after 1.8 to lay ``out`` down as rows of words
(:func:`_rows_as_words`). The first form summed eight tokens a vreg, a
strided load for every slot and word sublane: 5.5 ms, 3.4 of them the
348,160 strided loads, 9.8 ns each (my chip runs, PR 52).
"""

from __future__ import annotations

import functools
import math
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
    # [N, a, 128] words: a row's sublane pairs, one word each (the words view comes as such)
    words = x_ref if x_ref.dtype == jnp.uint32 else x_ref.bitcast(jnp.uint32)
    chunks = o_ref.shape[1] // _LANES  # the REAL chunks of 128 columns: the view may hold more
    a = -(-chunks // 2)  # and their word sublanes: the first `a` of a row's place in the buffer

    def fetch(ids, to, r):
        pltpu.make_async_copy(words.at[ids[r], pl.ds(0, a)], buf.at[to, r, pl.ds(0, a)], sem.at[to]).start()

    @pl.when(i == 0)
    def _first_tile():  # once a call: a plain loop, a row a turn
        def one(r, c):
            fetch(idx_ref, 0, r)
            return c

        lax.fori_loop(0, rows, one, 0)

    # one wait for the tile: the semaphore counts bytes, and these are the bytes of `rows` rows
    pltpu.make_async_copy(words.at[pl.ds(0, rows), pl.ds(0, a)], buf.at[slot, :, pl.ds(0, a)], sem.at[slot]).wait()
    place = buf.shape[2]  # `a` in whole 8-sublane tiles: only so is a flat view of the buffer rows of places
    flat = buf.at[slot].reshape(rows * place, _LANES)
    more = i + 1 < steps

    def chunk(q, c):
        first = pl.multiple_of(q * _CHUNK, _CHUNK)

        @pl.when(more)
        def _next_tile():
            for u in range(_CHUNK):
                fetch(next_ref, 1 - slot, first + u)

        for s in range(a):
            w = flat[pl.ds(first * place + s, _CHUNK, stride=place), :]
            low = lax.bitcast_convert_type(w << 16, jnp.float32)
            o_ref[pl.ds(first, _CHUNK), 2 * s * _LANES:(2 * s + 1) * _LANES] = low.astype(o_ref.dtype)
            if 2 * s + 1 < chunks:  # (an odd last chunk is the low halves of a last word)
                high = lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000), jnp.float32)
                o_ref[pl.ds(first, _CHUNK), (2 * s + 1) * _LANES:(2 * s + 2) * _LANES] = high.astype(o_ref.dtype)
        return c

    lax.fori_loop(0, rows // _CHUNK, chunk, 0)


# what XLA's own gather keeps in vector memory: over an `x` of up to 111.0 MiB it ran at 8-10 ns a
# row, over one of 112.5 MiB and more at 40-48 (the module's docstring; my chip runs, PR 70)
XLA_KEEPS_BYTES = 112 * 2 ** 20


def tile_rows(n: int, m: int, d: int, dtype) -> int:
    """Rows a grid step of the kernel for a call that moves ``m`` rows out of
    ``[n, d]`` of ``dtype``; 0 where :func:`gather_rows` leaves the gather to
    XLA. The ONE place the rule stands, shapes and a dtype in: the kernel
    takes bfloat16 rows of whole 128-column chunks, in tiles of 1,024 (a
    ragged last one) or, under that, one tile of whole bursts of copies.
    Where a row is no whole ``[8, 128]`` word tiles (``d % 2048``: 2,560,
    2,688, 3,072, 7,168) it reads ``x`` through a view that is a pass over
    ALL of ``x``, and takes the call only where that pays: ``m >= n`` (a
    pass ahead of the held rows' loop moves 3 to 4.5 rows a row of ``x``;
    measured, ling3's ``x``: the kernel with its view wins from ``m = n``,
    1.29 ms for 1.43, and loses at ``0.75 n``, 1.15 for 0.89; a turn of
    the loop, 2,048 rows of 8,704 to 34,816, is XLA's at 0.22-0.25 ms
    where the view alone is 0.6-1.6), and ``x`` past what XLA's own gather
    keeps in vector memory (laguna's ``[17408, 3072]``, 102 MiB, is XLA's at
    10 ns a row; ling3's and nemotron3's ``[34816, .]`` at 41-42 are the
    kernel's at 23-24). Widths of whole word tiles keep the rule they had
    (lfm2's and keye's ``x`` is 136 MiB and over)."""
    if jnp.dtype(dtype) != jnp.bfloat16 or d % _LANES:
        return 0
    if d % (16 * _LANES) and (m < n or n * d * 2 <= XLA_KEEPS_BYTES):
        return 0
    if m >= _ROWS:
        return _ROWS
    return m if m % _CHUNK == 0 else 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(x, idx, *, interpret: Optional[bool] = None) -> jax.Array:
    """``x [N, D]``, ``idx [M]`` int32, every index PROMISED in ``[0, N)``
    -> ``x[idx] [M, D]``, the same bits."""
    rows = tile_rows(x.shape[0], idx.shape[0], x.shape[1], x.dtype)
    if not rows:
        return x.at[idx].get(mode="promise_in_bounds")
    return _kernel_rows(x, idx, rows, interpret)


def _kernel_rows(x, idx, rows: int, interpret: Optional[bool] = None) -> jax.Array:
    """:func:`gather_rows` through the kernel, ``rows`` a grid step (1,024, or
    all ``M`` of them in whole 16s): ``x`` bfloat16, ``D`` in whole 128s."""
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    m = idx.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    steps = -(-m // rows)
    chunks = d // _LANES
    idx = idx.astype(jnp.int32)
    if steps * rows > m:  # whole SMEM tiles: the pad reads row 0, and the last output block is a partial one
        idx = jnp.pad(idx, (0, steps * rows - m))
    # a row as ONE block: XLA's reshape where it is whole [8, 128] word tiles, else the words laid down
    words = _words_view(x, interpret) if chunks % 16 else x.reshape(n, chunks, _LANES)
    ids = pl.BlockSpec((rows,), lambda i: (i,), memory_space=pltpu.SMEM)
    ahead = pl.BlockSpec((rows,), lambda i: (jnp.minimum(i + 1, steps - 1),), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, steps=steps),
        grid=(steps,),
        in_specs=[ids, ahead, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        # a row's place: its word sublanes in whole 8-sublane tiles (`_kernel` reads the buffer flat)
        scratch_shapes=[pltpu.VMEM((2, rows, -(-chunks // 16) * 8, _LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="row_gather",
    )(idx, idx, words)


def _words_kernel(x_ref, o_ref, buf, sem, *, rows, words, steps):
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    slot = lax.rem(i, 2)

    def put(step, at):
        return pltpu.make_async_copy(buf.at[at], o_ref.at[pl.ds(step * rows, rows)], sem.at[at])

    @pl.when(i >= 2)
    def _buffer_free():
        put(i - 2, slot).wait()

    def chunk(q, c):
        first = pl.multiple_of(q * _CHUNK, _CHUNK)

        def bits(of):  # 16 rows of a chunk of 128 columns, each value the HIGH half of a word
            part = x_ref[pl.ds(first, _CHUNK), pl.ds(pl.multiple_of(of * _LANES, _LANES), _LANES)]
            return lax.bitcast_convert_type(part.astype(jnp.float32), jnp.uint32)

        def word(s, carry):  # word (s, l) of a row: its columns 256 s + l and 256 s + 128 + l
            buf[slot, pl.ds(first, _CHUNK), s, :] = (bits(2 * s) >> 16) | bits(2 * s + 1)
            return carry

        lax.fori_loop(0, words, word, None, unroll=True)  # traced once: a warm start pays the trace
        if x_ref.shape[1] % (2 * _LANES):  # an odd last chunk of 128 columns: the low halves of one word more
            buf[slot, pl.ds(first, _CHUNK), words, :] = bits(2 * words) >> 16
        return c

    lax.fori_loop(0, rows // _CHUNK, chunk, 0)
    put(i, slot).start()

    @pl.when(i == steps - 1)
    def _all_written():
        if steps > 1:
            put(i - 1, 1 - slot).wait()
        put(i, slot).wait()


def _rows_as_words(x, view: int, interpret) -> jax.Array:
    """``x [N, D]`` bfloat16, ``N`` in whole 16s and ``D`` in whole 128s (an odd last chunk of 128
    columns is the low halves of a last word, whose high halves are 0: no column is padded first, as
    until PR 64, a pass over ``[N, D]``) -> ``[N, view / 2, 128]`` uint32, the words
    :func:`gather_rows` reads (a row ONE block of ``view / 2`` word sublanes, those past ``D /
    256`` left as they were), in one pass over ``x``. XLA's own relayout to the ``[N, view,
    128]`` view takes two passes where the columns are padded first (a pad, a transposing copy:
    3.77 ms for ``[104448, 2560]``) and three where the view comes first (5.56); this one 1.81
    (my chip runs, PR 52)."""
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    rows = math.gcd(n, 512)
    steps = n // rows
    return pl.pallas_call(
        functools.partial(_words_kernel, rows=rows, words=d // (2 * _LANES), steps=steps),
        grid=(steps,),
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n, view // 2, _LANES), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((2, rows, view // 2, _LANES), jnp.uint32), pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="rows_as_words",
    )(x)


def _words_view(x, interpret) -> jax.Array:
    """:func:`_rows_as_words` of any bfloat16 ``x [N, D]`` -> ``[N', view / 2, 128]``: a row ONE block
    for a copy, its chunks' room in whole 8-row tiles of the ``[N, ., 128]`` view (whole words of
    whole 16 rows as they come: the pad is none)."""
    n, d = x.shape
    view = -(-d // (8 * _LANES)) * 8
    return _rows_as_words(jnp.pad(x, ((0, -n % _CHUNK), (0, -d % _LANES))), view, interpret)


_FLAG = 1 << 30  # on a listed slot that does not count: it sorts last


def _sum_kernel(limit_ref, n_ref, list_ref, next_ref, back_ref, gates_ref, rows_ref, o_ref, buf, sem, *,
                tokens, k, chunks, halves, steps):
    """A step's ``tokens``: ``rows_ref [N, a, 128]`` 32-bit (HBM; a row, one block), the step's
    ``tokens * k`` slots listed the counted first, ``n_ref[i]`` of them."""
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    slot = lax.rem(i, 2)
    span = tokens * k

    def bursts(n, one):
        """``one(c)`` for the first ``n`` of the tile's listed slots, in whole bursts of 16 (the
        body traced once: every unrolled copy is traced and lowered at each start of a process)."""
        def burst(b, carry):
            return lax.fori_loop(0, _CHUNK, lambda u, _: one(b * _CHUNK + u), carry, unroll=True)

        lax.fori_loop(0, pl.cdiv(n, _CHUNK), burst, None)

    def fetch_tile(listed, n, to):
        """Starts the copies of a tile's counted slots (and of the uncounted that fill the last
        burst): a slot's row lands at the slot's own place in the buffer."""
        def fetch(c):
            p = listed[c] & (_FLAG - 1)
            pltpu.make_async_copy(rows_ref.at[lax.div(p, span)], buf.at[to, lax.rem(p, span)], sem.at[to]).start()

        bursts(n, fetch)

    @pl.when(i == 0)
    def _first_tile():
        fetch_tile(list_ref, n_ref[0], 0)

    # the semaphore counts bytes: a wait a row, as many as were started
    bursts(n_ref[i], lambda c: pltpu.make_async_copy(rows_ref.at[0], buf.at[slot, 0], sem.at[slot]).wait())

    @pl.when(i + 1 < steps)
    def _next_tile():  # in flight while this tile is summed
        fetch_tile(next_ref, n_ref[jnp.minimum(i + 1, steps - 1)], 1 - slot)

    limit = limit_ref[0]

    def token(t, c):
        def add(j, sums):
            counts = back_ref[t * k + j] < limit
            gate = lax.select(counts, gates_ref[t * k + j], jnp.float32(0))
            w = buf[slot, t * k + j]  # [a, 128]: the slot's row, or where it got no copy anything
            w = lax.select(jnp.broadcast_to(counts, w.shape), w, jnp.zeros_like(w))
            if halves == 2:  # word (s, l): the row's columns 256 s + l and 256 s + 128 + l
                parts = (lax.bitcast_convert_type(w << 16, jnp.float32),
                         lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000), jnp.float32))
            else:
                parts = (w,)
            return tuple(acc + part * gate for acc, part in zip(sums, parts))

        sums = lax.fori_loop(0, k, add, (jnp.zeros(buf.shape[2:], jnp.float32),) * halves, unroll=True)
        # the output block is the [tokens, D] float32 TILE BY TILE, [tokens / 8, D / 128, 8, 128] as
        # rows of 128. Sublane s of a sum is the token's columns of chunk `halves * s + h`: row
        # t % 8 of tile (t / 8, chunk), a stride of `halves` tiles apart
        first = lax.div(t, 8) * (chunks * 8) + lax.rem(t, 8)
        for h, acc in enumerate(sums):
            held = (chunks - h + halves - 1) // halves
            if held:  # a bfloat16 row of one chunk has no odd half
                o_ref[pl.ds(first + 8 * h, held, stride=8 * halves), :] = acc[:held]
        return c

    lax.fori_loop(0, tokens, token, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sum_counted_rows(out, back, limit, gates, *, interpret: Optional[bool] = None) -> jax.Array:
    """``out [N, D]``, ``back [T, k]`` int32 (slot ``(t, j)``'s row of
    ``out``), ``limit`` (a slot COUNTS where ``back < limit``; ``limit <=
    N``), ``gates [T, k]`` float32 -> ``y [T, D]`` float32, ``y[t] = sum_j
    gates[t, j] * out[back[t, j]]`` over the counted ``j``, ascending, in
    float32: a slot that does not count adds exactly nothing, and its row is
    not copied (but for the up to 15 a grid step of 1,024 slots that fill
    the last burst of 16 copies: what a place holds is selected to zero
    wherever its slot does not count)."""
    from jax.experimental.pallas import tpu as pltpu

    n, d = out.shape
    t, k = back.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    halves = 2 if out.dtype == jnp.bfloat16 else 1  # columns a 32-bit word holds
    chunks = -(-d // _LANES)
    # a row as ONE block for a copy: whole 8-row tiles of the [N, ., 128] view
    view = -(-chunks // 8) * 8
    if halves == 2:
        rows = _words_view(out, interpret)
    else:  # 32-bit rows are their own words: XLA's view
        rows = jnp.pad(out.astype(jnp.float32), ((0, 0), (0, view * _LANES - d))).reshape(n, view, _LANES)
    # token slots a grid step: whole tiles of 1,024 (an SMEM block's), or one step of them all
    # (tokens in whole 8-row tiles of the output, their slots in whole bursts of copies)
    tokens = _ROWS // math.gcd(k, _ROWS) if t * k > _ROWS else -(-t // 16) * 16
    span = tokens * k
    steps = -(-t // tokens)
    if n * span > _FLAG:
        raise ValueError(f"{n} rows x {span} slots a step do not fit a listed slot's 30 bits")
    back = jnp.pad(back.astype(jnp.int32), ((0, steps * tokens - t), (0, 0)), constant_values=n).reshape(-1)
    gates = jnp.pad(gates.astype(jnp.float32), ((0, steps * tokens - t), (0, 0))).reshape(-1)
    limit = jnp.minimum(jnp.asarray(limit, jnp.int32), n).reshape(1)
    counted = (back < limit).reshape(steps, span)
    # a step's slots, the counted first: row * span + the slot's place in the step
    listed = jnp.minimum(back, n - 1).reshape(steps, span) * span + jnp.arange(span, dtype=jnp.int32)
    listed = lax.sort(jnp.where(counted, listed, listed | _FLAG), dimension=1, is_stable=False).reshape(-1)
    per_step = jnp.sum(counted, axis=1, dtype=jnp.int32)
    # the two buffers of a step's rows (a row's word sublanes in whole 8-sublane tiles) and the two
    # of its output tile: at k 8 a step is 1,024 slots (16.8 + 2.6 MB at ling3's 2,560 columns), at
    # k 10, the first k that is no power of two, 5,120 (83.9 + 12.6 MB at 3,072 columns: 98 MiB
    # with Mosaic's own), and the scope grows with it (the v5e has 128 MiB)
    need = 2 * 4 * _LANES * (span * (-(-(view // halves) // 8) * 8) + tokens * chunks)
    whole = pl.BlockSpec(memory_space=pltpu.SMEM)
    slots = pl.BlockSpec((span,), lambda i: (i,), memory_space=pltpu.SMEM)
    following = pl.BlockSpec((span,), lambda i: (jnp.minimum(i + 1, steps - 1),), memory_space=pltpu.SMEM)
    y = pl.pallas_call(
        functools.partial(_sum_kernel, tokens=tokens, k=k, chunks=chunks, halves=halves, steps=steps),
        grid=(steps,),
        in_specs=[whole, whole, slots, following, slots, slots, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tokens * chunks, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((steps * tokens * chunks, _LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, span, view // halves, _LANES), rows.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(64, -(-need // 2 ** 20) + 8) * 1024 * 1024),
        interpret=interpret,
        name="sum_counted_rows",
    )(limit, per_step, listed, listed, back, gates, rows)
    # [T / 8, D / 128, 8, 128] row-major IS [T, D] in its (8, 128) tiles: no pass
    return y.reshape(-1, chunks, 8, _LANES).transpose(0, 2, 1, 3).reshape(steps * tokens, chunks * _LANES)[:t, :d]
