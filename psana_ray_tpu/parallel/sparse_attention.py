"""Learned sparse attention: index scores, a per-query top-k key selection,
and grouped-query attention restricted to the selected keys.

The mechanism (DeepSeek-V3.2-Exp's description of DSA, at the sizes of
Keye-VL-2.0's ``sa_config``, 16 index heads of 64 over grouped-query
attention, and at DeepSeek-V3.2's own, 64 of 128 over latent attention):
a light INDEXER of ``H_I`` query heads
against ONE key head scores every earlier key for every query,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]) / sqrt(d_I)     s <= t

and each query attends, in every attention head alike, to
``Sel(t)``: the ``min(t + 1, topk)`` keys of largest ``I[t, .]``, equal
scores resolved towards the EARLIER key.

What is here, and what it is:

- :func:`select_keys` — one Pallas kernel per tile of queries: the tile's
  whole row of index scores is computed into VMEM (causal key tiles
  only), each query's ``topk``-th largest score is found EXACTLY by a
  32-step bisection over the scores' bit patterns (no sort, no
  ``lax.top_k``: a k of 2048 over 34,304 is a sort on the TPU), ties at
  that score are cut at the key index that ``Sel`` would cut them at, and
  the selection leaves as an int8 mask ``[S/bq, ceil(S/mk), bq, mk]`` (tile
  major, so that the attention kernel's mask tile is one block; a last
  tile past the sequence ends in zeros). The key
  tile ``mk`` it is WRITTEN in is the attention's, chosen for that
  kernel's speed (:func:`mask_tile`), not the ``bk`` the selection scores
  and counts in.
- :func:`masked_gqa_attention` — a flash kernel over ALL causal tiles
  that applies that mask: the MASKED-DENSE form. It does the work of
  dense causal attention (``S^2/2`` pairs a head) whatever the selection;
  a form that visits only live tiles, or gathers the selected keys, is
  the optimisation this form is the yardstick for. The ``G`` key-value
  heads each serve ``H/G`` query heads, whose query tiles are stacked
  into one ``[H/G * bq, d]`` operand so that a key tile is loaded once
  for all of them. ONE body (``_causal_kernel``; until PR 68 a selection
  over grouped-query heads ran a second one, on ``[S, .]`` operands over a
  rectangular grid) on ``[B, S, .]`` operands, whose grid
  holds the tiles at or below the diagonal only (a table of ``(query
  tile, key tile)`` pairs rides in as scalar prefetch), at any head width
  (heads of 64, LFM2's, go head-major into it; heads of whole lane blocks
  — latent attention's alone in their groups, Laguna's six and nine a
  group, Keye's eight — are read and written as column blocks of the token-major arrays
  their products wrote), with values of a width of their own, a part of
  the score read from ONE key for all heads (latent attention; that
  part of the QUERY comes float32 and unturned where its angle tables
  come with it, and the kernel turns a query tile of it once) and a
  gate a (token, head) applied where the output is written (Laguna).
  Without a
  mask it is plain causal attention over a batch of sequences; with one
  (one sequence) it is the selection, over latent or grouped-query
  attention, masked-dense
  again: the mask's tile is one more operand of a grid step, and its key
  tile is the kernel's — where that tile does not divide ``S``
  (:func:`mask_tile`: 34,304 keys in sixteen tiles of 2,176) the keys
  and values are padded with zeros to the whole tiles, which the mask
  closes. Under a WINDOW (``window``: a query attends to
  its own key and the ``window - 1`` before it; Laguna's sliding layers,
  Phi-4-mini-flash's differential ones) the keys follow the band
  DIAGONALLY (PR 77): ONE grid step a query tile, against ONE window of
  ``window + bq`` key rows that starts ``window`` rows before the tile's
  first (:func:`band_keys`; Element-addressed blocks: the rows are no
  whole blocks of their own size), so every key of a row is in the step
  and the softmax is ONE pass with no running state: the same body under
  a name of its own (``windowed_gqa_attention``). The query tile follows
  from the heads a group and the window (:func:`causal_tiles`).
- :func:`live_tiles` — how many ``stat_tile`` x ``stat_tile`` tiles at
  or below the diagonal hold a selected pair (from the flags the selection
  kernel writes beside its mask), and how many there are: what a
  tile-skipping kernel could save.

Off the TPU the kernels run in Pallas interpret mode (tests, rehearsals).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30
INT_MIN = -(2**31)
_VMEM_LIMIT = 100 * 1024 * 1024  # of the v5e's 128 MiB; the default scope is 16
# of the batched causal kernel's stacked score tile [heads a group x query tile, key tile] float32
# (:func:`causal_tiles`): a fifth of the limit, for the tile, its exponentials and their bf16 copy
SCORE_TILE_BYTES = _VMEM_LIMIT // 5
# a windowed call's widest query tile, as a share of its window (the keys it is run against are no
# share of anything: the window and the tile, `band_keys`). The kernel ALONE on the v5e, 2 x 8,704
# tokens under a window of 512, twenty calls a reading, ms (my chip runs, PR 77, seed 7700000002;
# **bold** what the rules give; the parent's body over the band's 256 x 512 TILES read again last):
# laguna's call (9 heads of 128 a group turned by the kernel, gated), parent 8.12 (three parts
# 7.50) -> a query tile of 128 against 640 keys 3.57 at one part, 3.62 at three; 256 against 768
# 3.92, **3.71** at three, 3.65 at nine; 512 against 1,024 4.62 at three; phi4flash's (10 groups of 2
# half-heads of 64 over values of 128, head-major), parent 3.01 -> 128: 1.85; 256: **1.67** (two
# parts 1.62); 512: 1.76. A tile of 128 meets fewer keys a row outside the band (640 for 768)
# and reads laguna's call 4% faster and phi4flash's 11% SLOWER than 256: the share stays
BAND_QUERY_TILE = 0.5
BLOCK_HEADS = (8, 4, 2)  # the heads a grid step may take where each is alone in its group
# of the score tiles a kernel body's unrolled heads write, over its branches (:func:`heads_a_step`):
# 35.7 and 18.9 MB read faster than one head a step, 37.9 a third SLOWER (PR 66). The falloff is a
# BLOCK OF HEADS', not the written order's: a stacked group cut into parts (:func:`parts_a_step`)
# writes the score bytes its one product wrote, and read faster at lfm2's 37.9 MB (four parts, -20%),
# at nemotron3's 35.6 in sixteen parts (32 unrolled part bodies, -18%) and at minicpm_sala's sixteen
# (PR 75, the kernels alone): neither the unrolled bytes nor the number of unrolled bodies makes a body slow. What a
# block adds and parts do not is every head's OWN key and value block, accumulator and turned
# scratch in VMEM and in the pipeline's copies; no bundle dump was taken, so that is where to look
UNROLLED_SCORE_BYTES = 36_000_000
# of ONE part's score tile where a stacked group's rows are cut into parts (:func:`parts_a_step`): parts
# of 1.05-1.1 MB read fastest or within 2% of it, parts of 0.52 MB slower than no cut (PR 75); and
# the part bodies a kernel may hold unrolled over its branches: each costs a start 0.1 s
PART_SCORE_BYTES = 1_000_000
PART_BODIES = 8
MASK_TILE = 2176  # the widest key tile a selection's mask is written in (:func:`mask_tile`): on the
# v5e the kernel under it read 34.8 ms a layer at 512 x 2,176 and 39.4 at 256 x 4,352 (PR 47)


@dataclasses.dataclass(frozen=True)
class BlockSelection:
    """The sizes of a selection of BLOCKS of keys a key head, scored by the
    attention's own queries against mean-pooled keys (InfLLM-V2's
    ``sparse_config``, arXiv:2509.24663): a pooled key is the mean of
    ``kernel_size`` keys, one every ``kernel_stride``; a query keeps ``topk``
    blocks of ``block_size`` keys, the first ``init_blocks`` and the latest
    ``window_size // block_size`` among them whatever their scores; a sequence
    of at most ``dense_len`` tokens attends densely (:func:`select_blocks`)."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        bs, st = self.block_size, self.kernel_stride
        if (self.kernel_size != 2 * st or bs % st or bs & (bs - 1) or bs > MASK_TILE
                or self.init_blocks + self.window_size // bs > self.topk):
            raise ValueError(f"{self}: built for pooled keys of two strides, blocks of a power of "
                             "two of whole strides, and forced blocks within topk")

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size

    def selects(self, s: int) -> bool:
        """Whether a sequence of ``s`` tokens selects (else it attends densely)."""
        return s > self.dense_len

    def tiles(self, s: int) -> Tuple[int, int, int]:
        """``(key tile, blocks a tile, flag lanes)`` the attention under the
        selection runs a sequence of ``s`` in: the key tile is the widest
        power of two of blocks (at most a lane tile's 128, so that a tile's
        flags are ONE aligned run of a lane tile) that stays within
        :data:`MASK_TILE` and within the sequence (32 blocks of 64: 2,048, and
        34,304 keys are padded to 17 such tiles, as :func:`mask_tile` pads them
        to 16 of 2,176); the flags of every (padded) block, whole lane tiles."""
        a_tile = 1
        while (a_tile < 128 and 2 * a_tile * self.block_size <= MASK_TILE
               and 2 * a_tile * self.block_size <= max(s, self.block_size)):
            a_tile *= 2
        tiles = -(-s // (a_tile * self.block_size))
        return a_tile * self.block_size, a_tile, -(-tiles * a_tile // 128) * 128

    def pairs(self, s: int) -> int:
        """The (query, key) pairs ONE key head's selection keeps of a sequence
        of ``s``: a query at ``t`` keeps ``min(t // block_size + 1, topk)``
        blocks, its own block among them (the latest blocks are forced) and of
        that one the keys up to ``t``: a constant of the shapes."""
        t = np.arange(s)
        return int(np.sum((np.minimum(t // self.block_size + 1, self.topk) - 1) * self.block_size
                          + t % self.block_size + 1))


def pick_tile(s: int, want: int) -> int:
    """The largest multiple of 128 that divides ``s`` and is at most
    ``want``; ``want`` itself where it divides ``s`` (small test sizes);
    else the whole of ``s`` (one tile)."""
    if s % want == 0:
        return want
    for b in range(min(want, s) // 128 * 128, 0, -128):
        if s % b == 0:
            return b
    return s


def mask_tile(s: int, block_k: int) -> int:
    """The key tile a selection's mask is WRITTEN in, which is the key tile
    the attention under it runs in: the widest whole number of 128-lane
    blocks that divides ``s`` up to :data:`MASK_TILE` (8,704 = 68 x 128 ->
    2,176 = 17 x 128); where that is under half of :data:`MASK_TILE` and
    not ``s`` itself (34,304 = 4 x 67 lane blocks: 512), a tile that does
    NOT divide ``s`` — the narrowest whole number of lane blocks that
    covers ``s`` in as many tiles as :data:`MASK_TILE` would (34,304 -> 16
    tiles of 2,176 = 34,816 keys, +1.5%): the mask's last tile ends in
    zeros and the attention pads its keys to the whole tiles
    (:func:`_causal_attention`; the selection is causal, so a key laid
    after the sequence is selected by no query). The pieces' ``block_k``
    where ``s`` is no whole number of lane blocks (small test sizes). One
    layer's kernel alone on the v5e at 34,304 tokens, 8 heads a group of 4,
    ms (my chip runs, PR 68): 256 x 512 over 34,304 keys 111.75, over
    34,816: 256 x 1,088 93.31, 256 x 2,176 **84.93**, 128 x 2,176 85.66,
    512 x 1,088 94.09; :func:`select_keys` writing it in slices of what
    piece and tile share 35.27 / 39.76 (1,088: 64 lanes) / 35.33, and a
    piece in one store (as it is written now) 35.37 at 512, 35.40 at 2,176."""
    if s % 128:
        return block_k
    tile = pick_tile(s, MASK_TILE)
    if tile == s or 2 * tile >= MASK_TILE:
        return tile
    tiles = -(-s // MASK_TILE)
    return -(-s // (128 * tiles)) * 128


def _interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def sortable_key(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


# ---------------------------------------------------------------------------
# index scores + selection
# ---------------------------------------------------------------------------

def _select_kernel(q_ref, k_ref, w_ref, mask_ref, live_ref, keys_ref, *, topk, scale, block_q,
                   block_k, n_kb, mask_k):
    qi = pl.program_id(0)
    n_heads = q_ref.shape[0]
    row0 = qi * block_q
    n_live = (row0 + block_q + block_k - 1) // block_k  # key tiles at or below the diagonal
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols0 = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def score_tile(kb, carry):
        kt = k_ref[pl.ds(pl.multiple_of(kb * block_k, block_k), block_k), :]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(n_heads):
            s = jax.lax.dot_general(q_ref[j], kt, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + w_ref[j] * jnp.maximum(s, 0.0)
        key = sortable_key(acc * scale)
        keys_ref[kb] = jnp.where(cols0 + kb * block_k > rows, INT_MIN, key)
        return carry

    jax.lax.fori_loop(0, n_live, score_tile, 0)

    fold = 128 if block_k % 128 == 0 else block_k  # lanes of the running count

    def count(pred):
        """Per query, over its causal keys: how many satisfy ``pred(keys, kb)``.
        The running count is one lane block wide, so that it stays in registers."""
        def body(kb, acc):
            hit = pred(keys_ref[kb], kb).astype(jnp.int32)
            for c in range(0, block_k, fold):
                acc = acc + hit[:, c:c + fold]
            return acc

        acc = jax.lax.fori_loop(0, n_live, body, jnp.zeros((block_q, fold), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    t = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    want = jnp.minimum(t + 1, topk)  # |Sel(t)|

    # the want-th largest key, bit by bit from the top: the largest T with
    # count(key >= T) >= want. Dead entries hold INT_MIN and T never does.
    n0 = count(lambda k, kb: k >= 0)
    ok = n0 >= want
    lo = jnp.where(ok, 0, INT_MIN).astype(jnp.int32)
    n_lo = jnp.where(ok, n0, t + 1)

    def bit_step(i, carry):
        lo, n_lo = carry
        cand = lo + jnp.left_shift(jnp.int32(1), 30 - i)
        n = count(lambda k, kb: k >= cand)
        ok = n >= want
        return jnp.where(ok, cand, lo), jnp.where(ok, n, n_lo)

    thr, n_ge = jax.lax.fori_loop(0, 31, bit_step, (lo, n_lo))

    # n_ge - want keys too many share the score thr: Sel keeps the EARLIER
    # ones, so the cut is the largest bound c with
    # count(key > thr) + count(key == thr, index < c) <= want. Float ties
    # are rare: the search runs only in a tile that has one.
    excess = n_ge - want
    bits = (n_kb * block_k).bit_length()

    def find_cut(_):
        n_gt = count(lambda k, kb: k > thr)

        def step(i, c):
            cand = c + jnp.left_shift(jnp.int32(1), bits - 1 - i)
            n = n_gt + count(lambda k, kb: (k == thr) & (cols0 + kb * block_k < cand))
            return jnp.where(n <= want, cand, c)

        return jax.lax.fori_loop(0, bits, step, jnp.zeros((block_q, 1), jnp.int32))

    def keep_all(_):
        return jnp.full((block_q, 1), 2**bits, jnp.int32)

    cut = jax.lax.cond(jnp.max(excess) > 0, find_cut, keep_all, 0)

    # the mask leaves in key tiles of mask_k, a piece in ONE store where it lies inside a tile and
    # in two where it crosses into the next (512-wide pieces in 2,176-wide tiles: every fourth or
    # fifth, cut at a whole lane block); the flags stay the pieces' own. (Until PR 68 a piece left
    # in slices of gcd(block_k, mask_k), four stores each: the same bytes in as many ms, but 268
    # unrolled stores at 34,304 keys cost a start 1.1 s of tracing where 82 cost nothing that shows)
    live_ref[...] = jnp.zeros(live_ref.shape, jnp.int32)
    mask_ref[...] = jnp.zeros(mask_ref.shape, jnp.int8)
    for kb in range(n_kb):
        @pl.when(kb < n_live)
        def _live(kb=kb):
            k = keys_ref[kb]
            sel = ((k > thr) | ((k == thr) & (cols0 + kb * block_k < cut))).astype(jnp.int32)
            live_ref[0, :, kb:kb + 1] = jnp.max(sel, axis=(0, 1), keepdims=True)
            sel = sel.astype(jnp.int8)
            c = 0
            while c < block_k:
                tile, at = divmod(kb * block_k + c, mask_k)
                run = min(block_k - c, mask_k - at)
                mask_ref[0, tile, :, at:at + run] = sel[:, c:c + run]
                c += run


def select_keys(q_idx, k_idx, w_idx, *, topk: int, block_q: int = 128, block_k: int = 512,
                mask_k: Optional[int] = None,
                interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """``q_idx [H_I, S, d_I]`` and ``k_idx [S, d_I]`` (after their rotary
    and norm), ``w_idx [S, H_I]`` float32 -> the selection as an int8 mask
    ``[S/bq, ceil(S/mk), bq, mk]``: entry ``[a, b, i, j]`` is 1 iff key
    ``b*mk + j`` is in ``Sel(a*bq + i)`` (where ``mk`` does not divide
    ``S`` the last tile's tail, keys past the sequence, is zeros); and
    which ``bq x bk`` pieces of it hold a selected pair, int32 ``[S/bq,
    S/bk]`` (reducing the mask for that afterwards took 38 ms a layer on
    the v5e: my chip run, PR 36). The kernel scores, counts and flags over
    the ``S`` real keys in pieces of ``bk``; the mask's own key tile ``mk``
    (``mask_k``, by default :func:`mask_tile`'s: 2,176 at 8,704 keys and,
    sixteen tiles over 34,816, at 34,304) is the attention's to run in,
    and only the last write knows it."""
    from jax.experimental.pallas import tpu as pltpu

    n_heads, s, d = q_idx.shape
    bq, bk = pick_tile(s, block_q), pick_tile(s, block_k)
    mk = mask_tile(s, bk) if mask_k is None else int(mask_k)
    n_qb, n_kb, n_mk = s // bq, s // bk, -(-s // mk)  # (the last key tile's tail: zeros)
    w = jnp.transpose(w_idx.astype(jnp.float32))[:, :, None]  # [H_I, S, 1]
    kernel = functools.partial(_select_kernel, topk=int(topk), scale=float(d) ** -0.5,
                               block_q=bq, block_k=bk, n_kb=n_kb, mask_k=mk)
    lanes = -(-n_kb // 128) * 128
    mask, live = pl.pallas_call(
        kernel,
        grid=(n_qb,),
        in_specs=[
            pl.BlockSpec((n_heads, bq, d), lambda i: (0, i, 0)),
            pl.BlockSpec((s, d), lambda i: (0, 0)),
            pl.BlockSpec((n_heads, bq, 1), lambda i: (0, i, 0)),
        ],
        out_specs=[pl.BlockSpec((1, n_mk, bq, mk), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((1, 1, lanes), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_qb, n_mk, bq, mk), jnp.int8),
                   jax.ShapeDtypeStruct((n_qb, 1, lanes), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((n_kb, bq, bk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(interpret),
        name="select_keys",
    )(q_idx, k_idx, w)
    return mask, live[:, 0, :n_kb]


def mask_to_dense(mask: jax.Array) -> jax.Array:
    """The tile-major selection mask as a plain ``[S, S]`` boolean."""
    n_qb, n_kb, bq, bk = mask.shape
    return jnp.transpose(mask, (0, 2, 1, 3)).reshape(n_qb * bq, n_kb * bk) != 0


def causal_tile_count(s: int, stat_tile: int = 512) -> int:
    """How many ``stat_tile``-square tiles lie at or below the diagonal of
    one sequence of ``s`` tokens: what :func:`live_tiles` counts as
    ``causal``, and as ``live`` too where every earlier key is attended."""
    n = s // pick_tile(s, stat_tile)
    return n * (n + 1) // 2


def band_tile_count(s: int, window: int, stat_tile: int = 512) -> int:
    """How many of :func:`causal_tile_count`'s tiles MEET the band ``t -
    window < j <= t`` of one sequence of ``s`` tokens (33 of 153 at 8,704
    tokens under a window of 512): what a windowed layer counts as ``live``."""
    tile = pick_tile(s, stat_tile)
    return len(_band_tiles(s, tile, tile, window))


def live_tiles(live: jax.Array, s: int, stat_tile: int = 512) -> Tuple[jax.Array, int]:
    """``(live, causal)``: of the ``stat_tile``-square tiles at or below
    the diagonal of a sequence of ``s``, how many hold a selected pair
    (int32 scalar, on the device) and how many there are (static), from
    :func:`select_keys`' ``[S/bq, S/bk]`` flags of its own pieces (whatever
    key tile the mask beside them was written in)."""
    n_qb, n_kb = live.shape
    block_q, block_k = s // n_qb, s // n_kb
    tile = pick_tile(s, stat_tile)
    if tile % block_q or tile % block_k:
        raise ValueError(f"the statistics' tile {tile} is no multiple of the mask's "
                         f"{block_q} x {block_k}")
    n = s // tile
    big = jnp.any(live.reshape(n, tile // block_q, n, tile // block_k) != 0, axis=(1, 3))
    return jnp.sum(big.astype(jnp.int32)), n * (n + 1) // 2


# ---------------------------------------------------------------------------
# a selection of BLOCKS a key head, scored by the attention's own operands
# ---------------------------------------------------------------------------

BIG = 1e30  # a forced block's score: above every sum of a group's probabilities


def pooled_keys(k, g: int, sel: BlockSelection, lanes: int):
    """``k [S, G*d]`` -> the keys mean-pooled, ``K[j] = mean(k[st*j : st*j +
    2*st])`` for ``j < S/st - 1``, in the type of ``k``, laid out for
    :func:`select_blocks`' kernel: ``[G, R*lanes, d]`` with ``R = block_size /
    kernel_stride`` and pooled key ``R*n + c`` at row ``c*lanes + n`` (block
    ``n``'s ``c``-th pooled key in lane ``n`` of segment ``c``; zeros where
    there is none)."""
    s, d = k.shape[0], k.shape[1] // g
    st, ratio = sel.kernel_stride, sel.block_size // sel.kernel_stride
    runs = jnp.sum(k.astype(jnp.float32).reshape(s // st, st, g, d), axis=1)
    pooled = ((runs[:-1] + runs[1:]) / sel.kernel_size).astype(k.dtype)  # [S/st - 1, G, d]
    pooled = jnp.pad(pooled, ((0, ratio * lanes - pooled.shape[0]), (0, 0), (0, 0)))
    return jnp.transpose(pooled.reshape(lanes, ratio, g, d), (2, 1, 0, 3)).reshape(g, ratio * lanes, d)


def _select_blocks_kernel(q_ref, pool_ref, flags_ref, live_ref, *scores_ref, sel, rep, d, block_q,
                          lanes, n_pool, piece):
    from jax.experimental.pallas import tpu as pltpu

    qi = pl.program_id(1)
    f32 = jnp.float32
    st, bs, ratio = sel.kernel_stride, sel.block_size, sel.block_size // sel.kernel_stride
    t = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    n = jax.lax.broadcasted_iota(jnp.int32, (block_q, lanes), 1)
    # pooled key R*n + c is VISIBLE to a query where it ends at or before it
    seen = jnp.concatenate([(st * (ratio * n + c) + sel.kernel_size - 1 <= t)
                            & (ratio * n + c < n_pool) for c in range(ratio)], axis=1)
    pool = pool_ref[...]
    total = jnp.zeros((block_q, ratio * lanes), f32)  # the group's heads' probabilities, summed
    for h in range(rep):
        q = q_ref[h] if len(q_ref.shape) == 3 else q_ref[:, h * d:(h + 1) * d]
        s = jax.lax.dot_general(q, pool, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        s = jnp.where(seen, s, NEG_INF)
        p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
        total = total + p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    # a block's score: the largest of the R + 1 pooled keys that overlap it, R*n - 1 .. R*n + R - 1
    parts = [total[:, c * lanes:(c + 1) * lanes] for c in range(ratio)]
    before = jnp.where(n == 0, 0.0, pltpu.roll(parts[-1], 1, 1))  # lane n meets pooled key R*n - 1
    score = functools.reduce(jnp.maximum, parts + [before])
    if scores_ref:
        scores_ref[0][...] = score
    last = t // bs  # the query's own block
    forced = (n < sel.init_blocks) | (n > last - sel.window_blocks)
    key = jnp.where(n <= last, sortable_key(jnp.where(forced, BIG, score)), INT_MIN)
    want = jnp.minimum(last + 1, sel.topk)

    def count(hit):
        return jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)

    # the want-th largest key, bit by bit from the top (a score is at least 0: so is its key)
    def bit_step(i, carry):
        lo, n_lo = carry
        cand = lo + jnp.left_shift(jnp.int32(1), 30 - i)
        hits = count(key >= cand)
        ok = hits >= want
        return jnp.where(ok, cand, lo), jnp.where(ok, hits, n_lo)

    thr, _ = jax.lax.fori_loop(0, 31, bit_step, (jnp.zeros((block_q, 1), jnp.int32), count(key >= 0)))
    # blocks that share the score thr: the LOWER ones stay, up to the largest bound c with
    # count(key > thr) + count(key == thr, n < c) <= want (scores that underflow to 0 are equal)
    above = count(key > thr)
    bits = lanes.bit_length()

    def cut_step(i, c):
        cand = c + jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(above + count((key == thr) & (n < cand)) <= want, cand, c)

    cut = jax.lax.fori_loop(0, bits, cut_step, jnp.zeros((block_q, 1), jnp.int32))
    kept = (key > thr) | ((key == thr) & (n < cut))
    flags_ref[...] = kept.astype(jnp.int8)
    # which pieces of `piece` blocks hold a kept block: a product against the pieces' membership
    of_piece = (jax.lax.broadcasted_iota(jnp.int32, (lanes, 128), 0) // piece
                == jax.lax.broadcasted_iota(jnp.int32, (lanes, 128), 1))
    held = jnp.dot(kept.astype(jnp.bfloat16), of_piece.astype(jnp.bfloat16),
                   preferred_element_type=f32)
    live_ref[...] = (jnp.max(held, axis=0, keepdims=True) > 0).astype(jnp.int32)[None]


def select_blocks(q, k, *, num_kv_heads: int, selection: BlockSelection, block_q: int = 128,
                  with_scores: bool = False, interpret: Optional[bool] = None):
    """``q [S, H*d]`` (already scaled by ``d**-0.5``) and ``k [S, G*d]``, ONE
    sequence's, the attention's own operands -> a selection of BLOCKS a KEY
    HEAD ``g`` (query heads ``g*H/G ..``), InfLLM-V2's, with ``K`` the keys
    mean-pooled (:func:`pooled_keys`):

        p[t, h, j] = softmax_j(q[t, h] . K[g, j])      over the pooled keys that END at or before t
        r[t, g, j] = sum_{h in g} p[t, h, j]           (all zero where none does)
        b[t, g, n] = max_{R n - 1 <= j < R n + R} r[t, g, j]      for blocks n <= t // block_size
        b = +inf for n < init_blocks and for the latest window_size / block_size blocks
        Sel(t, g) = the min(t // block_size + 1, topk) largest b, equal scores to the LOWER n

    as int8 flags ``[G, S, W]`` (entry ``[g, t, n]``: block ``n`` in ``Sel(t,
    g)``; ``W`` whole lane tiles: ``selection.tiles(S)``), and which pieces of
    ``pick_tile(S, 512)`` keys hold a kept block of a query tile, int32 ``[G,
    S/bq, S/piece]`` (what :func:`live_tiles` reads, a group at a time); with
    ``with_scores`` (a test's) ``b [G, S, W]`` float32 third, as scored, the
    forced and unseen blocks not yet marked. ONE kernel a (key head, query
    tile): a head's scores against ALL pooled keys are a ``[bq, R*W]`` tile
    that never leaves VMEM (the sixteen heads' ``[16, S, S/16]`` would be 4.7
    GB at 34,304), the softmax is the score's own (not InfLLM-V2's second,
    coarser pooling of its denominator), and the ``topk``-th largest score is
    found by :func:`select_keys`' bit-by-bit search, over a row of ``W``
    candidates. The pooled keys lie block-major in ``R`` lane segments, so a
    block's maximum over its ``R + 1`` overlapping pooled keys is a maximum of
    whole lane segments and one rotation."""
    from jax.experimental.pallas import tpu as pltpu

    s, g = q.shape[0], int(num_kv_heads)
    d = k.shape[1] // g
    rep = q.shape[1] // (g * d)
    sel = selection
    if s % sel.block_size or q.shape != (s, g * rep * d):
        raise ValueError(f"a selection of blocks of {sel.block_size} keys over q {q.shape} and k "
                         f"{k.shape}: not whole blocks of one sequence")
    _, _, lanes = sel.tiles(s)
    bq = pick_tile(s, block_q)
    ratio = sel.block_size // sel.kernel_stride
    n_pool = s // sel.kernel_stride - 1
    keys = pick_tile(s, 512)  # the statistics' piece, in keys
    if keys % sel.block_size:
        raise ValueError(f"the statistics' piece of {keys} keys is no whole blocks of {sel.block_size}")
    pool = pooled_keys(k, g, sel, lanes)
    if d % 128:  # heads narrower than a lane tile (tests) go head-major
        q_in = jnp.transpose(q.reshape(s, g, rep, d), (1, 2, 0, 3))
        q_spec = pl.BlockSpec((None, rep, bq, d), lambda gi, i: (gi, 0, i, 0))
    else:  # a group's heads are ONE token-major block, read where W_q's product wrote them
        q_in, q_spec = q, pl.BlockSpec((bq, rep * d), lambda gi, i: (i, gi))
    out_specs = [pl.BlockSpec((None, bq, lanes), lambda gi, i: (gi, i, 0)),
                 pl.BlockSpec((None, 1, 1, 128), lambda gi, i: (gi, i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((g, s, lanes), jnp.int8),
                 jax.ShapeDtypeStruct((g, s // bq, 1, 128), jnp.int32)]
    if with_scores:
        out_specs.append(pl.BlockSpec((None, bq, lanes), lambda gi, i: (gi, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((g, s, lanes), jnp.float32))
    flags, live, *scores = pl.pallas_call(
        functools.partial(_select_blocks_kernel, sel=sel, rep=rep, d=d, block_q=bq, lanes=lanes,
                          n_pool=n_pool, piece=keys // sel.block_size),
        grid=(g, s // bq),
        in_specs=[q_spec, pl.BlockSpec((None, ratio * lanes, d), lambda gi, i: (gi, 0, 0))],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(interpret),
        name="select_blocks",
    )(q_in, pool)
    return (flags, live[:, :, 0, :s // keys], *scores)


def blocks_to_dense(flags: jax.Array, s: int, block_size: int) -> jax.Array:
    """A key head's block flags ``[S, W]`` as a plain ``[S, S]`` boolean over
    the keys, the diagonal block cut at the query."""
    open_ = jnp.repeat(flags[:, :s // block_size] != 0, block_size, axis=1)
    return open_ & (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])


# ---------------------------------------------------------------------------
# masked grouped-query flash attention
# ---------------------------------------------------------------------------

def _turned_tile(x, cos, sin, scale: float, dtype):
    """A query tile's rotary part ``x [rep, bq, ds]`` float32 as its product
    wrote it -> ``[rep * bq, ds]`` turned by the tile's angles (``cos, sin
    [bq, ds]``: ``[cos | cos]`` and ``[sin | sin]``), times ``scale``,
    rounded once to ``dtype``: ``decoder.rotate``'s arithmetic, ``x * [cos |
    cos] + [-x2 | x1] * [sin | sin]``, the halves cut at a static lane. One
    layer's kernel at kimi's shape on the v5e, ms (my chip runs, PR 61): the
    query turned before it 32.87; this form 33.45, as with the sign in the
    sine table and no negation; the rotate-half as a product against the
    signed permutation at the highest precision (six bf16 passes) 34.01."""
    rep, bq, ds = x.shape
    half = jnp.concatenate([-x[..., ds // 2:], x[..., :ds // 2]], axis=-1)
    return ((x * cos[None] + half * sin[None]) * scale).astype(dtype).reshape(rep * bq, ds)


def _turned_head(x, cos, sin, width: int, scale: float):
    """One head's rows ``x [rows, d]`` float32 as its product wrote them, ``d``
    whole lane blocks, turned by the rows' tables (``cos, sin [rows, d]``:
    ``decoder.turn_tables(angles, d)``, ``[cos | cos | 1]`` and the SIGNED
    sine ``[-sin | sin | 0]``) over its leading ``width`` components, float32:
    ``decoder._turn_leading``'s arithmetic in whole vregs, ``x * cos +
    rolled * sin`` with ``rolled`` the head's lanes rotated so that each
    turned lane meets its pair's other half (``a - b*s`` and ``a + b*(-s)``
    are the same float32) — at ``width == d`` ONE rotation by ``d / 2``;
    under a partial rotary two, by ``width / 2`` each way, chosen by lane (a
    lane that passes meets a one and a zero) — and the turned lanes times
    ``scale`` (YaRN's factor on the cosines and sines) where it is not 1."""
    from jax.experimental.pallas import tpu as pltpu

    d, half = x.shape[-1], width // 2
    rolled = pltpu.roll(x, d - half, 1)  # lane i meets lane i + half
    if width != d:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        rolled = jnp.where(lane < half, rolled, pltpu.roll(x, half, 1))
    out = x * cos + rolled * sin
    if scale != 1.0:
        out = out * (scale if width == d else jnp.where(lane < width, scale, 1.0))
    return out


def _causal_kernel(qi_ref, kb_ref, q_ref, k_ref, *rest, block_q, block_k, shared, masked,
                   gated=False, window=None, turn=None, rotary=None, heads=1, joint=False,
                   stack=False, blocks=None, cut=1):
    rest = list(rest)
    # a BLOCK of `heads` heads, each alone in its group, is a wider block of the same arrays: with
    # `joint` keys and values are ONE block, head h's keys then its values
    v_ref, parts = (k_ref, 2) if joint else (rest.pop(0), 1)
    if rotary is not None:  # q and k are float32 and UNTURNED: the query tile's and the key tile's
        # rows of the two tables, and last of the scratch the turned query tile, stacked
        width, turned_by, q_scale = rotary
        (cos_q, sin_q, cos_k, sin_k), rest = rest[:4], rest[4:]
    if rotary is not None or stack:  # last of the scratch: the query tile's heads, stacked
        stacked_ref = rest.pop()
    if shared:  # the part of the score that all heads read from ONE key
        qs_ref, ks_ref = rest.pop(0), rest.pop(0)
    if turn is not None:  # the shared query part is float32 and UNTURNED: its tile's two tables,
        # and last of the scratch the turned tile, written at the tile's first key step
        cos_ref, sin_ref, turned_ref = rest.pop(0), rest.pop(0), rest.pop()
    if masked:  # the selection's tile, one for all heads: it is causal by construction
        mask_ref = rest.pop(0)
    if gated:  # a float32 scalar a (token, head) on the output: the query tile's, [1, bq, H]
        gate_ref = rest.pop(0)
    o_ref, m_ref, l_ref, acc_ref = rest
    gi, t = pl.program_id(1), pl.program_id(2)
    qi, kb = qi_ref[t], kb_ref[t]
    rep, _, d = q_ref.shape
    if rotary is not None or stack or heads > 1:  # q is ONE token-major block [1, bq, heads * rep * d]
        d = k_ref.shape[-1] // (heads * parts)
        rep = q_ref.shape[-1] // (heads * d)
    dv = v_ref.shape[-1] // (heads * parts)
    rows = rep * block_q  # the group's query heads, stacked: one product serves them all

    # (alone in its step a head reads the whole refs, as it was traced before there were blocks:
    # `tests/test_decoder_kimi.py -k traces_the_kernel` holds that body's jaxpr)
    # (`cut > 1`: a STACKED group's rows in `cut` parts of whole heads, the same slices `cut` times as
    # short. `heads > 1` are heads alone in their groups, `cut > 1` heads that share their keys)
    def of(h):  # head h of the block's: its rows of the stacked scratch (all of them where it is alone)
        return slice(None) if heads * cut == 1 else slice(h * rows // cut, (h + 1) * rows // cut)

    def lanes(ref, h, width, part=0):  # head h's part: a lane block of a token-major tile
        if heads == 1:
            return ref[...]
        at = (h * parts + part) * width
        return ref[..., at:at + width]

    def _reset():  # a query tile's first key step (under a window its only one: no state to reset)
        if window is None:
            m_ref[:] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
            l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)
        if turn is not None:  # once a query tile: every key step reads the scratch
            qs = qs_ref[...]  # [rep, bq, ds], or a block's [hb, 1, bq, ds]: its heads lead
            turned_ref[...] = _turned_tile(qs if heads == 1 else qs.reshape(heads, block_q, -1),
                                           cos_ref[...], sin_ref[...], turn, turned_ref.dtype)
        if rotary is not None:  # the turn and the stacking are one pass, head by head
            cos, sin = cos_q[...], sin_q[...]
            for r in range(heads * rep):
                head = _turned_head(q_ref[0, :, r * d:(r + 1) * d], cos, sin, width, turned_by)
                stacked_ref[r * block_q:(r + 1) * block_q] = (head * q_scale).astype(stacked_ref.dtype)
        elif stack:  # the stacking without the turn: a head's lane block becomes its rows
            for r in range(rep):
                stacked_ref[r * block_q:(r + 1) * block_q] = q_ref[0, :, r * d:(r + 1) * d]

    def update(with_diagonal, with_lower_edge=False):
        open_ = []  # where a pair counts: the selection's tile or the band's edges, ONE for every head
        tiles = []  # a stacked group's key tile (turned, where the kernel turns) and value tile, ONE for its parts

        def part(h):  # part h of a stacked group's rows, and the group's key and value tiles
            if not tiles:
                k = k_ref[...]
                if rotary is not None:
                    k = _turned_head(k, cos_k[...], sin_k[...], width, turned_by).astype(stacked_ref.dtype)
                tiles.append((k, v_ref[...]))
            if stack or rotary is not None:
                return (stacked_ref[of(h)], *tiles[0])
            return (q_ref[h * rep // cut:(h + 1) * rep // cut].reshape(rows // cut, d), *tiles[0])

        def head(h):  # head h's rows (a group's, stacked), and its key and value tiles
            v = lanes(v_ref, h, dv, parts - 1)
            if stack:
                q, k = stacked_ref[...], k_ref[...]
            elif rotary is None:
                q = q_ref[...].reshape(rows, d) if heads == 1 else q_ref[0, :, h * d:(h + 1) * d]
                k = lanes(k_ref, h, d)
            else:  # the key tile is turned at every visit (a sixth to a thirty-sixth of a score tile)
                q = stacked_ref[...] if heads == 1 else stacked_ref[of(h)]
                k = _turned_head(lanes(k_ref, h, d), cos_k[...], sin_k[...], width,
                                 turned_by).astype(stacked_ref.dtype)
            return q, k, v

        def score(h):  # head h's value tile, and its score tile with the closed pairs at NEG_INF
            q, k, v = part(h) if cut > 1 else head(h)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            if shared:
                if turn is not None:
                    qs = turned_ref[...] if heads == 1 else turned_ref[of(h)]
                else:
                    qs = (qs_ref[...] if heads == 1 else qs_ref[h]).reshape(rows, qs_ref.shape[-1])
                s = s + jax.lax.dot_general(qs, ks_ref[...], (((1,), (1,)), ((), ())),
                                            preferred_element_type=jnp.float32)
            if masked and blocks is not None and not open_:
                # a selection of BLOCKS (`select_blocks`' flags, the key head's own): the tile's
                # flags are an aligned run of ONE lane tile, spread over the tile's keys by a
                # product against the blocks' membership, and the diagonal block is cut at the
                # query. Block 0 is forced: every row has an open key from its first tile on
                flags = mask_ref[...].astype(jnp.float32).astype(jnp.bfloat16)  # [bq, 128]
                first_flag = (kb % (128 // blocks[1])) * blocks[1]  # (blocks: keys a block, blocks a tile)
                spread = (jax.lax.broadcasted_iota(jnp.int32, (128, block_k), 0) - first_flag
                          == jax.lax.broadcasted_iota(jnp.int32, (128, block_k), 1) // blocks[0])
                sel = jnp.dot(flags, spread.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
                row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                col = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                open_.append(((sel > 0.0) & (col <= row))[None])
            elif masked and not open_:
                # a row with nothing selected yet has m_new == NEG_INF and p == 1 on its masked
                # entries: the first selected key's alpha == 0 wipes that, and every row selects a
                # key at or before its diagonal tile
                sel = mask_ref[...].astype(jnp.float32).reshape(block_q, block_k)
                open_.append((sel > 0.0)[None])
            elif (with_diagonal or with_lower_edge) and not open_:
                # key 0 of the sequence is open to every row: m is finite from tile 0 (under a
                # window `kb` counts the key window's first row in query tiles, and every row
                # meets its own key in the step)
                row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                col = kb * (block_k if window is None else block_q) + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                both = ([col <= row] if with_diagonal else []) + (
                    [col > row - window] if with_lower_edge else [])
                open_.append(functools.reduce(jnp.logical_and, both)[None])
            if open_:
                s = jnp.where(open_[0], s.reshape(rep // cut, block_q, block_k),
                              NEG_INF).reshape(rows // cut, block_k)
            return s, v

        def fold(h, s, v):  # head h's running softmax takes the tile in
            m = m_ref[of(h)]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_ref[of(h)] = l_ref[of(h)] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[of(h)] = acc_ref[of(h)] * alpha + jnp.dot(p.astype(v.dtype), v,
                                                              preferred_element_type=jnp.float32)
            m_ref[of(h)] = m_new

        def once(h, s, v):  # under a window every key of head h's rows is in the step: ONE pass
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            l_ref[of(h)] = jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[of(h)] = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)

        # head h + 1's score product stands BEFORE head h's softmax, and head h's `p . v` after it:
        # Mosaic keeps unrolled heads in the order they are written, so one head's products run
        # under another's exponentials (two score tiles live; alone in its step a head is the
        # chain it was)
        ahead = score(0)
        for h in range(heads * cut):
            tile, ahead = ahead, score(h + 1) if h + 1 < heads * cut else None
            (fold if window is None else once)(h, *tile)

    def _finalize():
        out = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)
        if not gated and heads == 1 and o_ref.shape[0] == rep:
            o_ref[...] = out.reshape(o_ref.shape)
            return
        if gated:  # head h's scalars are lane h of the tile: picked by a compare and a lane sum
            gate = gate_ref[0]
            lane = jax.lax.broadcasted_iota(jnp.int32, gate.shape, 1)
        for r in range(heads * rep):  # head by head: whole lane blocks, and no product
            head = out[r * block_q:(r + 1) * block_q]
            if gated:
                of_head = jnp.sum(jnp.where(lane == gi * heads * rep + r, gate, 0.0),
                                  axis=-1, keepdims=True)
                head = (head.astype(jnp.float32) * of_head).astype(o_ref.dtype)
            if o_ref.shape[0] == rep and heads == 1:
                o_ref[r] = head
            else:  # the group's heads are adjacent lane blocks of ONE token-major tile
                o_ref[0, :, r * dv:(r + 1) * dv] = head

    if window is not None:  # one grid step a query tile, its first and its last: the step's keys are
        # ONE window of `block_k` rows from row kb * block_q on, and both edges of the band are compared
        # over the whole of it, in ONE branch (the compares on the column runs the edges cross alone, the
        # runs between left open and the tile put together again, read SLOWER: `_causal_attention`)
        _reset()
        update(True, True)
        _finalize()
        return
    pl.when(kb == 0)(_reset)
    if masked:
        update(False)
    else:
        below = (kb + 1) * block_k - 1 <= qi * block_q  # every pair of the tile is causal
        pl.when(below)(lambda: update(False))
        pl.when(jnp.logical_not(below))(lambda: update(True))
    pl.when(kb == ((qi + 1) * block_q - 1) // block_k)(_finalize)  # the row's last tile


def band_keys(s: int, bq: int, window: int) -> int:
    """The rows of the ONE key window a query tile of ``bq`` rows is run against
    under a ``window``: whole query tiles, the diagonal's and the ``ceil((window
    - 1) / bq)`` before it that hold the key ``window - 1`` before the tile's
    first row (``window + bq`` where ``bq`` divides the window: 768 at 256 x 512),
    at most the sequence."""
    return min((-(-(window - 1) // bq) + 1) * bq, s)


def _band_tiles(s: int, bq: int, bk: int, window: Optional[int] = None) -> list:
    """The ``(query tile, key tile)`` pairs a sequence of ``s`` is run in,
    a query tile's key tiles in order: those that hold a pair at or below
    the diagonal. Under a ``window`` of those the ones that MEET the band
    ``t - window < j <= t`` of some row ``t`` of the query tile (at 8,704
    tokens in 512 x 512 tiles 153, and 33 under a window of 512): the
    GEOMETRY the statistics count in (:func:`band_tile_count`), and until
    PR 77 the grid of a windowed call, which since takes one step a query
    tile against one key window (:func:`band_keys`)."""
    def first(i):  # the tile of the key `window - 1` before the query tile's first row
        return 0 if window is None else max(i * bq - (window - 1), 0) // bk

    return [(i, j) for i in range(s // bq) for j in range(first(i), ((i + 1) * bq - 1) // bk + 1)]


def _causal_attention(q, k, v, g: int, block_q: int, block_k: int, interpret: bool,
                      q_shared=None, k_shared=None, mask=None, window: Optional[int] = None,
                      out_gate=None, shared_turn=None, shared_scale: float = 1.0, turn=None,
                      turn_width: int = 0, turn_scale: float = 1.0, q_scale: float = 1.0,
                      heads: Optional[int] = None, mask_blocks: Optional[BlockSelection] = None,
                      cut: Optional[int] = None):
    """What :func:`masked_gqa_attention` runs. The grid's last
    axis runs over the ``(query tile, key tile)`` pairs at or below the
    diagonal, a row's key tiles in order, so a tile above it costs not
    even a grid step; under a ``window`` over the query tiles ALONE, each
    against one key window (below). ONE kernel body, several ways of addressing its
    blocks, chosen by the widths, each operand by its own. Where a head
    is whole lane blocks (``width % 128 == 0``), at ANY number of query
    heads a group: a key or value head's tile is column block ``g`` of
    the TOKEN-MAJOR array ``[B, S, G*width]``, read where the projection's
    product wrote it, and a group's ``H/G`` output heads are ONE block
    ``[bq, H/G * dv]`` of ``[B, S, H*dv]``, written head by head to its
    lane blocks where ``W_o`` contracts it: in the tiled HBM layout runs
    of whole 4 KB tiles at a fixed stride. ``v`` may then be ``None``: ``k
    [B, S, G*2d]`` is ONE array with head ``h``'s keys at column block
    ``2h`` and its values at ``2h + 1`` (the latent's one decompression),
    two blocks of one operand. The QUERY of such heads is a column block
    too where a head is alone in its group (latent attention's, straight
    from its product), and at ANY number a group where the kernel turns
    it (below); else, ``H/G > 1`` heads a group that come TURNED go as
    ``[G, H/G, B*S, d]``, the batch in the rows (a layout of the rotary's
    own fusion wherever XLA turns: no op). With ``turn`` (``[cos | cos |
    1]`` and the signed sine ``[-sin | sin | 0]``, each ``[B*S, d]``
    float32: ``decoder.turn_tables(angles, d)``) the rotary is the
    KERNEL's (PR 63; maskless or windowed, no shared part): ``q [B, S,
    H*d]`` and ``k [B, S, G*d]`` come FLOAT32, unturned and unscaled,
    exactly what ``W_q``'s and ``W_k``'s products wrote. At a query
    tile's first key step the kernel reads the group's heads as ONE
    token-major block ``[bq, H/G * d]`` and that tile's rows of the two
    tables, turns head by head (:func:`_turned_head`: ``x * cos + rolled *
    sin`` in whole vregs, the leading ``turn_width`` lanes of a head, times
    ``turn_scale``), multiplies by ``q_scale`` (the softmax scale),
    rounds ONCE to ``v``'s type and writes each head's rows into the
    stacked ``[H/G * bq, d]`` VMEM scratch that every key step reads: the
    turn and the stacking are one pass. A key tile is turned by ITS rows
    of the tables at every visit (``bk * d`` elements beside a score tile
    of ``H/G * bq * bk``), rounded once. Equal to the bit to
    ``decoder._turn_leading`` before the call. What went: the two 64-lane
    halves of every head that XLA sliced out of the float32 product into
    copies of their own (fifteen ``f32[17408, 72|48, 64]`` a step in
    Laguna's, 768 ``f32[4608, 16, 64]`` in the looped reader's), the
    lane-padded passes that turned them and the bf16 head-major copy. One
    layer alone on the v5e with its projections and ``W_o``, ms, before ->
    with the keys turned once into a per-sequence VMEM scratch ``[S, d]``
    / at every visit (my chip runs, PR 63): 72 heads under a window of 512
    33.70 -> 21.13 / 20.33; 48 heads 38.99 -> 27.98 / 28.05; the looped
    reader's 16 on 16 key heads at 2 x 2,304 2.599 -> 1.678 / 1.677; the
    kernel's own call 11.08 -> 8.91 / 8.13, 20.10 -> 19.26 / 19.32, 0.626
    -> 0.724 / 0.727: a kept key saves nothing that shows and its guarded
    store costs the windowed layers 0.8 ms each, so every visit turns.
    (Tried in PR 58: a group's TURNED heads stacked from one token-major
    block, the gate still XLA's: Laguna's step 523.6 ms against 514.1.) Else
    (heads of 64: LFM2's, granite's) operands go HEAD-MAJOR (``[B, G, H/G,
    S, d]`` and ``[B, G, S, d]``: a block's last dimension is then the
    whole head width, which Mosaic takes at 64 where a 64-lane block of
    ``[S, G*64]`` it does not) by a transpose each way. One layer's kernel
    alone on the v5e, head-major and then in place, ms (my chip runs, PR
    48): 64 heads alone in their groups, B 2, maskless 32.79 and 32.82
    (32.86 with k and v of one array); 128 heads, masked 34.83 and 34.86
    (34.89), the call with its transposes 41.04 -> 34.43 and 43.07 ->
    35.98; (PR 58, B 2 x 8,704, 8 key heads) 48 heads, six a group 18.25
    and 18.28 (18.43 with the gate), the call 21.08 -> 19.90, the layer
    with its projections 45.44 -> 38.94; 72 heads, nine a group, under a
    window of 512 8.00 and 8.02 (8.26), 12.12 -> 10.78, 43.06 -> 33.65:
    the strided tiles cost nothing that shows, and what stood around the
    kernel is gone (at nine a group q's copy 0.96 ms, and THREE float32
    passes over o and a materialised broadcast of its gate 6.5: XLA had
    ``gated`` run with the tokens in the lanes).
    The values' width is ``v``'s own (``v [B, S, G*dv]``
    -> ``o [B, S, H*dv]``). With ``q_shared [B, S, H*ds]`` and ``k_shared
    [B, S, ds]`` a score is ``q . k + q_shared . k_shared``: the shared
    key's tile is read from its one array, once a grid step, for every
    head (latent attention's one rotary key); the shared QUERY part is
    narrow (64) and always head-major with the batch in the rows, ``[G,
    H/G, B*S, ds]`` (PR 48: where ``[B, G, ..]`` cost a reshape and a
    copy). With ``shared_turn`` (``[cos | cos]`` and ``[sin | sin]``, each
    ``[B*S, ds]`` float32: ``decoder.turn_tables``) ``q_shared`` is the
    rotary part as its product wrote it, FLOAT32 and UNTURNED, and the
    kernel turns it: at a query tile's first key step it reads the ``[H/G,
    bq, ds]`` float32 tile and the tile's rows of the two tables, turns
    (:func:`_turned_tile`), multiplies by ``shared_scale``, rounds ONCE to
    the activations' type into a VMEM scratch ``[H/G * bq, ds]``, and
    every key step of the tile reads the scratch where it read the operand
    (PR 61: the transpose above is then the product's own layout, and the
    two lane-padded float32 passes and the bf16 head-major copy XLA made
    of the turn are gone). A branch taken in Python by the operands given:
    a call without the tables traces the body it traced. With ``out_gate
    [B, S, H]`` (float32: a scalar a token and head, Laguna's sigmoid
    gate) the output leaves as ``gated`` made it
    — the output rounded to its type, times the scalar in float32,
    rounded: equal to the bit — from the tile's last grid step: the
    query tile's scalars ride in as ONE block ``[bq, H]`` of the array as
    it is, and head ``h``'s are lane ``h`` of it, picked by a compare and
    a lane sum. ``W_o`` then contracts what the kernel wrote; done by XLA
    on a token-major output the same multiply cost a float32 copy of o
    and a materialised ``[T, H, dv]`` broadcast of the gate, and the step
    514.1 ms where it is 455.2 (my chip runs, PR 58). With ``mask`` (the selection
    of ONE sequence, from :func:`select_keys`) every tile at or below the
    diagonal is still visited, and a pair counts where the mask says so: the
    key tile is the mask's own (:func:`mask_tile` chose it for THIS
    kernel), the query tile the largest multiple of the mask's that divides
    ``S`` and is at most ``block_q`` and whose stacked score tile fits
    (:func:`_masked_query_tile`): 512 x 2,176 under masks of 128 x
    2,176 at 8,704 tokens, 44 grid steps a head. Where the mask's tile does
    not divide ``S`` (its last tile ends in zeros: :func:`mask_tile`) k and
    v are padded with zero rows to the whole tiles — keys no query
    selected — and the table of pairs is the real query tiles' over them.
    Under a mask ``H/G > 1`` heads of whole lane blocks (Keye's eight a
    group, 256 x 2,176 over 34,816 keys: 4,512 grid steps a layer) are read
    as ONE token-major block ``[bq, H/G * d]`` and stacked head by head
    into the ``[H/G * bq, d]`` VMEM scratch at a query tile's first key
    step, the rotary path's stacking without its turn: 1.6 ms a layer
    faster than XLA's head-major copy at every tile, which it spares
    (:func:`mask_tile` has the readings). The mask's tile is read
    once a grid step, so once a HEAD or group. One layer's kernel at 128 heads of
    128 + 64 on the v5e (my chip runs, PR 47), ms: 512 x 2,176 34.8, 256 x
    2,176 36.2, 256 x 4,352 39.4, 512 x 512 (the mask written in the
    selection's 512-wide pieces, as it was until PR 47) 43.4, 2,176 x 512
    54.9; WITHOUT a mask 512 x 512 43.1 and 1,088 x 1,088 32.7: the tile
    is what a mask costs, its convert, compare and select 0.35. Where a
    head is ALONE in its group (latent attention's) a
    grid step takes a BLOCK of ``hb`` heads (:func:`heads_a_step`, the rule;
    ``heads`` asks for a number of its own): the grid is ``(B, G / hb,
    pairs)`` and a block is a WIDER block of the same arrays — q and o
    ``[bq, hb * d]`` at column block ``gi``, k and v of one array ONE block
    ``[bk, hb * 2d]`` holding ``[k_h | v_h]`` of the block's heads, the shared
    query ``hb`` leading heads, ``m``, ``l``, ``acc`` and the turned scratch
    ``hb`` times as tall; the shared key's tile, the tables' and the mask's
    (its convert and compare too) are read once for ``hb`` heads. The body's
    heads are unrolled and WRITTEN so that head ``h + 1``'s score product
    stands before head ``h``'s softmax and head ``h``'s ``p . v`` after it:
    alone in its step a head is a chain — product, then max, ``exp``, sum
    and rescale over a 4.5-4.7 MB score tile, then product — and the MXU
    idles under the vector work; Mosaic keeps the written order, so one
    head's products run under another's exponentials. Every head's own
    arithmetic is what it was: the outputs are equal to the bit. One
    layer's kernel alone on the v5e, ms, 1 head a step -> ``hb`` heads
    written head after head / skewed by one (built) / part by part through
    all heads (my chip runs, PR 66; twenty calls a reading; the parents'
    own readings of the single-head form: 34.83 PR 47, 32.87-33.45 PR 61):
    128 heads under a mask, 512 x 2,176: 37.82 -> at 2: 36.60 / 36.13 /
    36.13; at 4: 34.77 / 33.36 / 34.54; at 8: 33.73 / **32.00** / 33.86
    (14.5 s to compile where one head takes 1.1); 64 heads at B 2, maskless
    1,088 x 1,088: 36.59 -> at 2: 34.01 / **31.54** / 31.54; at 4: 49.71 /
    47.76 / 48.26; at 8: 44.74 / 42.44 / 42.56 — SLOWER than no block; 32
    heads at B 4 the same to 0.1; the looped reader's 16 heads at 2 x 2,304,
    q and k turned by the kernel, 768 x 768: 0.727 -> at 2: 0.588 / 0.578 /
    0.581; at 4: 0.566 / 0.541 / 0.520; at 8: 0.580 / 0.486-0.501 /
    0.549 — measured, and NOT taken by the rule (:func:`heads_a_step` says
    why: the step's 48 call sites pay for the longer body at every start).
    Where heads SHARE their keys (``H/G > 1``) a grid step holds the group's
    heads stacked and, since PR 75, cuts the stacked rows into ``parts`` runs
    of WHOLE heads (:func:`parts_a_step`, the rule; ``cut`` asks for a number
    of its own) written in the same order: part ``p + 1``'s score product
    before part ``p``'s softmax. Every part reads the SAME key and value
    tile, and the key tile's turn, the mask's convert and compare, the
    flags' spread and the band's compares are computed once a visit; a
    row's arithmetic is what it was, and on the chip every reading below
    was EQUAL TO THE BIT to its parent's output. One layer's kernel alone on
    the v5e, ms, the parent's one stacked product -> ``parts`` written part
    after part / skewed by one (my chip runs, PR 75, seed 7500000001,
    twenty calls a reading, the parent's read again last to 0.01; **bold**
    the rule's): laguna full, 2 x 8,704, 8 groups of 6 turned by the
    kernel, gated, 512 x 1,088: 19.33 -> at 2: 18.28 / 16.60; at 3: 17.80 /
    **15.54**; at 6: 18.83 / 15.86; laguna windowed, 9 a group, 256 x 512
    under 512: **8.13** -> at 3: 8.23 / 7.51 (twelve part bodies over its
    four branches: past what a start pays for, :func:`parts_a_step`); at 9:
    9.43 / 8.53 (parts of 0.52 MB: slower than none); keye, 34,304 under the mask, 4 groups of 8
    stacked in the kernel, 256 x 2,176: 84.93 -> at 2: 77.24 / 75.38; at 4:
    73.99 / 68.32; at 8: 73.57 / **65.61** (in 128 x 2,176 tiles 85.65 ->
    78.56 / 75.70, 75.85 / 68.09, 82.20 / 64.61: the query tile stays);
    lfm2, 4 x 8,704, 8 groups of 4 heads of 64 head-major, 1,088 x 1,088:
    25.35 -> at 2: 24.63 / 21.19; at 4: 24.19 / **20.30** (in 544 x 1,088
    tiles 25.80 -> 25.29 / 20.91, 25.29 / 20.49: the tile stays; granite's
    one sequence 6.10 -> 5.93 / 5.06, 5.81 / **4.84**); nemotron3, 4 x
    8,704, 2 groups of 16, 256 x 1,088: 25.25 -> at 2: 24.15 / 20.69; at 4:
    23.89 / **20.32**; at 8: 24.98 / 20.68; at 16: 26.39 / 20.69;
    minicpm_sala, 34,304 under a block selection's flags, 2 groups of 16,
    128 x 2,048: 79.09 -> at 2: 73.67 / 77.13; at 4: 72.03 / 69.40; at 8:
    72.65 / **66.51**; at 16: 80.18 / 62.91; phi4flash's differential
    calls, 2 x 8,704, 10 groups of 2 half-heads of 64 over values of 128:
    1,088 x 1,088 7.61 -> 7.50 / **6.12**; 256 x 512 under the window 3.02
    -> 3.11 / 2.86 (**1 part**: 0.52 MB a part, under the floor the nine
    set). Part after part gains a third of what the skew gains or nothing:
    the gain is the ORDER, one part's exponentials under the next part's
    product, not the smaller tile.
    Under a ``window`` (PR 77) the keys follow the band DIAGONALLY: the
    grid's last axis is the query tiles, and a step's key and value blocks
    (where the kernel turns, the key rows of the two tables; a shared key)
    are ONE window of :func:`band_keys` rows — whole query tiles: the
    diagonal's and the ``ceil((window - 1) / bq)`` before it, 768 rows at
    256 x 512 where two key tiles of 512 met 1,024 — from row ``max(qi + 1
    - tiles, 0) * bq`` on, which the scalar-prefetched table holds in query
    tiles (a sequence's first tiles start at key 0). The rows of such a
    window are no whole blocks of its own size, so these blocks are
    ELEMENT-addressed (``pl.Element`` on every dimension that is not
    squeezed, the offsets proven multiples of the query tile by
    ``pl.multiple_of``): Mosaic takes that at token-major column blocks of
    128 lanes and at head-major heads of 64. ``block_k`` is not a windowed
    call's to take. With every key of a row in the step there is NO running
    state: the maximum, the exponentials, their sum and ``p . v`` are taken
    ONCE (``once``: no ``m``, no ``alpha``, no rescale of the accumulator,
    no first and last step to tell apart), scores float32 and ``p`` rounded
    to ``v``'s type as ever — the same mathematics, one rounding chain
    shorter, so outputs are not the parent's to the bit (13% of laguna's
    bf16 outputs differ, by an ulp; against the plain float32 band max
    0.0190 for the parent's 0.0190, mean 9.52e-5 for 9.49e-5; phi4flash's
    max 0.0146 for 0.0146, mean 3.93e-4 for 3.90e-4). Both edges of the
    band are compared over the WHOLE window in ONE branch; the stacked
    rows go in parts as above (laguna's nine heads in three of ``[768,
    768]``). The kernel ALONE on the v5e, 2 x 8,704 tokens under a window
    of 512, ms, twenty calls a reading (my chip runs, PR 77; the table of
    tiles and parts is beside :data:`BAND_QUERY_TILE`, seed 7700000002):
    laguna's call 8.12 -> **3.71**, phi4flash's 3.01 -> **1.67**. Read
    first (seed 7700000001) with the compares on the column runs the edges
    cross ALONE — a window that starts ``window`` keys before the tile's
    first row meets the lower edge in its first ``bq`` columns and the
    diagonal in its last, constants of the shapes; the open run between
    left as it is and the three put together again, a sequence's first
    tiles in a branch of their own: laguna's 4.29 at one part and 4.13 at
    three where the whole window's compare read 3.92 and 3.70, phi4flash's
    1.60 where it read 1.66. The select is one of a score element's six
    or seven vector passes and was spared on a third of them; the put-
    together cost more than that, and a second branch doubles the part
    bodies a start pays for: ONE branch. With the GROUPS inside a query
    tile's grid steps (the tables' rows and the gate's block then do not
    move between eight steps and are not fetched again: 1.0 of the 3.5 MB
    a 6.8 us step moves) laguna's call read 3.704 -> 3.706 and phi4flash's
    1.665 -> 1.662 (seed 7700000003): the vector work binds, not the bytes,
    and the grid keeps the other forms' order. First calls (trace, lowering and
    Mosaic's compile, on the chip): laguna's 5.8-6.3 s -> 2.4-2.7,
    phi4flash's 0.9-1.0 -> 0.36."""
    from jax.experimental.pallas import tpu as pltpu

    b, s, hd = q.shape
    if v is None:  # ONE array: head h's keys at column block 2h, its values at 2h + 1
        d = dv = k.shape[2] // (2 * g)
    else:
        d, dv = k.shape[2] // g, v.shape[2] // g
    rep = hd // (g * d)
    shared, masked = q_shared is not None, mask is not None
    bq, bk = pick_tile(s, block_q), pick_tile(s, block_k)
    blocks = None
    if masked and mask_blocks is not None:  # a selection of BLOCKS, one a key head: flags [G, S, W]
        bk, a_tile, lanes = mask_blocks.tiles(s)
        blocks, n_kb, mq = (mask_blocks.block_size, a_tile), -(-s // bk), pick_tile(s, 128)
        if b != 1 or mask.shape != (g, s, lanes):
            raise ValueError(f"flags {mask.shape} select blocks a key head of one sequence: not "
                             f"[{g}, {s}, {lanes}] of {b} of {s}")
        bq = _masked_query_tile(s, block_q, mq, rep * bk)
    elif masked:
        n_qb, n_kb, mq, bk = mask.shape
        if b != 1 or n_qb * mq != s or not 0 <= n_kb * bk - s < bk:
            raise ValueError(f"a mask {mask.shape} selects the keys of one sequence of "
                             f"{n_qb * mq}: not of {b} of {s}")
        bq = _masked_query_tile(s, block_q, mq, rep * bk)
    if masked:
        if n_kb * bk > s:  # keys laid after the sequence, closed by the mask: whole key tiles
            rows = ((0, 0), (0, n_kb * bk - s), (0, 0))
            k, v, k_shared = (u if u is None else jnp.pad(u, rows) for u in (k, v, k_shared))
    sk = k.shape[1]  # the keys' rows: the sequence's, or under a mask whole tiles of it
    if window is not None and (masked or window < 1):
        raise ValueError("a window is a band of at least the query's own key, and the maskless form's")
    if shared_turn is not None and not shared:
        raise ValueError("the tables turn the shared query part: there is none")
    if turn is not None and (masked or shared or d % 128 or q.dtype != jnp.float32
                             or k.dtype != jnp.float32 or v is None):
        raise ValueError("the kernel turns float32 heads of whole lane blocks, maskless and with "
                         "no shared part")
    if window is None:
        pairs = _band_tiles(s, bq, bk)
    else:  # ONE key window a query tile, whole query tiles of rows: `kb` counts its first in those
        bk = band_keys(s, bq, window)
        pairs = [(i, max(i + 1 - bk // bq, 0)) for i in range(s // bq)]
    qi, kb = (jnp.asarray(col, jnp.int32) for col in zip(*pairs))
    ku = bk if window is None else bq  # the rows `kb` counts in
    ds = k_shared.shape[2] if shared else 0
    hb = heads_a_step(g, rep, bq, bk, d, dv, ds, masked=masked, window=window,
                      turned=turn is not None, want=heads)
    joint = hb > 1 and v is None  # ONE block [k_h | v_h] of the block's heads
    cut = parts_a_step(rep, bq, bk, ds, masked=masked, window=window, want=cut)

    def in_place(width):  # a head of whole lane blocks
        return width % 128 == 0

    # a group's heads of whole lane blocks under a mask: read as ONE token-major block, stacked
    # into the scratch at a query tile's first key step (the rotary's stacking without its turn)
    stack = masked and rep > 1 and in_place(d) and not shared

    def rows_spec(width):  # of [G, H/G, B*S, width]: the batch in the rows; a block's hb heads lead
        return pl.BlockSpec((None if hb == 1 else hb, rep, bq, width),
                            lambda bi, gi, t, qi, kb: (gi, 0, bi * (s // bq) + qi[t], 0))

    def major_spec(width):  # of [B, G, H/G, S, width]
        return pl.BlockSpec((None, None, rep, bq, width),
                            lambda bi, gi, t, qi, kb: (bi, gi, 0, qi[t], 0))

    def place_spec(width):  # of [B, 1, S, H*width]: the step's heads, column block gi
        return pl.BlockSpec((None, 1, bq, hb * rep * width),
                            lambda bi, gi, t, qi, kb: (bi, 0, qi[t], gi))

    def keys_spec(shape, index, row):  # a key-side block, `bk` rows on axis `row` from row kb * ku
        if window is None:
            return pl.BlockSpec(shape, index)

        def first(*at):  # under a window ELEMENT by element: the rows are no whole blocks of `bk`
            units = [ku if axis == row else n for axis, n in enumerate(shape)]  # what an index counts
            return tuple(i if n is None else i * n if isinstance(i, int) else pl.multiple_of(i * n, n)
                         for i, n in zip(index(*at), units))

        return pl.BlockSpec(tuple(n if n is None else pl.Element(n) for n in shape), first)

    def q_tiles(x, width):  # -> the kernel's [rep, bq, width] tile
        if in_place(width) and (rep == 1 or turn is not None or stack):  # (stacked in the kernel)
            return x.reshape(b, 1, s, g * rep * width), place_spec(width)
        if in_place(width):
            return jnp.transpose(x.reshape(b * s, g, rep, width), (1, 2, 0, 3)), rows_spec(width)
        return jnp.transpose(x.reshape(b, s, g, rep, width), (0, 2, 3, 1, 4)), major_spec(width)

    def kv_tiles(x, width, part=0, parts=1):  # head gi's part is column block gi*parts + part
        if in_place(width):  # (a block of hb heads: every part of theirs, column block gi)
            return x, keys_spec((None, bk, hb * width * (parts if joint else 1)),
                                lambda bi, gi, t, qi, kb: (
                                    bi, kb[t], gi if joint else gi * parts + part), 1)
        return (jnp.transpose(x.reshape(b, sk, g, width), (0, 2, 1, 3)),
                keys_spec((None, None, bk, width), lambda bi, gi, t, qi, kb: (bi, gi, kb[t], 0), 2))

    parts = 1  # (of the ONE operand that holds keys and values; a stacked group's are `cut`)
    if v is None and in_place(d):
        v, parts = k, 2  # two blocks of the one operand
    elif v is None:
        k, v = (k.reshape(b, sk, g, 2, d)[:, :, :, i].reshape(b, sk, g * d) for i in (0, 1))
    operands, in_specs = (list(u) for u in zip(
        q_tiles(q, d), kv_tiles(k, d, 0, parts), *([] if joint else [kv_tiles(v, dv, parts - 1, parts)])))
    scratch = [pltpu.VMEM((hb * rep * bq, 1), jnp.float32), pltpu.VMEM((hb * rep * bq, 1), jnp.float32),
               pltpu.VMEM((hb * rep * bq, dv), jnp.float32)]
    rotary = None
    if turn is not None:  # the query tile's and the key tile's rows of the two tables [B*S, d], and
        # last of the scratch the turned query tile, stacked, as the key steps read it
        operands += [table.reshape(b * s, d) for table in turn] * 2
        in_specs += [pl.BlockSpec((bq, d), lambda bi, gi, t, qi, kb: (bi * (s // bq) + qi[t], 0))] * 2
        in_specs += [keys_spec((bk, d), lambda bi, gi, t, qi, kb: (bi * (s // ku) + kb[t], 0), 0)] * 2
        scratch.append(pltpu.VMEM((hb * rep * bq, d), v.dtype))
        rotary = (int(turn_width) or d, float(turn_scale), float(q_scale))
    elif stack:
        scratch.append(pltpu.VMEM((rep * bq, d), q.dtype))
    if shared:
        operands += [jnp.transpose(q_shared.reshape(b * s, g, rep, ds), (1, 2, 0, 3)), k_shared]
        in_specs += [rows_spec(ds),
                     keys_spec((None, bk, ds), lambda bi, gi, t, qi, kb: (bi, kb[t], 0), 1)]
    if shared_turn is not None:  # the query tile's rows of [cos | cos] and [sin | sin] [B*S, ds]
        operands += [table.reshape(b * s, ds) for table in shared_turn]
        in_specs += [pl.BlockSpec((bq, ds),
                                  lambda bi, gi, t, qi, kb: (bi * (s // bq) + qi[t], 0))] * 2
        scratch.append(pltpu.VMEM((hb * rep * bq, ds), q.dtype))  # the tile as the key steps read it
    if blocks:  # the key head's flags of the query tile: the lane tile that holds the key tile's
        if hb != 1:
            raise ValueError("a selection of blocks is a key head's: one group a grid step")
        operands.append(mask)
        in_specs.append(pl.BlockSpec((None, bq, 128), lambda bi, gi, t, qi, kb: (
            gi, qi[t], kb[t] // (128 // blocks[1]))))
    elif masked:
        operands.append(mask)
        in_specs.append(pl.BlockSpec((bq // mq, None, mq, bk),
                                     lambda bi, gi, t, qi, kb: (qi[t], kb[t], 0, 0)))
    if out_gate is not None:  # [B, S, H] as it is: a query tile's scalars, every head's
        operands.append(out_gate.astype(jnp.float32).reshape(b, 1, s, g * rep))
        in_specs.append(pl.BlockSpec((None, 1, bq, g * rep), lambda bi, gi, t, qi, kb: (bi, 0, qi[t], 0)))
    o5 = pl.pallas_call(
        functools.partial(_causal_kernel, block_q=bq, block_k=bk, shared=shared, masked=masked,
                          gated=out_gate is not None, window=window,
                          turn=None if shared_turn is None else float(shared_scale),
                          rotary=rotary, heads=hb, joint=joint, stack=stack,
                          **({"blocks": blocks} if blocks else {}), **({"cut": cut} if cut > 1 else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, g // hb, len(pairs)),
            in_specs=in_specs, out_specs=place_spec(dv) if in_place(dv) else major_spec(dv),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(
            (b, 1, s, g * rep * dv) if in_place(dv) else (b, g, rep, s, dv),
            q.dtype if turn is None else v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # the same body under a name of its own: a trace tells the windowed layers' calls apart
        name="masked_gqa_attention" if window is None else "windowed_gqa_attention",
    )(qi, kb, *operands)
    if in_place(dv):
        return o5.reshape(b, s, g * rep * dv)
    return jnp.transpose(o5, (0, 3, 1, 2, 4)).reshape(b, s, g * rep * dv)


def block_vmem_bytes(hb: int, bq: int, bk: int, d: int, dv: int, ds: int = 0, *,
                     masked: bool = False) -> int:
    """What a grid step of ``hb`` heads keeps in VMEM, counted as
    :func:`causal_tiles` counts a score tile: the TWO live score tiles of
    the written order, one's exponentials and their bf16 copy; the ``hb``
    accumulators with their maxima and sums (a lane block each); the
    pipeline's two buffers of every operand block (q, the heads' keys and
    values, the output; the shared query part float32 in lanes half full,
    its key, its two tables and the turned scratch; a mask's tile). Under a
    window ``bk`` is the ONE key window's rows (:func:`band_keys`): the step's
    whole row of scores and every key-side block at that height."""
    count = 14 * bq * bk + hb * bq * (4 * dv + 2 * 4 * 128)
    count += 2 * hb * 2 * (bq * d + bk * (d + dv) + bq * dv)
    if ds:
        count += 2 * (hb * bq * 128 * 4 + bk * 128 * 2 + 2 * bq * 128 * 4) + hb * bq * 128 * 2
    return count + (2 * bq * bk if masked else 0)


def _branches(masked: bool, window: Optional[int]) -> int:
    """The branches :func:`_causal_kernel`'s body holds, each with the unrolled
    heads or parts of its own: one under a mask, two without (below the
    diagonal and on it), ONE under a window (the whole key window's compare;
    four, one an edge of the band and tile, until PR 77)."""
    return 2 if window is None and not masked else 1


def heads_a_step(g: int, rep: int, bq: int, bk: int, d: int, dv: int, ds: int = 0, *,
                 masked: bool = False, window: Optional[int] = None, turned: bool = False,
                 want: Optional[int] = None) -> int:
    """How many heads ONE grid step of the batched kernel takes: the rule,
    taken in Python from the shapes. 1 wherever heads share their keys
    (``rep > 1``: a group's stacked heads already share a grid step, and
    :func:`parts_a_step` cuts THEIR rows into parts written in this order), a
    head is no whole lane blocks, or the kernel turns q and k itself
    (``turned``: below); else the LARGEST of 8 / 4 / 2 (never 16: slower,
    and 32 s to compile, PR 47) that divides ``g``, whose count
    (:func:`block_vmem_bytes`) fits :data:`_VMEM_LIMIT`, and under which the
    score tiles of the body's unrolled heads, over every branch the body
    holds (one under a mask or a window, two without: below the diagonal
    and on it), stay within :data:`UNROLLED_SCORE_BYTES` — past
    that a block reads SLOWER than no block (the readings:
    :func:`_causal_attention`; 128 heads under a mask in 512 x 2,176 tiles
    are 35.7 MB at 8 heads, the best of its row; 64 maskless in 1,088 x
    1,088 18.9 MB at 2, the best, and 37.9 at 4, a third slower than one
    head a step). The looped reader's call (16 heads whose q and k the
    kernel turns, 768 x 768) read faster ALONE at every block (0.727 ->
    0.541 ms at 4) and its step 650.6 -> 624.7 ms, and stays at one head a
    step all the same: its 48 call sites pay a block's longer body 48 times
    while the step is traced, ``startup_trace_s`` 0.68 -> 1.56 and warm
    ``setup_s`` 13.2-13.6 -> 14.3-15.7 of a bound of 1.34 s (my chip runs,
    PR 66: a start is what its users pay at every restart). ``want`` (a
    test's or a timing run's own) takes the place of the rule's choice
    wherever heads are alone in their groups."""
    if rep > 1 or d % 128 or dv % 128:
        return 1
    if want is not None:
        return next(hb for hb in range(int(want), 0, -1) if g % hb == 0)
    if turned:
        return 1
    bodies = _branches(masked, window)
    for hb in BLOCK_HEADS:
        if (g % hb == 0 and hb * bodies * 4 * bq * bk <= UNROLLED_SCORE_BYTES
                and block_vmem_bytes(hb, bq, bk, d, dv, ds, masked=masked) <= _VMEM_LIMIT):
            return hb
    return 1


def parts_a_step(rep: int, bq: int, bk: int, ds: int = 0, *, masked: bool = False,
                 window: Optional[int] = None, want: Optional[int] = None) -> int:
    """How many PARTS of whole heads a grid step cuts a STACKED group's rows
    into (``rep > 1`` query heads that share their keys: ``rep * bq`` rows,
    ONE key and value tile): the rule, taken in Python from the shapes. Part
    ``p + 1``'s score product is written before part ``p``'s softmax
    (:func:`_causal_kernel`), so one part's exponentials run under another's
    products; what the parts share — the key tile's turn, a mask's convert
    and compare, the flags' spread, the band's compares — is computed once a
    visit. 1 where a head is alone in its group (:func:`heads_a_step`'s
    blocks are that case's) or the score has a shared part (latent
    attention's, never stacked); else the MOST parts that divide ``rep``
    whose score tile ``[rep / parts * bq, bk]`` float32 is at least
    :data:`PART_SCORE_BYTES`, and under which the body's unrolled parts, over
    every branch it holds (one under a mask or a window, two without:
    :func:`heads_a_step` counts the same), are at most
    :data:`PART_BODIES`. The readings (:func:`_causal_attention` has the
    table): every served shape read fastest skewed, by 16-23% at the rule's
    parts; under a mask the most parts read fastest (keye's eight of 256
    rows: the mask's vector work hides the matrix unit's refills), maskless
    1,024 rows read 2% under 256 or 512 (laguna's full call 15.54 ms at 3
    parts, 15.86 at 6; nemotron3 20.32 at 4, 20.69 at 16), and parts of half
    a megabyte LOSE (laguna's windowed call 8.13 -> 8.53 at nine parts of 256
    x 512, on the band's tiles it ran in until PR 77): the floor. The bound on the bodies is a START's: a part and
    branch more costs about 0.1 s of trace and lowering at every start, a
    kernel and not a call site (laguna's step at 6 parts in its full calls
    and 3 in its windowed ones, 24 part bodies where 6 stood: ``step_ms.hit``
    333.3 -> 319.2 and warm ``setup_s`` 19.0-19.3 -> 20.4-21.3,
    ``startup_trace_s`` 3.18 -> 3.99, ``startup_lower_s`` 3.37 -> 4.29: my chip
    runs, PR 75), while a compile alone shows nothing (laguna's full call 6.5
    s at one part, 6.4-9.2 at 2 / 3 / 6). So minicpm_sala's sixteen parts
    (62.91 for 66.51 at eight) stay behind it, as laguna's windowed calls
    did while their body held four branches (7.51 ms at three parts for 8.13:
    12 bodies); in ONE branch against one key window (PR 77) the rule gives
    them three parts of ``[768, 768]`` (3.71 ms for 3.92 at one; nine of
    0.79 MB read 3.65 and stay under the floor, as phi4flash's two, 1.62 for
    1.67). No bound on VMEM or on the
    unrolled score tiles: the parts' live set is a part's scores, the next
    part's and one part's exponentials, less than the stacked tile's with its
    own, and the body writes the score bytes it wrote
    (:data:`UNROLLED_SCORE_BYTES`' comment). ``want`` (a test's or a timing
    run's own) takes the place of the rule's choice: the most parts at or
    under it that divide ``rep``."""
    if rep == 1 or ds:
        return 1
    if want is not None:
        return next(cut for cut in range(min(int(want), rep), 0, -1) if rep % cut == 0)
    return next((cut for cut in range(min(rep, PART_BODIES // _branches(masked, window)), 1, -1)
                 if rep % cut == 0 and rep // cut * bq * bk * 4 >= PART_SCORE_BYTES), 1)


def _masked_query_tile(s: int, block_q: int, mq: int, row_keys: int) -> int:
    """The largest multiple of the mask's query tile ``mq`` that divides ``s``
    and is at most ``block_q``, and whose stacked score tile — ``row_keys``
    float32 scores a query row: the group's heads times the key tile, as
    :func:`causal_tiles` counts them — stays within :data:`SCORE_TILE_BYTES`
    (the mask's own tile at the least)."""
    most = min(block_q, s, SCORE_TILE_BYTES // (4 * row_keys))
    return next(t for t in range(max(most // mq, 1) * mq, 0, -mq) if s % t == 0)


def causal_steps(b: int, s: int, g: int, rep: int, d: int, dv: int, ds: int = 0, *,
                 block_q: int = 256, block_k: int = 512, mask_tiles: Optional[Tuple[int, int]] = None,
                 window: Optional[int] = None, turned: bool = False) -> Tuple[int, int, int]:
    """``(head tiles, grid steps, part tiles)`` of ONE call of the batched
    kernel, from what :func:`masked_gqa_attention` is given (``mask_tiles``:
    the mask's ``(query tile, key tile)``): the ``(head, query tile, key
    tile)`` visits, the grid steps that make them — their quotient is the
    heads a grid step beyond a group's own (:func:`heads_a_step`) — and the
    ``(part, query tile, key tile)`` score products those steps write: over
    the grid steps, the parts a stacked group's rows are cut into
    (:func:`parts_a_step`; the grid steps' own number where nothing is cut).
    Under a ``window`` a call takes ONE grid step a query tile (its key tile
    is the key window, :func:`band_keys`: laguna's windowed call 2 x 8 x 34 =
    544 where the band's 256 x 512 tiles took 1,056 until PR 77)."""
    if mask_tiles is None:
        bq, bk = causal_tiles(s, rep, block_q, block_k, window, d if turned else 0)
    else:
        bk = mask_tiles[1]
        bq = _masked_query_tile(s, block_q, mask_tiles[0], rep * bk)
    hb = heads_a_step(g, rep, bq, bk, d, dv, ds, masked=mask_tiles is not None, window=window,
                      turned=turned)
    cut = parts_a_step(rep, bq, bk, ds, masked=mask_tiles is not None, window=window)
    pairs = s // bq if window is not None else len(_band_tiles(s, bq, bk))  # (one step a query tile)
    return b * g * pairs, b * (g // hb) * pairs, b * (g // hb) * pairs * cut


def causal_tiles(s: int, rep: int, block_q: int, block_k: int,
                 window: Optional[int] = None, turned: int = 0) -> Tuple[int, int]:
    """The ``(query tile, key tile)`` the maskless batched kernel runs a
    sequence of ``s`` in, from the tiles asked for (``pick_tile``'s, as
    ever), the ``rep`` query heads a group whose query tiles it stacks, and
    the window: a rule, where there were two constants measured at two
    shapes. (1) Under a window the query tile is no wider than its share of
    the window (:data:`BAND_QUERY_TILE`) and the "key tile" is the ONE key
    window a query tile is run against (:func:`band_keys`: the window and the
    tile, ``block_k`` is not asked): a row is run against ``window + bq``
    keys, of which ``window`` are the band's. (2) The stacked score tile ``[rep * bq, bk]`` float32 stays
    within :data:`SCORE_TILE_BYTES` — a HEAD's budget (a group's stacked
    heads are one tile): where a grid step takes a block of heads alone in
    their groups (:func:`heads_a_step`) the step keeps the block's live
    tiles, two of them, and that rule counts them
    (:func:`block_vmem_bytes`), not this one: the query tile is the largest that
    divides ``s`` and does; where the kernel turns heads of ``turned`` lanes
    (``_causal_attention``'s ``turn``) a query row's share of that budget
    also counts its float32 block in the pipeline's two buffers, its rows
    of the stacked bf16 scratch and of the two tables (at 6 heads of 128 a
    group 26,112 + 9,728 bytes a row: 512 rows still fit). It leaves the
    tiles of every step measured
    before it as they were (4 heads of 64 a group at 1,088 x 1,088: 18.9 MB;
    a head of 128 + 64 alone: 4.7) and gives 6 heads of 128 a group 512 x
    1,088 (13.4 MB) and 9 under a window of 512 256 rows against 768 keys
    (7.1). One full
    layer on the v5e, 2 x 8,704 tokens, 6 heads of 128 a group, on PR 53's
    tree (the call with the head-major transposes it then had: my chip
    runs, PR 53), ms: 512 x 1,088 21.2, 256 x 1,088 21.0, 512 x 512 24.6,
    1,088 x 512 24.7, 1,088 x 1,088 (a score tile of 28.4 MB) 31.7; since
    PR 58 the same call at 512 x 1,088 is 19.9 for a kernel of 18.28 (my
    chip runs, PR 58: q's layout and k's reshape are what is left); the
    windowed layer's are beside :data:`BAND_QUERY_TILE`."""
    bq, bk = pick_tile(s, block_q), pick_tile(s, block_k)
    if window is not None:
        bq = min(bq, pick_tile(s, max(int(window * BAND_QUERY_TILE), 1)))
        bk = band_keys(s, bq, window)
    # a query row's bytes: its scores; where the kernel turns heads of `turned`, its float32 block
    # (twice: the pipeline's two buffers), the stacked bf16 scratch and its rows of the two tables
    fits = SCORE_TILE_BYTES // (4 * rep * bk + (10 * rep + 16) * turned)
    if bq > fits:
        bq = min(bq, pick_tile(s, max(fits, 1)))
        bk = bk if window is None else band_keys(s, bq, window)
    return bq, bk


def masked_gqa_attention(q, k, v, mask=None, *, num_kv_heads: int, block_q: Optional[int] = None,
                         block_k: int = 512, interpret: Optional[bool] = None,
                         q_shared=None, k_shared=None, window: Optional[int] = None,
                         out_gate=None, shared_turn=None, shared_scale: float = 1.0, turn=None,
                         turn_width: int = 0, turn_scale: float = 1.0,
                         q_scale: float = 1.0,
                         mask_blocks: Optional[BlockSelection] = None) -> jax.Array:
    """``q [B, S, H*d]`` (already scaled by ``d**-0.5``), ``k, v [B, S,
    G*d]``, ``mask`` from :func:`select_keys` (``B`` 1: a selection is one
    sequence's; with ``mask_blocks``, the sizes of a selection of BLOCKS,
    :func:`select_blocks`' flags ``[G, S, W]``, one selection a KEY HEAD:
    every causal tile is still computed, in the selection's own key tile,
    ``mask_blocks.tiles(S)``, the keys padded to whole tiles) or none -> ``o
    [B, S, H*d]``: causal softmax attention,
    under a mask of every query head over the keys its query selected,
    query head ``h`` reading key-value head ``h // (H/G)``. Masked-dense:
    every causal tile is computed. ``block_q`` is the kernel's query tile
    (under a mask a multiple of the mask's, which is the least).

    ONE form (:func:`_causal_attention`): each sequence on its own, in
    tiles of ``block_q`` (default 256) by ``block_k``, only tiles at or
    below the diagonal visited. Without a mask it is plain causal
    attention. Operands and output are token-major HERE; what the kernel
    reads in place and what it has transposed head-major first follows
    from the widths (:func:`_causal_attention`: heads of whole lane
    blocks in place at any number a group; heads of 64 transposed). With
    ``turn`` (two float32 tables ``[B*S, d]``, ``decoder.turn_tables(angles,
    d)``; maskless or windowed) ``q`` and ``k`` are FLOAT32 and unturned,
    as their products wrote them, and the kernel turns the leading
    ``turn_width`` lanes (0: all) of every head by them, times
    ``turn_scale``, q also times ``q_scale``, each rounded once to ``v``'s
    type. There the value heads
    may have a width of their own (``v [B, S, G*dv]`` -> ``[B, S, H*dv]``),
    ``v`` may be ``None`` where ``k [B, S, G*2d]`` holds each head's keys
    and then its values (read in place: no slice), and a score may have a second
    part, ``q_shared [B, S, H*ds] . k_shared [B, S, ds]``, whose key is ONE
    for all heads (latent attention: the rotary key); with ``shared_turn``
    (two float32 tables ``[B*S, ds]``, ``[cos | cos]`` and ``[sin | sin]``
    of every token's angles) ``q_shared`` is float32 and not yet turned,
    and the kernel turns each query tile of it, times ``shared_scale``,
    rounded once to ``q``'s type. With a mask it is the selection, over
    latent attention (a head alone in its group, a block of heads a grid
    step) or over grouped-query heads (Keye's eight a group: the group's
    query tile read as ONE token-major block and stacked in the kernel), in
    the mask's key tile, k and v padded to its whole tiles where it does
    not divide ``S``.
    With ``window`` (maskless only) a query attends to the keys ``t -
    window < j <= t`` of its sequence and the kernel takes ONE grid step a
    query tile, against one window of ``window + block_q`` keys that follows
    the diagonal (``block_k`` is not its to take), in one softmax pass,
    under the name ``windowed_gqa_attention``. With
    ``out_gate [B, S, H]`` float32 each head's output leaves times its
    token's scalar (``decoder.gated``'s arithmetic, in the kernel's last
    step). The maskless form's tiles are :func:`causal_tiles`' of the two
    asked for."""
    if q.ndim != 3:
        raise ValueError(f"operands are [B, S, .] (a selection's: [1, S, .]): q is {q.shape}")
    block_q = block_q or 256
    if mask is None:
        g = int(num_kv_heads)
        d = k.shape[2] // (2 * g if v is None else g)
        block_q, block_k = causal_tiles(q.shape[1], q.shape[2] // (g * d), block_q, block_k,
                                        window, d if turn is not None else 0)
    return _causal_attention(q, k, v, int(num_kv_heads), block_q, block_k,
                             _interpret(interpret), q_shared, k_shared, mask, window, out_gate,
                             shared_turn, shared_scale, turn, turn_width, turn_scale, q_scale,
                             mask_blocks=mask_blocks)


def windowed_gqa_attention(q, k, v, *, window: int, num_kv_heads: int, block_q: Optional[int] = None,
                           block_k: int = 512, interpret: Optional[bool] = None,
                           out_gate=None, turn=None, turn_width: int = 0, turn_scale: float = 1.0,
                           q_scale: float = 1.0) -> jax.Array:
    """:func:`masked_gqa_attention`'s batched maskless form under a
    ``window``: ``q [B, S, H*d]``, ``k, v [B, S, G*d]`` -> ``[B, S, H*d]``,
    a query attending to the keys ``t - window < j <= t`` of its own
    sequence. A function of its own because a device trace names a kernel
    after the jitted function it was traced in wherever locations hold one
    frame (``utils/jaxenv.configure_compile_cache``: every entry point):
    jitted under this name the windowed layers' calls are
    ``%windowed_gqa_attention`` there, and a full layer's stay
    ``%masked_gqa_attention`` (seen on the v5e, PR 53: under the one jit all
    nine of a step carried the one name)."""
    return masked_gqa_attention(q, k, v, num_kv_heads=num_kv_heads, block_q=block_q,
                                block_k=block_k, interpret=interpret, window=window,
                                out_gate=out_gate, turn=turn, turn_width=turn_width,
                                turn_scale=turn_scale, q_scale=q_scale)
