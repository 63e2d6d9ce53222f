"""Mamba-2's selective state-space scan (SSD, arXiv:2405.21060), in chunks.

Per head ``h`` of ``H`` a ``[P, N]`` float32 state is carried along the
sequence, ``H_0 = 0`` at every sequence's first token; with the step
``Delta_t = softplus(dt_t + dt_bias) [H]`` and ``A = -exp(A_log) [H]``:

    H_t = exp(Delta_t[h] A[h]) H_{t-1} + Delta_t[h] x_t[h] (x) B_t        y_t[h] = H_t C_t + D[h] x_t[h]

``B_t`` and ``C_t`` (``N`` wide) are shared by the heads of a GROUP (``G``
groups of ``H / G`` consecutive heads: head ``h`` reads ``B_t[h // (H/G)]``;
one group where all heads share them), the decay is ONE scalar a head and
token, and the output is gated and then normed BY GROUP, over the ``H * P /
G`` channels of a token's group: ``rms(y * silu(z); gain)`` (the gate BEFORE
the norm; over all ``H * P`` channels under one group). ``G`` is read from
the shapes: ``[x | B | C]`` is ``H * P + 2 * G * N`` wide, the groups' ``B``
side by side and then their ``C``. ``ops/delta_rule.py`` is the nearest
thing in the package and shares only the skeleton (chunks on a sequential
grid axis, a float32 state in VMEM scratch zeroed at a sequence's first
chunk, ``chunk_rows``): no inverse, no per-channel decay, no L2 norm here.

Token by token that is 8,704 dependent rank-one updates a sequence. In
chunks of ``Q`` rows it is matrix products, an identity and no
approximation: with ``s_i`` the running sum of ``Delta A`` inside the chunk
(at most 0, falling) and ``H_in`` the state the chunk starts from,

    Y = (L o C B^T)(Delta . X) + e^s . (C H_in^T)        L[i, j] = e^(s_i - s_j)  (j <= i, else 0)
    H_out = e^(s_Q) H_in + (e^(s_Q - s) . Delta . X)^T B

Every exponent is a DIFFERENCE of two running sums and at most 0 (``L`` is
never a quotient of two exponentials: at -1.6 a token a chunk of 256 spans
``e^-410``), so nothing overflows and what underflows is the true value's
own underflow. What shapes it on the chip:

- ``C B^T [Q, Q]`` is ONE product a chunk for all 64 heads, masked to its
  lower triangle once; what differs by head is the ``[Q, Q]`` mask of decays
  (``Q^2`` exponentials a head-chunk) and the products with ``X``;
- heads of 64 are HALF a lane tile: the heads that share a tile (a PACK: 2
  at heads of 64) are read, weighted, gated and written as one ``[Q, 128]``
  array, their two states are one ``[128, N]`` array (a head's rows one
  after the other's), so ``C H^T`` and ``X^T B`` are one full-width product
  a pack, and a head's ``[Q, Q] x [Q, P]`` product takes the pack's ``X``
  with the other heads' lanes zeroed: the matrix unit is 128 wide whatever
  ``P`` is, so that costs what a 64-wide product costs, and the two results
  add up in place with no slice and no concatenation;
- a grid step takes ``HEADS`` heads and goes part by part through all of
  them (the masks of all, then the products of all: PR 56's lesson from the
  delta rule: written head after head, every product waits for its own
  result). The groups of heads are the INNERMOST grid axis: a chunk's output
  block ``[Q, H * P]`` stays in VMEM while its groups write their gated
  columns (float32, in scratch) and add up the rows' sums of squares; the
  last group norms the rows and writes the block once. One kernel reads
  ``x``, ``B``, ``C``, ``z`` and ``dt`` once and writes the normed output
  once; ``C B^T`` is made at a chunk's first group and kept;
- under ``G`` groups of ``B`` and ``C`` a grid step's heads lie inside ONE of
  them (at most ``HEADS``, and a divisor of ``H / G``), its ``B`` and ``C``
  blocks are that group's (the block index a function of the grid's
  head-group), and all of the above holds per group: the output block is the
  group's ``H * P / G`` columns, ``C B^T`` is made at the group's first step
  and the norm closes at its last. At 64 heads in 8 groups a grid step's
  heads ARE one group: ``C B^T`` is the step's own and the norm closes inside
  the step (no scratch but the states: nothing is carried across the chunk's
  steps and no write is deferred).

Matrix products take bf16 operands and sum in float32; the state, the
running sums (a triangular product of ``Delta A`` split in three bf16
parts: exact), the masks and every exponent are float32. Off the TPU it
runs in Pallas interpret mode (tests, rehearsals).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from psana_ray_tpu.ops.delta_rule import chunk_rows

HEADS = 8  # heads a grid step: four packs of two at heads of 64
LANES = 128
# a chunk's rows at most (scan_rows). A chunk here has no inverse and no row blocks, and a grid
# step's own cost is paid per chunk AND group of heads, so the scan wants more rows than the delta
# rule's 128: on the v5e at 8,704 tokens, 64 heads of 64 over a state of 128, a layer takes 1.57 ms
# at 128 rows, 1.60 at 256 and 1.29 at 512 (my chip run, PR 57). At 1,024 rows eight heads' masks
# alone are 32 MB of VMEM beside a 16 MB output block twice: over the kernel's 64 MB
ROWS = 512


def _mm(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _three_parts(a, axis: int):
    """``a`` float32 as three bf16 parts that sum to it exactly, side by side
    along ``axis`` (held in float32: the cast that follows is exact)."""
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    mid = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.concatenate([hi, mid, a - hi - mid], axis=axis)


def scan_rows(seq_len: int) -> int:
    """The rows of a chunk of the scan for sequences of ``seq_len``, from
    ``seq_len`` alone: the largest multiple of a lane tile (128) that divides
    it and is at most ``ROWS``, which is what the chip takes (the running sums
    are also rows of ``[heads, Q]``: a chunk is whole lane tiles, or the one
    chunk of the array); where none does (the tests' and the rehearsals' short
    sequences, interpreted), the largest multiple of 8 (``chunk_rows``)."""
    lanes = next((c for c in range(ROWS, 0, -LANES) if seq_len % c == 0), 0)
    return lanes or chunk_rows(seq_len, ROWS)


def _kernel(x_ref, b_ref, c_ref, z_ref, dtr_ref, dtc_ref, bias_r_ref, nega_r_ref, bias_c_ref,
            nega_c_ref, skip_ref, gain_ref, o_ref, state_ref, *carried_refs,
            heads, p, pack, eps, steps=None):
    """``steps``: the grid steps (of ``heads`` heads) that one group of ``B``
    and ``C`` spans, ``None`` where all heads share one (then every step of a
    chunk). Over more than one step the gated columns (``y_ref``), the rows'
    sums of squares (``ss_ref``) and ``C B^T`` (``cb_ref``) are carried in
    ``carried_refs``; a step that is a whole group of its own carries nothing."""
    c, g = pl.program_id(1), pl.program_id(2)
    own = steps == 1  # this step's heads are one whole group of B and C
    slot = g if steps is None else g % steps  # the step's place among its group's
    q = x_ref.shape[0]
    wide = p * pack  # a pack's lanes
    packs = heads // pack

    @pl.when(c == 0)  # a sequence starts: H_0 = 0
    def _start():
        state_ref[g] = jnp.zeros(state_ref.shape[1:], jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)

    if not own:
        y_ref, ss_ref, cb_ref = carried_refs

        @pl.when(slot == 0)  # a group starts: C B^T once for all its heads, its lower triangle
        def _chunk():
            ss_ref[...] = jnp.zeros(ss_ref.shape, jnp.float32)
            cb_ref[...] = jnp.where(row >= col, _mm(c_ref[...], b_ref[...], ((1,), (1,))), 0.0)

    # the steps and the running sums of Delta A, as columns [Q, heads] and as rows [heads, Q]
    step = jax.nn.softplus(dtr_ref[...] + bias_r_ref[...])
    s = _mm((row >= col), _three_parts(step * nega_r_ref[...], 1))
    s = s[:, :heads] + s[:, heads:2 * heads] + s[:, 2 * heads:]
    s_t = _mm(_three_parts(jax.nn.softplus(dtc_ref[...] + bias_c_ref[...]) * nega_c_ref[...], 0),
              (row <= col))
    s_t = s_t[:heads] + s_t[heads:2 * heads] + s_t[2 * heads:]
    last = s[q - 1:q]  # [1, heads]: the chunk's whole log-decay
    carried, kept = jnp.exp(s), step * jnp.exp(last - s)  # of the state read; of a row in the state left
    left = jnp.exp(last)

    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, wide), 1) // p
    row_head = jax.lax.broadcasted_iota(jnp.int32, (wide, 1), 0) // p

    def spread(cols, k, over=lane_head):
        """Pack ``k``'s lanes (or state rows), each holding its own head's entry of ``cols``."""
        out = cols[:, k * pack:k * pack + 1]
        for i in range(1, pack):
            out = jnp.where(over == i, cols[:, k * pack + i:k * pack + i + 1], out)
        return out

    # part by part through all the heads: one head's parts are a chain
    cb = (jnp.where(row >= col, _mm(c_ref[...], b_ref[...], ((1,), (1,))), 0.0) if own
          else cb_ref[...])
    masks = [jnp.exp(jnp.minimum(s[:, h:h + 1] - s_t[h:h + 1], 0.0)) * cb for h in range(heads)]
    at = [slice(k * wide, (k + 1) * wide) for k in range(packs)]
    x = [x_ref[:, cols].astype(jnp.float32) for cols in at]
    stepped = [x[k] * spread(step, k) for k in range(packs)]
    y = [sum(_mm(masks[k * pack + i], jnp.where(lane_head == i, stepped[k], 0.0))
             for i in range(pack)) for k in range(packs)]
    states = [state_ref[g, k] for k in range(packs)]
    from_state = [_mm(c_ref[...], states[k], ((1,), (1,))) for k in range(packs)]
    for k, cols in enumerate(at):
        out = y[k] + spread(carried, k) * from_state[k] + skip_ref[:, cols] * x[k]
        gate = z_ref[:, cols].astype(jnp.float32)
        out = out * gate * jax.nn.sigmoid(gate)
        if own:
            y[k] = out
        else:
            y_ref[slot, :, cols] = out
            ss_ref[...] += jnp.sum(out * out, axis=-1, keepdims=True)
    for k in range(packs):
        state_ref[g, k] = (states[k] * spread(left, k, row_head)
                           + _mm(x[k] * spread(kept, k), b_ref[...], ((0,), (0,))))

    if own:  # every channel of the rows' group is here: the norm closes inside the step
        scale = jax.lax.rsqrt(sum(jnp.sum(out * out, axis=-1, keepdims=True) for out in y)
                              / o_ref.shape[1] + eps)
        for k, cols in enumerate(at):
            o_ref[:, cols] = (y[k] * scale * gain_ref[:, cols]).astype(o_ref.dtype)
        return

    # the group's last heads: every channel of a row's group is there
    @pl.when(slot == (pl.num_programs(2) if steps is None else steps) - 1)
    def _norm():
        scale = jax.lax.rsqrt(ss_ref[...] / o_ref.shape[1] + eps)
        for j in range(y_ref.shape[0]):
            cols = slice(j * heads * p, (j + 1) * heads * p)
            o_ref[:, cols] = (y_ref[j] * scale * gain_ref[:, cols]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("seq_len", "heads", "state", "eps", "rows",
                                             "interpret"))
def ssd_scan(xbc, z, dt, dt_bias, a_log, skip, gain, *, seq_len: int, heads: int, state: int,
             eps: float, rows: Optional[int] = None,
             interpret: Optional[bool] = None) -> jax.Array:
    """``xbc [T, H*P + 2*G*N]`` (``[x | B | C]`` after their convolution, ``T``
    rows being whole sequences of ``seq_len``; ``x`` head after head, then the
    ``G`` groups' ``B``, then their ``C``, each read in place as column blocks;
    ``G`` follows from the width), ``z [T, H*P]`` (the gate's), ``dt [T, H]``
    float32 (the step's pre-activation: a log-decay is summed over a chunk),
    ``dt_bias, a_log, skip [H]``, ``gain [H*P]`` -> ``rms((y + skip x) *
    silu(z); gain) [T, H*P]`` in ``xbc``'s type, the norm over each group's
    ``H*P / G`` channels. ``state`` is ``N``, ``eps``
    the norm's; ``rows`` a chunk's rows where a test or a timing run sets them
    (None, as the model calls it: :func:`scan_rows`)."""
    from jax.experimental.pallas import tpu as pltpu

    t, wide = z.shape
    p = wide // heads
    rows = rows or scan_rows(seq_len)
    bc_groups = max((xbc.shape[1] - wide) // (2 * state), 1)  # of B and C, from the shapes
    if (xbc.shape != (t, wide + 2 * bc_groups * state) or dt.shape != (t, heads) or t % seq_len
            or wide % heads or wide % state or heads % bc_groups):
        raise ValueError(f"ssd: [x | B | C] {xbc.shape}, z {z.shape} and dt {dt.shape} are not "
                         f"sequences of {seq_len} rows of {heads} heads over a state of {state} "
                         f"in whole groups of B and C")
    # a grid step's heads lie inside one group of B and C
    group = next(n for n in range(min(HEADS, heads // bc_groups), 0, -1)
                 if heads // bc_groups % n == 0)
    # the grid steps a group of B and C spans (None: one group, every step of a chunk), and the
    # steps whose columns one output block holds
    steps = None if bc_groups == 1 else heads // bc_groups // group
    span = heads // group if steps is None else steps
    pack = next(n for n in range(min(max(LANES // p, 1), group), 0, -1) if group % n == 0)
    n_groups, n_chunks = heads // group, seq_len // rows
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if seq_len % rows or (not interpret and rows % LANES and rows != t):
        raise ValueError(f"ssd: chunks of {rows} rows do not cut sequences of {seq_len} rows "
                         f"into whole lane tiles ({LANES} rows)")
    f32 = jnp.float32
    by_group = dt.astype(f32).reshape(t, n_groups, group)

    def per_head(u):  # [H] -> a group's entries as a row and as a column
        u = u.astype(f32).reshape(n_groups, 1, group)
        return u, jnp.transpose(u, (0, 2, 1))

    (bias_r, bias_c), (nega_r, nega_c) = per_head(dt_bias), per_head(-jnp.exp(a_log.astype(f32)))

    def chunk_of(b, c, g):  # the row block of a grid step
        return b * n_chunks + c

    cols = pl.BlockSpec((rows, group * p), lambda b, c, g: (chunk_of(b, c, g), g))
    def of_group(g):  # the group of B and C a grid step's heads lie in (one group: a constant)
        return 0 if steps is None else g // steps

    shared = [pl.BlockSpec((rows, state), lambda b, c, g, k=k: (
        chunk_of(b, c, g), wide // state + k * bc_groups + of_group(g)))
              for k in (0, 1)]  # the groups' B, then their C: the column blocks after x's
    carried = [] if steps == 1 else [pltpu.VMEM((span, rows, group * p), f32),
                                     pltpu.VMEM((rows, 1), f32), pltpu.VMEM((rows, rows), f32)]
    row_entry = pl.BlockSpec((None, 1, group), lambda b, c, g: (g, 0, 0))
    col_entry = pl.BlockSpec((None, group, 1), lambda b, c, g: (g, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, heads=group, p=p, pack=pack, eps=float(eps), steps=steps),
        grid=(t // seq_len, n_chunks, n_groups),
        in_specs=[cols, *shared, cols,
                  pl.BlockSpec((None, rows, group), lambda b, c, g: (g, chunk_of(b, c, g), 0)),
                  pl.BlockSpec((None, group, rows), lambda b, c, g: (g, 0, chunk_of(b, c, g))),
                  row_entry, row_entry, col_entry, col_entry,
                  pl.BlockSpec((1, group * p), lambda b, c, g: (0, g)),
                  pl.BlockSpec((1, span * group * p), lambda b, c, g: (0, of_group(g)))],
        out_specs=pl.BlockSpec((rows, span * group * p),
                               lambda b, c, g: (chunk_of(b, c, g), of_group(g))),
        out_shape=jax.ShapeDtypeStruct((t, wide), xbc.dtype),
        scratch_shapes=[pltpu.VMEM((n_groups, group // pack, pack * p, state), f32), *carried],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssd_scan",
    )(xbc, xbc, xbc, z, jnp.transpose(by_group, (1, 0, 2)), jnp.transpose(by_group, (1, 2, 0)),
      bias_r, nega_r, bias_c, nega_c, jnp.repeat(skip.astype(f32), p)[None],
      gain.astype(f32)[None])
