"""The gated delta rule in chunks: a decay PER CHANNEL, and ONE decay a head.

Kimi Delta Attention's recurrence (arXiv:2510.26692) carries, per head, a
``[d_k, d_v]`` float32 state along the sequence, ``S_0 = 0`` at every
sequence's first token:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T        o_t = S_t^T q_t

with ``alpha_t = exp(g_t)`` per head AND channel, ``g_t = lower *
sigmoid(exp(A) (f_t + b))`` in ``(lower, 0)`` (the bounded gate:
``kda_safe_gate``, ``kda_lower_bound``), ``q_t`` and ``k_t`` the L2-normed
rows of the head (``q_t`` times ``d_k^-1/2``), and the output normed per
head and gated: ``rms(o_t; gain) * sigmoid(z_t)``. Nothing else in the
package carries a state along a sequence (``ops/short_conv.py`` carries
rows of its input).

Token by token that is 8,704 dependent rank-one updates a sequence. Here it
is matrix products over chunks of ``C`` rows, an identity and not an
approximation (Kimi Linear's WY form). With ``G`` the running sum of ``g``
inside the chunk and ``S`` the state the chunk starts from:

    A_kk[i, j] = sum_c k_i[c] k_j[c] e^(G_i[c] - G_j[c])    (j < i)     A_qk alike from q_i   (j <= i)
    X = (I + Diag(beta) tril(A_kk, -1))^-1
    [W | U] = X [beta k e^G | beta v]          vbar = U - W S          o = (q e^G) S + tril(A_qk) vbar
    S <- Diag(e^(G_C)) S + (k e^(G_C - G))^T vbar

Four things shape it on the chip:

- ``e^(-G_j)`` leaves float32 after 17 rows at -5 a row, so ``A_kk`` and
  ``A_qk`` are not one product of pre-scaled operands: a row block of
  ``BLOCK`` (16) rows scales its rows by ``e^(G_i - r)`` and the keys by
  ``e^(r - G_j)``, ``r`` the block's first row's ``G``: the first is at
  most 1, the second at most ``e^75`` inside the block (capped, so that
  nothing past a row's diagonal, which is masked, is infinite) and at most
  1 before it. A block reads the keys up to its own last row only, and the
  ones before the block's before come from that block's, times ``e^(r -
  r_before)``: one ``[1, d]`` factor of at most 1 (``_keys_of_blocks``);
- the inverse of the unit lower-triangular ``I + B`` comes by halves: with
  the inverses of the two diagonal halves known, the block below the
  diagonal is ``-X_22 B_21 X_11``; from 1 x 1 blocks up that is ``2 log2(C)
  - 2`` products of whole ``C x C`` matrices (the block-diagonal ``X`` of
  one level on both sides of that level's off-diagonal part of ``B``) in
  place of a row-by-row substitution. Every factor is a true inverse of a
  sub-block or a part of ``B``: bounded. The series ``(I + N)(I + N^2)(I +
  N^4)...`` costs the same and is the same matrix on paper, but its powers
  are not bounded: a run of identical keys under a slow decay (a detector's
  blank patches) makes ``N`` a triangle of ``-beta`` whose 64th power has
  entries of 10^37, and the step came out NaN on the chip (PR 50);
- one head's chunk is a CHAIN: the running sum, the blocks, twelve
  products of the inverse each waiting for the one before, ``W`` and ``U``,
  the state. ``HEADS`` heads a grid step, and the body goes part by part
  and level by level through ALL of them (``_chunks``), so that the matrix
  unit always has another head's product to take. Written head after head
  the compiler kept that order and every product waited for its own
  result: 18.9 ms a layer at the served shape for 7.5 (PR 56; the inverse
  alone 10.6 ms of it for 2.5). The chunks are the sequential grid axis,
  the states sit in VMEM scratch (transposed, ``[d_v, d_k]``: the decay
  then scales lanes);
- what the chains hid costs little once they run side by side. Counted in
  results of ``[8, 128]`` registers the body was 3,362 a head-chunk, about
  a fifth of them the inverse's masks (seven shifts-and-compares of ``[C,
  C]`` a head) and a quarter the blocks' keys (all ``C`` rows rescaled for
  every block). The masks built once a grid step (``_constants``) and the
  keys rescaled from the block before leave 2,500; that bought 3% under
  the old order and 1.1% under the new, and one cast an operand in place
  of ``_mm``'s two bought nothing (PR 56, on the chip: PERF.md section 7).

Matrix products take bf16 operands and sum in float32; the state, the
running sums of ``g`` (a triangular product of ``g`` split in three bf16
parts: exact) and every exponent are float32. The gate, both L2 norms,
the recurrence and the output's norm and gate are this ONE kernel: it
reads ``[q | k | v]`` (after their convolution), ``f``, ``z`` and ``beta``
once and writes the gated output once. Off the TPU it runs in Pallas
interpret mode (tests, rehearsals).

The SECOND form (:func:`gated_delta_net`: Gated DeltaNet, arXiv:2412.06464)
has ONE decay a head and token, Mamba's gate ``g_t = -exp(A_log) softplus(a_t
+ dt_bias)``, unbounded below, keys ``d_k`` and values ``d_v`` wide (a
RECTANGULAR ``[d_k, d_v]`` state), a step size that may pass 1 and a SiLU
gate AFTER the output's norm. A scalar decay leaves the products: ``A_kk[i,
j] = (k_i . k_j) e^(G_i - G_j)``, one ``[C, C]`` matrix of decays a head whose
every exponent under the mask is <= 0 (:func:`_head_products`), so there are
no row blocks, no rescaled keys and no cap. What the two forms share: the
constants made once a grid step, the exact running sums, the inverse by
halves and everything after the scores (:func:`_chunks`: ``W | U``, the
state's read and update, the heads of a grid step part by part side by
side). Heads that fill no whole lane tile (96 wide: 30 of them are 22.5
tiles, and no group of heads that divides 30 cuts them at tiles) reach the
kernel a head at 128 columns, zeros after the 96 (:func:`lanes_a_head`). On
the v5e at 8,704 tokens, 30 heads of 96 -> 192, a layer ALONE (my chip runs,
PR 67; rows a chunk x heads a grid step): 128 x 6 1.83 ms (built), 128 x 10
1.75, 128 x 2 2.88, 64 x 10 1.77, 64 x 6 2.18, 64 x 2 4.59, 32 x 6 3.09, 256
x 2 4.10 (the first call of 10 heads 3.1 s for 6 heads' 1.7).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# rows a chunk. On the v5e at 4 x 8,704 tokens, 32 heads of 128, HEADS 4, a layer: 128 rows 7.53 ms,
# 64 rows 10.13, 32 rows 15.47 (a grid step's own cost, paid per chunk); HEADS 8 6.64, HEADS 2 11.35
# at 128 rows (my chip runs, PR 56: the heads of a grid step side by side; 8 heads are a body twice
# as long to trace and compile). Head after head, PR 50's body: 19.9 / 26.4 / 38.6; HEADS 8 19.3,
# HEADS 2 20.7 (my chip runs, PR 50): more heads bought a grid step's cost and no more
CHUNK = 128
BLOCK = 16  # rows that share a reference row: 15 rows at -5 a row is e^75, float32 ends at e^88
HEADS = 4  # heads a grid step
L2_EPS = 1e-6  # in the L2 norms' root, as the public KDA kernels have it
_CAP = 80.0  # of a masked entry's exponent: 128 channels of e^80 still sum inside float32
# the form with ONE decay a head (`gated_delta_net`): rows a chunk and heads a grid step. On the v5e
# at 8,704 tokens, 30 heads of 96 -> 192, a layer: 128 rows x 6 heads 1.83 ms, x 10 heads 1.75 (a
# body and a first call nearly twice as long), x 2 heads 2.88; 64 rows 2.18 / 1.77 / 4.59; 32 rows x
# 6 heads 3.09; 256 rows x 2 heads 4.10 (my chip runs, PR 67)
HEAD_CHUNK = 128
HEAD_GROUP = 6


def _mm(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _constants(c):
    """What a chunk's body reads of its rows' and columns' NUMBERS, the same for
    every head, chunk and layer, made once a grid step: the two triangles, the
    identity, the running sum's triangle of ones, and for each level of the
    inverse the entries that join two diagonal blocks of ``2^shift`` rows into
    one (same block of ``2 * 2^shift``, other half: ``(row ^ col) >> shift ==
    1``; of a strictly lower ``below`` that is the part under the diagonal)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    apart, lower_eq = row ^ col, row >= col
    joins = [(apart >> shift) == 1 for shift in range((c - 1).bit_length())]
    return row > col, lower_eq, jnp.where(apart == 0, 1.0, 0.0), lower_eq.astype(jnp.bfloat16), joins


def _running_sum(g, ones):
    """``[C, d]`` float32 -> the sums over rows ``0..i``, exact: the product of
    ``ones`` (lower triangular, bf16) with ``g`` split in three bf16 parts."""
    d = g.shape[1]
    parts = _mm(ones, jnp.concatenate(_three_parts(g), axis=1))
    return parts[:, :d] + parts[:, d:2 * d] + parts[:, 2 * d:]


def _three_parts(g):
    """``g`` float32 as three bf16 arrays whose sum it is, exactly."""
    hi = g.astype(jnp.bfloat16)
    rest = g - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return [hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)]


def _unit_lower_inverses(below, eye, joins):
    """``(I + b)^-1`` for each ``b [C, C]`` of ``below``, strictly lower
    triangular, by halves from 1 x 1 blocks up: a level's ``X`` holds the
    inverses of the diagonal blocks of ``size`` rows, and the next level's is
    ``X - X E X``, ``E`` the part of ``b`` that joins two such blocks into one.
    Level by level for ALL the matrices, so that their chains run side by side."""
    xs = [eye - jnp.where(joins[0], b, 0.0) for b in below]  # the 1 x 1 blocks' inverses are 1
    for level in joins[1:]:
        half = [_mm(x, jnp.where(level, b, 0.0)) for x, b in zip(xs, below)]
        xs = [x - _mm(xe, x) for x, xe in zip(xs, half)]
    return xs


def _keys_of_blocks(k, gam, block):
    """For each block of ``block`` rows, first row ``lo``, the keys it reads:
    rows ``0 .. lo + block`` of ``k [C, d]``, row ``j`` times ``e^(r - G_j)``,
    ``r = G_lo``. The block's own rows and the block's before are made so; the
    rows before those are the block before's, times ``e^(r - r_before)``: one
    ``[1, d]`` factor of at most 1, so nothing can overflow and what underflows
    is the true value's own underflow. The cap guards the block's own rows
    (``j > lo``: at most ``e^75``; past a row's diagonal they are masked)."""
    out = []
    for lo in range(0, k.shape[0], block):
        r, near = gam[lo:lo + 1], max(lo - block, 0)
        keys = k[near:lo + block] * jnp.exp(jnp.minimum(r - gam[near:lo + block], _CAP))
        if near:
            keys = jnp.concatenate([out[-1][:near] * jnp.exp(r - gam[near:near + 1]), keys])
        out.append(keys)
    return out


def _channel_products(q, k, g, constants, block: int):
    """The decay PER CHANNEL: ``g [C, d_k]`` a head -> ``(G, A)`` per head, ``G``
    the running sums ``[C, d_k]`` and ``A`` a list over the row blocks of
    ``[A_kk | A_qk]`` stacked ``[2 * block, C]`` (unmasked: zeros after the
    block's last row, anything past a row's diagonal). The decays ride INSIDE
    the products, on operands rescaled block by block."""
    ones = constants[3]
    heads, (c, d_k) = range(len(q)), k[0].shape
    gam = [_running_sum(u, ones) for u in g]
    keys = [_keys_of_blocks(k[h], gam[h], block) for h in heads]
    a = [[] for _ in heads]
    for i, lo in enumerate(range(0, c, block)):
        for h in heads:
            scale = jnp.exp(gam[h][lo:lo + block] - gam[h][lo:lo + 1])
            seen = keys[h][i]  # the rows after the block are masked: zeros
            if lo + block < c:
                seen = jnp.concatenate([seen, jnp.zeros((c - lo - block, d_k), jnp.float32)])
            a[h].append(_mm(jnp.concatenate([k[h][lo:lo + block] * scale,
                                             q[h][lo:lo + block] * scale]), seen, ((1,), (1,))))
    return gam, a


def _running_sum_rows(g, ones):
    """:func:`_running_sum` with the rows along the LANES: ``[heads, C]`` ->
    the sums over columns ``0..j``, exact (three products of a few rows: a
    stack of them would cut sublane tiles)."""
    return sum(_mm(part, ones, ((1,), (1,))) for part in _three_parts(g))


def _head_products(q, k, g_cols, g_rows, constants):
    """ONE decay a head: ``g_cols [C, heads]`` and the same numbers as
    ``g_rows [heads, C]`` -> ``(G, A)`` as :func:`_channel_products` gives
    them, ``G [C, 1]`` a head and ``A`` ONE block of all ``C`` rows. A scalar
    decay leaves the products: ``A_kk[i, j] = (k_i . k_j) e^(G_i - G_j)``, one
    ``[C, C]`` matrix of decays a head whose every exponent at or under the
    diagonal is <= 0 (the rest is masked BEFORE the exponential), so there
    are no row blocks, no rescaled keys and no cap, and the gate may be as
    negative as it likes: what underflows is the true value's own underflow."""
    lower_eq, ones = constants[1], constants[3]
    gam, along = _running_sum(g_cols, ones), _running_sum_rows(g_rows, ones)
    gam = [gam[:, h:h + 1] for h in range(len(q))]
    a = []
    for h, col in enumerate(gam):
        decay = jnp.exp(jnp.where(lower_eq, col - along[h:h + 1], -jnp.inf))
        a.append([_mm(jnp.concatenate([k[h], q[h]]), k[h], ((1,), (1,)))
                  * jnp.concatenate([decay, decay])])
    return gam, a


def _chunks(q, k, v, gam, a, beta, state_t, constants, block: int):
    """One chunk of each of a grid step's heads (lists over the heads): ``q, k``
    (normed) ``[C, d_k]``, ``v [C, d_v]``, the running sums ``gam [C, d_k]`` or
    ``[C, 1]`` and the scores ``a`` (:func:`_channel_products`,
    :func:`_head_products`), ``beta [C, 1]``, the state transposed ``[d_v, d_k]``
    -> ``(o [C, d_v], state_t)`` per head. Part by part for all the heads: one
    head's parts are a chain."""
    strict, lower_eq, eye, ones, joins = constants
    heads, (c, d_k) = range(len(q)), k[0].shape
    xs = _unit_lower_inverses(
        [jnp.where(strict, beta[h] * jnp.concatenate([u[:block] for u in a[h]]), 0.0)
         for h in heads], eye, joins)
    decay = [jnp.exp(u) for u in gam]
    wu = [_mm(xs[h], jnp.concatenate([beta[h] * k[h] * decay[h], beta[h] * v[h]], axis=1))
          for h in heads]
    from_state = [_mm(jnp.concatenate([wu[h][:, :d_k], q[h] * decay[h]]), state_t[h], ((1,), (1,)))
                  for h in heads]
    vbar = [wu[h][:, d_k:] - from_state[h][:c] for h in heads]
    o = [from_state[h][c:]
         + _mm(jnp.where(lower_eq, jnp.concatenate([u[block:] for u in a[h]]), 0.0), vbar[h])
         for h in heads]
    last = [u[c - 1:c] for u in gam]
    along = last
    if gam[0].shape[1] == 1:  # ONE decay a head: along the lanes BEFORE the exponential, down the
        along = [jnp.broadcast_to(u, (1, d_k)) for u in last]  # state's rows after it (Mosaic
        # broadcasts one way at a time)
    return [(o[h], state_t[h] * jnp.exp(along[h])
             + _mm(vbar[h], k[h] * jnp.exp(last[h] - gam[h]), ((0,), (0,)))) for h in heads]


def _kernel(q_ref, k_ref, v_ref, f_ref, z_ref, beta_ref, ea_ref, b_ref, gain_ref, o_ref,
            state_ref, *, heads, d, lower, eps, block):
    @pl.when(pl.program_id(2) == 0)  # a sequence starts: S_0 = 0
    def _start():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    gain = gain_ref[...].astype(jnp.float32)
    cols = [slice(h * d, (h + 1) * d) for h in range(heads)]  # a head's columns of every operand
    q, k = ([u[:, at].astype(jnp.float32) for at in cols] for u in (q_ref, k_ref))
    q = [u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + L2_EPS) * d ** -0.5 for u in q]
    k = [u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + L2_EPS) for u in k]
    g = [lower * jax.nn.sigmoid(ea_ref[:, at] * (f_ref[:, at] + b_ref[:, at])) for at in cols]
    v = [v_ref[:, at].astype(jnp.float32) for at in cols]
    beta, state = [beta_ref[:, h:h + 1] for h in range(heads)], [state_ref[h] for h in range(heads)]
    constants = _constants(q_ref.shape[0])
    done = _chunks(q, k, v, *_channel_products(q, k, g, constants, block), beta, state, constants,
                   block)
    for h, (at, (o, state)) in enumerate(zip(cols, done)):
        state_ref[h] = state
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * gain
        o_ref[:, at] = (o * jax.nn.sigmoid(z_ref[:, at].astype(jnp.float32))).astype(o_ref.dtype)


def chunk_rows(seq_len: int, chunk: int = CHUNK) -> int:
    """The rows of a chunk for sequences of ``seq_len``: the largest multiple
    of 8 that divides it and is at most ``chunk``."""
    rows = next((c for c in range(min(chunk, seq_len) // 8 * 8, 0, -8) if seq_len % c == 0), 0)
    if not rows:
        raise ValueError(f"delta rule: no chunk of whole 8-row tiles divides {seq_len} rows")
    return rows


@functools.partial(jax.jit, static_argnames=("seq_len", "heads", "lower", "eps", "chunk",
                                             "interpret"))
def gated_delta_rule(qkv, f, z, beta, log_a, bias, gain, *, seq_len: int, heads: int,
                     lower: float, eps: float, chunk: int = CHUNK,
                     interpret: Optional[bool] = None) -> jax.Array:
    """``qkv [T, 3*H*d]`` (``[q | k | v]`` head after head, ``T`` rows being
    whole sequences of ``seq_len``), ``f [T, H*d]`` float32 (the decay's
    pre-activation), ``z [T, H*d]`` (the output gate's), ``beta [T, H]``
    float32 in (0, 1), ``log_a [H]``, ``bias [H*d]``, ``gain [d]`` -> the
    normed, gated output ``[T, H*d]`` in ``qkv``'s type. ``lower`` is the
    log-decay's bound a row (negative), ``eps`` the output norm's."""
    from jax.experimental.pallas import tpu as pltpu

    t, hd = f.shape
    d = hd // heads
    rows = chunk_rows(seq_len, chunk)
    block = BLOCK if rows % BLOCK == 0 else 8
    if qkv.shape != (t, 3 * hd) or t % seq_len or hd % heads or (block - 1) * -lower > _CAP:
        raise ValueError(f"delta rule: [q | k | v] {qkv.shape} and f {f.shape} are not sequences "
                         f"of {seq_len} rows of {heads} heads, or {block - 1} rows at {lower} a "
                         "row leave the exponent's cap")
    group = next(n for n in range(min(HEADS, heads), 0, -1) if heads % n == 0)
    n_groups, n_chunks = heads // group, seq_len // rows
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def cols(part):  # a group's columns of q, k or v (parts 0-2 of qkv), or of f, z and the output
        return pl.BlockSpec((rows, group * d),
                            lambda b, j, c: (b * n_chunks + c, part * n_groups + j))

    per_channel = pl.BlockSpec((1, group * d), lambda b, j, c: (0, j))
    expand = jnp.repeat(jnp.exp(log_a.astype(jnp.float32)), d)[None]
    return pl.pallas_call(
        functools.partial(_kernel, heads=group, d=d, lower=float(lower), eps=float(eps),
                          block=block),
        grid=(t // seq_len, n_groups, n_chunks),
        in_specs=[cols(0), cols(1), cols(2), cols(0), cols(0),
                  pl.BlockSpec((None, rows, group), lambda b, j, c: (j, b * n_chunks + c, 0)),
                  per_channel, per_channel, pl.BlockSpec((1, d), lambda b, j, c: (0, 0))],
        out_specs=cols(0),
        out_shape=jax.ShapeDtypeStruct((t, hd), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((group, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_rule",
    )(qkv, qkv, qkv, f.astype(jnp.float32), z,
      jnp.transpose(beta.astype(jnp.float32).reshape(t, n_groups, group), (1, 0, 2)),
      expand, bias.astype(jnp.float32)[None], gain.astype(jnp.float32)[None])


# ---------------------------------------------------------------------------
# ONE decay a head (Gated DeltaNet, arXiv:2412.06464), keys and values of their own widths
# ---------------------------------------------------------------------------

def _head_kernel(q_ref, k_ref, v_ref, z_ref, ac_ref, ar_ref, beta_ref, nega_c_ref, bias_c_ref,
                 nega_r_ref, bias_r_ref, gain_ref, o_ref, state_ref, *, heads, d_k, eps):
    @pl.when(pl.program_id(2) == 0)  # a sequence starts: S_0 = 0
    def _start():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    lanes, d_v = q_ref.shape[1] // heads, v_ref.shape[1] // heads
    gain = gain_ref[...].astype(jnp.float32)
    keys = [slice(h * lanes, (h + 1) * lanes) for h in range(heads)]  # a head's columns of q and k,
    vals = [slice(h * d_v, (h + 1) * d_v) for h in range(heads)]  # and of v, z and the output
    q, k = ([u[:, at].astype(jnp.float32) for at in keys] for u in (q_ref, k_ref))
    q = [u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + L2_EPS) * d_k ** -0.5 for u in q]
    k = [u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + L2_EPS) for u in k]
    # Mamba's gate, unbounded below, as columns [C, heads] and as rows [heads, C]
    g_cols = nega_c_ref[...] * jax.nn.softplus(ac_ref[...] + bias_c_ref[...])
    g_rows = nega_r_ref[...] * jax.nn.softplus(ar_ref[...] + bias_r_ref[...])
    v = [v_ref[:, at].astype(jnp.float32) for at in vals]
    beta, state = [beta_ref[:, h:h + 1] for h in range(heads)], [state_ref[h] for h in range(heads)]
    constants = _constants(q_ref.shape[0])
    done = _chunks(q, k, v, *_head_products(q, k, g_cols, g_rows, constants), beta, state,
                   constants, q_ref.shape[0])
    for h, (at, (o, state)) in enumerate(zip(vals, done)):
        state_ref[h] = state
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * gain
        z = z_ref[:, at].astype(jnp.float32)
        o_ref[:, at] = (o * jax.nn.silu(z)).astype(o_ref.dtype)


def lanes_a_head(u, heads: int, lanes: int = 128):
    """``u [T, H*d]`` -> ``[T, H*dl]``, ``dl`` the next multiple of ``lanes``:
    each head's columns at whole lane tiles, zeros after them (``u`` itself
    where ``d`` is whole tiles already). A zero column changes no L2 norm, no
    product with a key and no row of a state: how heads that fill no whole
    lane tile (96 wide: 30 of them are 22.5 tiles, and no group of heads that
    divides 30 starts every block at a tile) reach :func:`gated_delta_net`."""
    t, d = u.shape[0], u.shape[1] // heads
    if d % lanes == 0:
        return u
    return jnp.pad(u.reshape(t, heads, d), ((0, 0), (0, 0), (0, -d % lanes))).reshape(t, -1)


def head_group(heads: int, lanes: int, d_v: int, want: int = 0, tiles: bool = True) -> int:
    """The heads a grid step of :func:`gated_delta_net` takes: the largest
    count up to ``want`` (0: :data:`HEAD_GROUP`) that divides ``heads`` and,
    on the chip (``tiles``), whose key and value columns are whole lane tiles
    (values 192 wide: even counts); all the heads where none does."""
    return next((n for n in range(min(want or HEAD_GROUP, heads), 0, -1)
                 if heads % n == 0 and not (tiles and (n * lanes % 128 or n * d_v % 128))), heads)


@functools.partial(jax.jit, static_argnames=("seq_len", "heads", "key_dim", "eps", "chunk", "group",
                                             "interpret"))
def gated_delta_net(q, k, v, a, z, beta, a_log, dt_bias, gain, *, seq_len: int, heads: int,
                    key_dim: int, eps: float, chunk: int = HEAD_CHUNK, group: int = 0,
                    interpret: Optional[bool] = None) -> jax.Array:
    """The gated delta rule with ONE decay a head over a RECTANGULAR state:
    ``q, k [T, H*dl]`` (after their convolution; a head's ``key_dim``
    components in its first columns and zeros after them: :func:`lanes_a_head`),
    ``v, z [T, H*d_v]``, ``a [T, H]`` float32 (the decay's pre-activation),
    ``beta [T, H]`` float32 (the step size, whatever its range: ``2 sigmoid``
    where eigenvalues may be negative), ``a_log, dt_bias [H]``, ``gain [d_v]``
    -> the normed, gated output ``[T, H*d_v]`` in ``v``'s type, ``T`` rows
    being whole sequences of ``seq_len``: per head ``g_t = -exp(a_log) softplus(a_t
    + dt_bias)``, ``S_t = (I - beta_t k_t k_t^T) e^(g_t) S_{t-1} + beta_t k_t
    v_t^T`` on a ``[key_dim, d_v]`` float32 state from 0, ``o_t = S_t^T q_t``
    (q, k L2-normed, q times ``key_dim^-1/2``), ``rms(o_t; gain)`` times
    ``silu(z_t)``. ``group``: the heads a grid step (0: :func:`head_group`'s)."""
    from jax.experimental.pallas import tpu as pltpu

    t, f32 = a.shape[0], jnp.float32
    lanes, d_v = q.shape[1] // heads, v.shape[1] // heads
    rows = chunk_rows(seq_len, chunk)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    group = head_group(heads, lanes, d_v, group, tiles=not interpret)
    if (q.shape != (t, heads * lanes) or k.shape != q.shape or v.shape != (t, heads * d_v)
            or z.shape != v.shape or a.shape != (t, heads) or beta.shape != a.shape or t % seq_len
            or lanes < key_dim or heads % group):
        raise ValueError(f"delta rule: q, k {q.shape}, v, z {v.shape}, a {a.shape} are not sequences "
                         f"of {seq_len} rows of {heads} heads with keys {key_dim} wide in groups "
                         f"of {group}")
    n_groups, n_chunks = heads // group, seq_len // rows

    def cols(width):  # a group's columns of q and k, or of v, z and the output
        return pl.BlockSpec((rows, group * width), lambda b, j, c: (b * n_chunks + c, j))

    def by_group(u):  # [T, H] -> [groups, T, group]: a grid step's heads as columns
        return jnp.transpose(u.astype(f32).reshape(t, n_groups, group), (1, 0, 2))

    def per_head(u):  # [H] -> a group's entries as a row and as a column
        u = u.astype(f32).reshape(n_groups, 1, group)
        return u, jnp.transpose(u, (0, 2, 1))

    (nega_c, nega_r), (bias_c, bias_r) = per_head(-jnp.exp(a_log.astype(f32))), per_head(dt_bias)
    as_cols = pl.BlockSpec((None, rows, group), lambda b, j, c: (j, b * n_chunks + c, 0))
    col_entry = pl.BlockSpec((None, 1, group), lambda b, j, c: (j, 0, 0))
    row_entry = pl.BlockSpec((None, group, 1), lambda b, j, c: (j, 0, 0))
    return pl.pallas_call(
        functools.partial(_head_kernel, heads=group, d_k=key_dim, eps=float(eps)),
        grid=(t // seq_len, n_groups, n_chunks),
        in_specs=[cols(lanes), cols(lanes), cols(d_v), cols(d_v), as_cols,
                  # the same pre-activations with a chunk's rows along the lanes, a chunk a block
                  pl.BlockSpec((None, None, group, rows), lambda b, j, c: (j, b * n_chunks + c, 0, 0)),
                  as_cols, col_entry, col_entry, row_entry, row_entry,
                  pl.BlockSpec((1, d_v), lambda b, j, c: (0, 0))],
        out_specs=cols(d_v),
        out_shape=jax.ShapeDtypeStruct((t, heads * d_v), v.dtype),
        scratch_shapes=[pltpu.VMEM((group, d_v, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_net",
    )(q, k, v, z, by_group(a),
      jnp.transpose(a.astype(f32).reshape(t // rows, rows, n_groups, group), (2, 0, 3, 1)),
      by_group(beta), nega_c, bias_c, nega_r, bias_r, gain.astype(f32)[None])
