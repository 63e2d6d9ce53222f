"""Linear attention with a decay that is a CONSTANT of the head, in chunks.

Lightning Attention-2's recurrence (arXiv:2401.04658) carries, per head
``h``, a ``[d, d]`` float32 state along the sequence, ``S_0 = 0`` at every
sequence's first token:

    S_t = lambda_h S_{t-1} + k_t v_t^T        o_t = S_t^T q_t        lambda_h = exp(-slope_h)

with ``q_t`` and ``k_t`` the head's rows RMS-normed over their ``d`` columns
(a gain each, ``q_t`` times the layer's scale) and then turned by a rotary,
and the output normed per head and THEN gated: ``rms(o_t; gain) *
sigmoid(z_t)``. Beside the package's other two chunked scans
(``ops/ssd.py``, ``ops/delta_rule.py``) it has no step size, no ``beta``, no
inverse and no running sum: the decay is not computed from a token, so a
chunk's decays are the SAME in every chunk. In chunks of ``C`` rows from a
state ``S`` — an identity, not an approximation:

    O = (Q . lambda^(i+1)) S + ((Q K^T) . M) V        M[i, j] = lambda^(i-j)  (j <= i, else 0)
    S <- lambda^C S + (K . lambda^(C-1-i))^T V

Every exponent is at most 0: nothing overflows, and what underflows (the
fastest head's ``lambda^255``) is the true value's own underflow. What
shapes it on the chip:

- ``M`` (``C^2`` exponentials a head) and the two decay vectors are made ONCE
  for a head block's whole sequence, at its first grid step, into VMEM
  scratch, and serve every chunk after it;
- q and k come FLOAT32, unnormed and unturned, exactly as ``W_q``'s and
  ``W_k``'s products wrote them, and the kernel norms, scales, turns (``x *
  cos + rolled * sin`` in whole vregs, as ``sparse_attention._turned_head``
  does, by the rows' two tables ``[T, d]``, one pair for all heads) and
  rounds them once: the two 64-lane halves of every head that XLA slices out
  of a float32 product to turn it (PR 63's finding) are never made;
- a grid step takes ``HEADS`` heads and ``ROWS`` rows and goes chunk by
  chunk, within a chunk part by part through ALL its heads (PR 56's lesson:
  written head after head, every product waits for its own result); one
  head's chunks are a chain only through the state;
- the row blocks are the sequential grid axis, the states sit in VMEM
  scratch, float32; nothing is handed on past a sequence's end.

Matrix products take ``product_dtype`` operands (bf16 as served) and sum in
float32; the norms, the turn, the decays and the state are float32. Off the
TPU it runs in Pallas interpret mode (tests, rehearsals).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from psana_ray_tpu.ops.delta_rule import chunk_rows

HEADS = 4  # heads a grid step
CHUNK = 256  # rows a chunk at most
ROWS = 512  # rows a grid step at most: whole chunks


def decay_slopes(heads: int) -> np.ndarray:
    """Lightning Attention-2's slopes, ``-log lambda_h = 2^(-8 (h + 1) / H)``
    for ``h = 0 .. H - 1`` (ALiBi's geometric sequence): float32 ``[H]``,
    from 0.84 a token (head 0: ``lambda`` 0.43) down to ``2^-8`` (0.996)."""
    return np.exp2(-8.0 * np.arange(1, heads + 1) / heads).astype(np.float32)


def step_rows(seq_len: int, chunk: int = CHUNK) -> tuple:
    """``(rows a grid step, rows a chunk)`` for sequences of ``seq_len``: the
    chunk the largest multiple of 8 that divides it and is at most ``chunk``
    (``delta_rule.chunk_rows``), a grid step as many whole chunks as divide
    the sequence within :data:`ROWS`."""
    c = chunk_rows(seq_len, chunk)
    n = next(n for n in range(max(ROWS // c, 1), 0, -1) if seq_len % (n * c) == 0)
    return n * c, c


def _kernel(slopes_ref, q_ref, k_ref, v_ref, z_ref, qg_ref, kg_ref, og_ref, *rest, heads, d, chunk,
            eps, turned, product_dtype):
    from jax.experimental.pallas import tpu as pltpu

    if turned:
        cos_ref, sin_ref, *rest = rest
    o_ref, state_ref, mask_ref, fall_ref, left_ref = rest
    hg, r = pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32

    def mm(a, b, dims=((1,), (0,))):
        return jax.lax.dot_general(a.astype(product_dtype), b.astype(product_dtype),
                                   (dims, ((), ())), preferred_element_type=f32)

    @pl.when(r == 0)  # a sequence starts: S_0 = 0, and the head block's decays, made once
    def _start():
        state_ref[...] = jnp.zeros(state_ref.shape, f32)
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(f32)
        for h in range(heads):
            slope = slopes_ref[hg * heads + h]
            mask_ref[h] = jnp.where(i >= j, jnp.exp(-slope * (i - j).astype(f32)), 0.0)
            # column 0: lambda^(i+1), of the state a row reads; column 1: lambda^(C-1-i), of a
            # row in the state the chunk leaves
            fall_ref[h] = jnp.concatenate([jnp.exp(-slope * (row + 1.0)),
                                           jnp.exp(-slope * (chunk - 1.0 - row))], axis=1)
            left_ref[h] = jnp.exp(jnp.full((1, d), -slope * chunk, f32))  # lambda^C: of the state left

    def normed(x, gain):  # one head's rows, float32: the RMS norm over its d columns
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain

    def one_chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        at = [slice(h * d, (h + 1) * d) for h in range(heads)]
        q = [normed(q_ref[rows, cols], qg_ref[...]) for cols in at]
        k = [normed(k_ref[rows, cols], kg_ref[...]) for cols in at]
        if turned:
            cos, sin = cos_ref[rows, :], sin_ref[rows, :]
            # lane i meets lane i + d/2, its pair's other half, under the SIGNED sine: the whole head
            # turned in whole vregs (`sparse_attention._turned_head`'s arithmetic at width d)
            q, k = ([x * cos + pltpu.roll(x, d - d // 2, 1) * sin for x in u] for u in (q, k))
        v = [v_ref[rows, cols] for cols in at]
        # part by part through all the heads: one head's parts are a chain
        scores = [mm(q[h], k[h], ((1,), (1,))) * mask_ref[h] for h in range(heads)]
        states = [state_ref[h] for h in range(heads)]
        falls = [fall_ref[h] for h in range(heads)]
        out = [mm(scores[h], v[h]) + mm(q[h] * falls[h][:, 0:1], states[h]) for h in range(heads)]
        for h in range(heads):
            state_ref[h] = states[h] * left_ref[h] + mm(k[h] * falls[h][:, 1:2], v[h], ((0,), (0,)))
        for h, cols in enumerate(at):
            gate = jax.nn.sigmoid(z_ref[rows, cols].astype(f32))
            o_ref[rows, cols] = (normed(out[h], og_ref[...]) * gate).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // chunk, one_chunk, 0)


@functools.partial(jax.jit, static_argnames=("seq_len", "heads", "eps", "scale", "chunk",
                                             "product_dtype", "interpret"))
def lightning_attention(q, k, v, z, slopes, q_gain, k_gain, o_gain, turn=None, *, seq_len: int,
                        heads: int, eps: float, scale: float, chunk: int = CHUNK,
                        product_dtype=jnp.bfloat16, interpret: Optional[bool] = None) -> jax.Array:
    """``q, k [T, H*d]`` FLOAT32, as their products wrote them (``T`` rows
    being whole sequences of ``seq_len``), ``v [T, H*d]``, ``z [T, H*d]`` (the
    output gate's pre-activation), ``slopes [H]`` float32 (``-log lambda``, at
    least 0), ``q_gain, k_gain, o_gain [d]``, ``turn`` the rows' two tables
    ``[T, d]`` float32 (``decoder.turn_tables(angles, d)``: ``[cos | cos]``
    and the signed sine ``[-sin | sin]``; ``None``: no rotary) -> ``rms(o;
    o_gain) * sigmoid(z) [T, H*d]`` in ``v``'s type, ``o_t = S_t^T q_t`` with
    ``q`` normed, times ``scale``, and turned, ``k`` normed and turned.
    ``eps`` is the three norms'; ``chunk`` the rows a chunk at most."""
    from jax.experimental.pallas import tpu as pltpu

    t, wide = v.shape
    d = wide // heads
    rows, c = step_rows(seq_len, chunk)
    if (q.shape != (t, wide) or k.shape != (t, wide) or z.shape != (t, wide) or t % seq_len
            or wide % heads or slopes.shape != (heads,)):
        raise ValueError(f"lightning: q {q.shape}, k {k.shape}, v {v.shape}, z {z.shape} and "
                         f"slopes {slopes.shape} are not sequences of {seq_len} rows of {heads} heads")
    group = next(n for n in range(min(HEADS, heads), 0, -1) if heads % n == 0)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    n_blocks = seq_len // rows

    def cols(b, g, r, slopes):  # a head block's columns of a row block
        return b * n_blocks + r, g

    def gain(u, by=1.0):
        return (u.astype(f32) * by)[None]

    block = pl.BlockSpec((rows, group * d), cols)
    entry = pl.BlockSpec((1, d), lambda b, g, r, slopes: (0, 0))
    tables, table_specs = [], []
    if turn is not None:
        tables = [u.astype(f32).reshape(t, d) for u in turn]
        table_specs = [pl.BlockSpec((rows, d), lambda b, g, r, slopes: (b * n_blocks + r, 0))] * 2
    return pl.pallas_call(
        functools.partial(_kernel, heads=group, d=d, chunk=c, eps=float(eps),
                          turned=turn is not None, product_dtype=product_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(t // seq_len, heads // group, n_blocks),
            in_specs=[block, block, block, block, entry, entry, entry, *table_specs],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((group, d, d), f32), pltpu.VMEM((group, c, c), f32),
                            pltpu.VMEM((group, c, 2), f32), pltpu.VMEM((group, 1, d), f32)]),
        out_shape=jax.ShapeDtypeStruct((t, wide), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="lightning_attention",
    )(slopes.astype(f32), q, k, v, z, gain(q_gain, scale), gain(k_gain), gain(o_gain), *tables)
