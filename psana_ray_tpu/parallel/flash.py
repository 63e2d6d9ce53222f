"""Flash attention (Pallas TPU kernel) + ring composition over the mesh.

:func:`ring_attention` (ring_attention.py) is the exact XLA formulation —
differentiable, runs anywhere, materializes one [Sq, Sk] score block per
hop. This module is the serving-optimized TPU path:

- :func:`attention_with_stats` — one device's attention returning the
  online-softmax statistics (normalized output + row log-sum-exp). On TPU
  with kernel-friendly shapes it runs a vendored Pallas flash kernel
  (below — no private JAX APIs) so the score matrix never leaves VMEM;
  elsewhere (or for odd shapes) an XLA fallback computes the same
  statistics.
- :func:`ring_flash_attention` — K/V shards rotate around the ``seq``
  mesh axis (``lax.ppermute`` — neighbor ICI traffic only); each hop runs
  a full flash attention against the visiting K/V block and hops combine
  by log-sum-exp, which is exact (softmax is associative under LSE
  renormalization). Causal hops use BLOCK-level structure: a visiting
  block entirely in the future contributes nothing (skipped — no wasted
  FLOPs), entirely in the past attends unmasked, and only the diagonal
  block runs the masked kernel.

Dtype contract: ``o`` matches the query dtype; the log-sum-exp statistics
are ALWAYS float32 regardless of input dtype (bf16 stats lose peaks and
break cross-hop renormalization), and the ring's running (m, num, den)
carry is float32 for the same reason.

Layouts match ring_attention.py: global ``[B, S, H, D]`` sharded
``P(None, seq_axis)``.

Differentiability: :func:`flash_attention` carries a full flash VJP
(backward kernels regenerate probability tiles from the saved row
log-sum-exp — no stored score matrix in either direction), which powers
``ulysses_attention(impl='flash')`` for long-context training. The
stats-returning :func:`attention_with_stats` is ALSO differentiable —
its lse cotangent folds into the backward's delta term (∂lse/∂s = p), so
the same two backward kernels serve it — which makes the hop-combining
:func:`ring_flash_attention` trainable end to end: gradients flow through
the LSE renormalization, the ``lax.switch`` causal hop structure, the
``fori_loop`` rotation (static trip count → scan), and the ``ppermute``
(whose transpose is the reverse rotation).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30
# Minimum tile edge (Mosaic lane constraint) — also the divisibility floor
# the kernel requires of Sq/Sk. ACTUAL block sizes are picked per call by
# :func:`_pick_blocks`: 128x128 tiles leave the kernel vector-bound (the
# f32 softmax/rescale work on a tile rivals its two 128-wide matmuls);
# growing the K edge amortizes the online-softmax state updates over more
# MXU work. Measured on v5e-1 at the ViT serving shape [2, 8448, 4, 128]:
# 128x128 = 16.9 ms, 256x256 = 8.3 ms, 384x1408 = 2.81 ms, plateau ~2.7 ms
# (~55% MXU util vs the 1.5 ms FLOP floor) — a 6x kernel speedup from
# block shape alone.
_BLOCK_MIN = 128
_MAX_BLOCK_Q = 512
_MAX_TILE_ELEMS = 1 << 20  # bq*bk cap: the f32 score tile stays ~4 MB VMEM
_MAX_KV_TILE_ELEMS = 1 << 18  # bk*d cap: K/V tiles (and the dkv backward's
# two f32 scratches) are double-buffered across grid steps — without this
# a small-sq / large-d call could pick a bk whose tiles alone blow VMEM


def _pick_blocks(sq: int, sk: int, d: int, backward: bool = False) -> Tuple[int, int]:
    """Largest (block_q, block_k) multiples of 128 that divide (sq, sk),
    with block_q capped and both the f32 score tile (bq*bk) and the K/V
    tile (bk*d) footprints bounded.

    ``backward=True`` halves both caps: the backward kernels keep THREE
    score-shaped f32 temps live at once (p, dp, ds) plus f32 dk/dv
    accumulator scratches, so forward-sized blocks can exceed VMEM on
    shapes (e.g. sq=sk=2048, d=128) that the forward compiles fine."""
    tile_cap = _MAX_TILE_ELEMS // (2 if backward else 1)
    kv_cap = _MAX_KV_TILE_ELEMS // (2 if backward else 1)
    bq = max(
        b for b in range(_BLOCK_MIN, min(sq, _MAX_BLOCK_Q) + 1, _BLOCK_MIN)
        if sq % b == 0
    )
    bk_cap = max(_BLOCK_MIN, min(tile_cap // bq, kv_cap // d))
    bk = max(
        b for b in range(_BLOCK_MIN, min(sk, bk_cap) + 1, _BLOCK_MIN)
        if sk % b == 0
    )
    return bq, bk


def _xla_attention_with_stats(q, k, v, causal: bool) -> Tuple[jax.Array, jax.Array]:
    """[B,H,Sq,D] x [B,H,Sk,D] -> (o [B,H,Sq,D] q.dtype, lse [B,H,Sq] f32)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        qi = jnp.arange(q.shape[2])[:, None]
        ki = jnp.arange(k.shape[2])[None, :]
        s = jnp.where((ki > qi)[None, None], NEG_INF, s)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ) / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return o.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# Vendored Pallas TPU flash kernel (public pallas APIs only).
#
# Grid (BH, Sq/block_q, Sk/block_k), key blocks iterating fastest: per step
# ONE [block_q, d] query tile and ONE [block_k, d] K/V tile are resident in
# VMEM (Pallas pipelines the tile DMAs across grid steps), so VMEM use is
# independent of sequence length — a [block_q, Sk] score matrix never
# exists and neither does a full K/V copy.  The online-softmax state
# (m, l, acc) lives in f32 VMEM scratch, which persists across grid steps;
# it is reset when a new query tile begins (kb == 0) and the normalized
# output + lse are written on the tile's last key step.  Scores/stats are
# f32; the p @ v matmul runs in the value dtype on the MXU with f32
# accumulation.  Causal tiles mask with NEG_INF; the masked-out entries
# are explicitly zeroed in p (exp(NEG_INF - NEG_INF) would otherwise
# contribute 1 on fully-dead tiles).
# ---------------------------------------------------------------------------


def _causal_tile_mask(qi, kb, block_q, block_k):
    """[block_q, block_k] bool, True where the entry is in the FUTURE
    (k index > q index) — shared by the forward and backward kernels so
    their masking can never desynchronize."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    cols = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return cols > rows


def _tile_live(qi, kb, block_q, block_k):
    """False when the whole (qi, kb) tile is in the causal future — its
    contribution is exactly zero, so kernels skip the tile body outright
    (~2x FLOPs saved on causal at long S; the README advertises this at
    hop level for the ring, the same structure applies at tile level)."""
    return (qi + 1) * block_q > kb * block_k


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, sm_scale, causal, n_kb
):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]

    @pl.when(kb == 0)
    def _reset():
        m_ref[:] = jnp.full((block_q, 1), NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros((block_q, 1), jnp.float32)
        acc_ref[:] = jnp.zeros((block_q, d), jnp.float32)

    def _tile_body():
        s = (
            jax.lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )  # [block_q, block_k]
        if causal:
            s = jnp.where(_causal_tile_mask(qi, kb, block_q, block_k), NEG_INF, s)

        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # masked scores are exactly NEG_INF; on a fully-dead tile m_new
        # stays NEG_INF and exp(s - m_new) would be exp(0) = 1 — zero
        # them explicitly
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + pv

    if causal:
        pl.when(_tile_live(qi, kb, block_q, block_k))(_tile_body)
    else:
        _tile_body()

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # lse rides in [bh, 1, sq] layout: 2D [bh, sq] blocks would need a
        # (1, block_q) block whose second-to-last dim Mosaic rejects (must
        # be divisible by 8 or equal the array dim)
        lse_ref[0, 0] = (m_ref[:] + jnp.log(l_safe))[:, 0]


def _pallas_attention_with_stats(
    q, k, v, causal: bool, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Vendored flash kernel entry. [B,H,S,D] layout, S/D multiples of 128."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf = k.reshape(bh, sk, d)
    vf = v.reshape(bh, sk, d)
    block_q, block_k = _pick_blocks(sq, sk, d)
    n_kb = sk // block_k

    kernel = functools.partial(
        _flash_kernel, sm_scale=d**-0.5, causal=causal, n_kb=n_kb
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# Largest head dim the kernels accept: beyond this even the minimum
# 128-wide K/V block exceeds the BACKWARD kv-tile cap (bk*d with the
# halved budget), so _pick_blocks' >=128 floor would silently void the
# documented VMEM bound — such shapes go to the XLA fallback instead.
_MAX_HEAD_DIM = _MAX_KV_TILE_ELEMS // (2 * _BLOCK_MIN)


def _kernel_shapes_ok(q, k) -> bool:
    sq, d = q.shape[2], q.shape[3]
    sk = k.shape[2]
    return (
        d % 128 == 0
        and d <= _MAX_HEAD_DIM
        and sq % _BLOCK_MIN == 0
        and sk % _BLOCK_MIN == 0
    )


# ---------------------------------------------------------------------------
# Flash backward (the standard two-kernel formulation).  With the forward's
# residuals (q, k, v, o, lse) the normalized probabilities regenerate per
# tile as p = exp(scale·qk − lse) — no stored score matrix, same VMEM
# independence from sequence length as the forward.  Given
# delta_i = Σ_d do_id·o_id (precomputed in XLA, one cheap fused reduce):
#
#     dv = pᵀ @ do
#     ds = p ⊙ (do @ vᵀ − delta)          (softmax Jacobian, normalized p)
#     dq = scale · ds @ k                  (accumulated over key blocks)
#     dk = scale · dsᵀ @ q                 (accumulated over query blocks)
#
# Two kernels because the two accumulations want opposite grid orders:
# dkv iterates query blocks innermost (dk/dv tiles resident), dq iterates
# key blocks innermost (dq tile resident).  Masked entries are explicitly
# zeroed in p — exp(NEG_INF − lse) is NOT reliably 0 when a row is fully
# masked (lse ≈ NEG_INF makes the exponent ≈ 0, i.e. p ≈ 1).
# ---------------------------------------------------------------------------


def _bwd_tile_p_ds(q_blk, k_blk, v_blk, do_blk, lse_blk, delta_blk,
                   sm_scale, causal, qi, kb, block_q, block_k):
    """Shared per-tile math: normalized probabilities + ds (both f32)."""
    s = (
        jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * sm_scale
    )  # [block_q, block_k]
    p = jnp.exp(s - lse_blk[:, None])
    if causal:
        p = jnp.where(_causal_tile_mask(qi, kb, block_q, block_k), 0.0, p)
    dp = jax.lax.dot_general(
        do_blk, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_blk[:, None]) * sm_scale
    return p, ds


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, sm_scale, causal, n_qb
):
    kb = pl.program_id(1)
    qi = pl.program_id(2)
    block_k, d = k_ref.shape[1], k_ref.shape[2]
    block_q = q_ref.shape[1]

    @pl.when(qi == 0)
    def _reset():
        dk_acc[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_acc[:] = jnp.zeros((block_k, d), jnp.float32)

    def _tile_body():
        p, ds = _bwd_tile_p_ds(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0, 0],
            delta_ref[0, 0], sm_scale, causal, qi, kb, block_q, block_k,
        )
        # dv += pᵀ @ do ; dk += dsᵀ @ q  (contract the query axis)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(_tile_live(qi, kb, block_q, block_k))(_tile_body)
    else:
        _tile_body()

    @pl.when(qi == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, sm_scale, causal, n_kb
):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]

    @pl.when(kb == 0)
    def _reset():
        dq_acc[:] = jnp.zeros((block_q, d), jnp.float32)

    def _tile_body():
        _, ds = _bwd_tile_p_ds(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0, 0],
            delta_ref[0, 0], sm_scale, causal, qi, kb, block_q, block_k,
        )
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(_tile_live(qi, kb, block_q, block_k))(_tile_body)
    else:
        _tile_body()

    @pl.when(kb == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _pallas_attention_bwd(
    q, k, v, o, lse, do, causal: bool, interpret: bool = False, dlse=None
):
    """[B,H,S,D] flash backward; returns (dq, dk, dv) in the input dtypes.

    ``dlse`` (optional, [B,H,Sq] f32) is the cotangent of the row
    log-sum-exp output. Since ∂lse_i/∂s_ij = p_ij, it enters the softmax
    Jacobian as ``ds = p·(dp − delta + dlse)·scale`` — algebraically just
    ``delta → delta − dlse``, so the kernels need no changes at all."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    sm_scale = d**-0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    qf, kf, vf = (x.reshape(bh, -1, d) for x in (q, k, v))
    dof = do.reshape(bh, sq, d)
    # [bh, 1, sq] stats layout — see the forward's lse note on Mosaic's
    # last-two-dims block constraint
    lsef = lse.reshape(bh, 1, sq)
    deltaf = delta.reshape(bh, 1, sq)
    block_q, block_k = _pick_blocks(sq, sk, d, backward=True)
    n_qb, n_kb = sq // block_q, sk // block_k

    qspec = pl.BlockSpec((1, block_q, d), lambda i, a, b_: (i, b_, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda i, a, b_: (i, a, 0))
    rowspec = pl.BlockSpec((1, 1, block_q), lambda i, a, b_: (i, 0, b_))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal, n_qb=n_qb
        ),
        grid=(bh, n_kb, n_qb),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, a, b_: (i, a, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, a, b_: (i, a, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)

    qspec2 = pl.BlockSpec((1, block_q, d), lambda i, a, b_: (i, a, 0))
    kspec2 = pl.BlockSpec((1, block_k, d), lambda i, a, b_: (i, b_, 0))
    rowspec2 = pl.BlockSpec((1, 1, block_q), lambda i, a, b_: (i, 0, a))
    (dq,) = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal, n_kb=n_kb
        ),
        grid=(bh, n_qb, n_kb),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda i, a, b_: (i, a, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)

    return (
        dq.reshape(b, h, sq, d),
        dk.reshape(b, h, sk, d),
        dv.reshape(b, h, sk, d),
    )


def _xla_attention_bwd(q, k, v, o, lse, do, causal: bool, dlse=None):
    """Reference backward from the same residuals (normalized p from lse);
    used off-TPU and for odd shapes — materializes the score matrix.
    ``dlse`` folds into delta exactly as in :func:`_pallas_attention_bwd`."""
    sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        qi = jnp.arange(q.shape[2])[:, None]
        ki = jnp.arange(k.shape[2])[None, :]
        s = jnp.where((ki > qi)[None, None], NEG_INF, s)
    p = jnp.exp(s - lse[..., None])
    if causal:
        p = jnp.where((ki > qi)[None, None], 0.0, p)
    dof = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * sm_scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _attention_core(q, k, v, causal: bool) -> Tuple[jax.Array, jax.Array]:
    """Undifferentiated (o, lse) in ``[B, H, S, D]``: Pallas flash kernel
    when the backend and shapes allow (D and both sequence lengths
    multiples of 128), else the XLA formulation."""
    if jax.default_backend() == "tpu" and _kernel_shapes_ok(q, k):
        return _pallas_attention_with_stats(q, k, v, causal)
    return _xla_attention_with_stats(q, k, v, causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def attention_with_stats(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Attention + row log-sum-exp, ``[B, H, S, D]`` layout.

    Both paths return ``o`` in the query dtype and ``lse`` in float32 —
    the statistics two hops combine must never be bf16.

    Differentiable IN BOTH OUTPUTS: the VJP handles the lse cotangent by
    folding it into the softmax-Jacobian delta term (∂lse/∂s = p, so
    ``ds = p·(dp − delta + dlse)·scale`` — the same two flash backward
    kernels, with ``delta − dlse`` as their delta input). This is what
    makes :func:`ring_flash_attention` trainable: the ring's LSE
    hop-combining differentiates through these stats.
    """
    return _attention_core(q, k, v, causal)


def _aws_fwd(q, k, v, causal):
    o, lse = _attention_core(q, k, v, causal)
    return (o, lse), (q, k, v, o, lse)


def _aws_bwd(causal, res, g):
    do, dlse = g
    q, k, v, o, lse = res
    if jax.default_backend() == "tpu" and _kernel_shapes_ok(q, k):
        return _pallas_attention_bwd(q, k, v, o, lse, do, causal, dlse=dlse)
    return _xla_attention_bwd(q, k, v, o, lse, do, causal, dlse=dlse)


attention_with_stats.defvjp(_aws_fwd, _aws_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False
) -> jax.Array:
    """Single-device attention, repo layout ``[B, S, H, D]`` (the
    long-sequence path when the whole context fits one chip).

    Differentiable: the VJP regenerates probabilities per tile from the
    saved (q, k, v, o, lse) residuals — flash memory behavior in both
    directions, no stored score matrix (kernel shapes permitting; odd
    shapes and non-TPU backends use the XLA formulation).

    ``causal`` uses TOP-LEFT-aligned absolute indices: q row ``i`` attends
    k cols ``<= i``, i.e. q and k are assumed to share an origin. With
    ``sq != sk`` this differs from FlashAttention's usual bottom-right
    alignment — cross-attention callers whose queries are OFFSET into the
    key sequence must bake the offset into the mask themselves (internally
    consistent here: forward, backward, and the XLA oracle all use the
    same ``k_index > q_index`` rule)."""
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    o, _ = _attention_core(qh, kh, vh, causal)
    return o.transpose(0, 2, 1, 3)


def _fa_fwd(q, k, v, causal):
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    o, lse = _attention_core(qh, kh, vh, causal)
    return o.transpose(0, 2, 1, 3), (qh, kh, vh, o, lse)


def _fa_bwd(causal, res, g):
    qh, kh, vh, o, lse = res
    doh = g.transpose(0, 2, 1, 3)
    if jax.default_backend() == "tpu" and _kernel_shapes_ok(qh, kh):
        dq, dk, dv = _pallas_attention_bwd(qh, kh, vh, o, lse, doh, causal)
    else:
        dq, dk, dv = _xla_attention_bwd(qh, kh, vh, o, lse, doh, causal)
    return tuple(x.transpose(0, 2, 1, 3) for x in (dq, dk, dv))


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str = "seq",
    causal: bool = False,
    data_axis: Optional[str] = None,
) -> jax.Array:
    """Exact ring attention with per-hop flash kernels + LSE combining.

    q/k/v: global ``[B, S, H, D]`` sharded ``P(data_axis, seq_axis)``
    (``data_axis=None`` replicates the batch; name a mesh axis to compose
    DP × SP — each data group runs its own independent ring). Under a
    causal mask the hop whose K/V block lies entirely in this shard's
    future is skipped outright (zero FLOPs), past blocks run unmasked, and
    only the diagonal hop pays the masked kernel — the block-level
    causal structure a token-level mask can't exploit.

    Trainable: every piece is reverse-differentiable — the per-hop
    :func:`attention_with_stats` carries a VJP with lse cotangent
    handling, and gradients flow back through the hop LSE-combine and the
    ring rotation (gradient parity vs :func:`ring_attention` is tested on
    the 8-device mesh, ``tests/test_ring_attention.py``).
    """
    n_ring = mesh.shape[seq_axis]
    spec = P(data_axis, seq_axis, None, None)

    def local(q, k, v):
        idx = lax.axis_index(seq_axis)
        qh = q.transpose(0, 2, 1, 3)  # [B,H,Sq,D]
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        b, h, sq, d = qh.shape

        # running stats in f32 ALWAYS (see module docstring): both kernel
        # and fallback emit f32 lse, and the hop-combine arithmetic below
        # must not round peaks through bf16
        mx = jnp.full((b, h, sq), NEG_INF, jnp.float32)
        num = jnp.zeros((b, h, sq, d), jnp.float32)
        den = jnp.zeros((b, h, sq), jnp.float32)

        def hop_outputs(k_cur, v_cur, src):
            if not causal:
                return attention_with_stats(qh, k_cur, v_cur, causal=False)

            def skip(k_cur, v_cur):
                return (
                    jnp.zeros_like(qh),
                    jnp.full((b, h, sq), NEG_INF, jnp.float32),
                )

            def full(k_cur, v_cur):
                return attention_with_stats(qh, k_cur, v_cur, causal=False)

            def diag(k_cur, v_cur):
                return attention_with_stats(qh, k_cur, v_cur, causal=True)

            branch = (src < idx).astype(jnp.int32) + 2 * (src == idx).astype(jnp.int32)
            return lax.switch(branch, (skip, full, diag), k_cur, v_cur)

        def body(step, carry):
            mx, num, den, k_cur, v_cur = carry
            src = (idx - step) % n_ring
            o_i, lse_i = hop_outputs(k_cur, v_cur, src)
            m_new = jnp.maximum(mx, lse_i)
            # guards: exp(NEG_INF - NEG_INF) = 1 would pollute the sums on
            # skipped hops / before the first contributing hop
            alpha = jnp.where(mx <= NEG_INF / 2, 0.0, jnp.exp(mx - m_new))
            w = jnp.where(lse_i <= NEG_INF / 2, 0.0, jnp.exp(lse_i - m_new))
            num = num * alpha[..., None] + o_i.astype(jnp.float32) * w[..., None]
            den = den * alpha + w
            perm = [(i, (i + 1) % n_ring) for i in range(n_ring)]
            k_nxt = lax.ppermute(k_cur, seq_axis, perm)
            v_nxt = lax.ppermute(v_cur, seq_axis, perm)
            return m_new, num, den, k_nxt, v_nxt

        mx, num, den, _, _ = lax.fori_loop(0, n_ring, body, (mx, num, den, kh, vh))
        o = (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)
        return o.transpose(0, 2, 1, 3)

    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
