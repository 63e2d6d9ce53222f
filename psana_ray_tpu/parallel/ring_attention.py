"""Long-context attention over the mesh: ring attention + Ulysses all-to-all.

The reference has no model code, hence no sequence parallelism (SURVEY.md
§5 "Long-context: absent"); the task spec makes it first-class for the TPU
build. Two standard schemes, both pure-JAX (shard_map + XLA collectives
over ICI — no hand-written sends):

- :func:`ring_attention` — K/V shards rotate around the 'seq' mesh axis via
  ``lax.ppermute`` while each device holds its Q shard, accumulating with
  the online-softmax (flash) recurrence. Memory per device is O(S/P); the
  P-step rotation overlaps compute with neighbor ICI transfers.
- :func:`ulysses_attention` — all-to-all re-shards sequence -> heads, runs
  ordinary attention on full sequences of H/P heads, and all-to-alls back.
  Cheaper at moderate S, needs H % P == 0.

Layouts: q/k/v are ``[B, S, H, D]`` global arrays sharded
``P(None, 'seq', None, None)``; outputs identical. Causal masking uses
global positions, so results match single-device attention bit-for-bit
(up to reduction order).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def reference_attention(q, k, v, causal: bool = False):
    """Plain softmax attention, [B,S,H,D] — the single-device oracle."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qi = jnp.arange(q.shape[1])[:, None]
        ki = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((ki > qi)[None, None], NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block_attn_accumulate(q, k_blk, v_blk, m, l, o, q_pos, k_pos, causal):
    """One online-softmax accumulation step against a K/V block.

    q [B,Sq,H,D]; k_blk/v_blk [B,Sk,H,D]; m,l [B,H,Sq]; o [B,Sq,H,D];
    q_pos [Sq], k_pos [Sk] global positions for causal masking."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale  # [B,H,Sq,Sk]
    if causal:
        mask = (k_pos[None, :] > q_pos[:, None])[None, None]
        s = jnp.where(mask, NEG_INF, s)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where(mask, 0.0, p)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None].transpose(0, 2, 1, 3) + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v_blk
    )
    return m_new, l_new, o_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str = "seq",
    causal: bool = False,
    data_axis: Optional[str] = None,
) -> jax.Array:
    """Exact attention with K/V rotating around the ring.

    q/k/v: global ``[B, S, H, D]``, sharded ``P(None, seq_axis)``. Each of
    the P devices holds S/P queries and rotates its K/V shard P times, so
    every Q block sees every K/V block with only neighbor ICI traffic
    (the ring-collective pattern XLA uses for all-gather, but with the
    flash accumulation fused between hops). ``data_axis`` additionally
    shards the batch dim for DP x SP composition (independent rings per
    data group)."""
    n_ring = mesh.shape[seq_axis]
    spec = P(data_axis, seq_axis, None, None)

    def local(q, k, v):
        # q,k,v local shards [B, S/P, H, D]
        idx = lax.axis_index(seq_axis)
        b, sq, h, d = q.shape
        sk = k.shape[1]
        m = jnp.full((b, h, sq), NEG_INF, q.dtype)
        l = jnp.zeros((b, h, sq), q.dtype)
        o = jnp.zeros_like(q)
        q_pos = idx * sq + jnp.arange(sq)

        def body(step, carry):
            m, l, o, k_cur, v_cur = carry
            # K/V currently held arrived from device (idx - step) % P
            src = (idx - step) % n_ring
            k_pos = src * sk + jnp.arange(sk)
            m, l, o = _block_attn_accumulate(q, k_cur, v_cur, m, l, o, q_pos, k_pos, causal)
            # rotate: send our block to the next device, receive previous
            perm = [(i, (i + 1) % n_ring) for i in range(n_ring)]
            k_nxt = lax.ppermute(k_cur, seq_axis, perm)
            v_nxt = lax.ppermute(v_cur, seq_axis, perm)
            return m, l, o, k_nxt, v_nxt

        m, l, o, _, _ = lax.fori_loop(0, n_ring, body, (m, l, o, k, v))
        l = jnp.maximum(l, 1e-30)  # fully-masked rows (strict causal tails)
        return o / l.transpose(0, 2, 1)[..., None]

    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str = "seq",
    causal: bool = False,
    impl: str = "reference",
    data_axis: Optional[str] = None,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Re-shards ``[B, S/P, H, D] -> [B, S, H/P, D]`` with one all-to-all,
    runs full-sequence attention per head group, and restores the layout
    with a second all-to-all. Requires H % P == 0.

    ``impl='flash'`` runs the per-head-group attention through
    :func:`parallel.flash.flash_attention` — fully differentiable with
    flash memory behavior in both directions (its VJP regenerates
    probability tiles from the saved lse instead of storing the score
    matrix), making this the long-context TRAINING path at scale;
    ``'reference'`` is the exact O(S²)-memory formulation.

    ``data_axis`` names the mesh axis the BATCH dim is sharded over, so
    DP and SP compose (each data-group runs its own independent
    all-to-alls over ``seq_axis``) — the ('data', 'seq') serving mesh of
    :class:`psana_ray_tpu.models.vit.ViTHitClassifier`."""
    p_devices = mesh.shape[seq_axis]
    if q.shape[2] % p_devices != 0:
        raise ValueError(f"heads {q.shape[2]} not divisible by {seq_axis}={p_devices}")
    if impl not in ("reference", "flash"):
        raise ValueError(f"impl must be 'reference' or 'flash', got {impl!r}")
    spec = P(data_axis, seq_axis, None, None)

    def local(q, k, v):
        # local [B, S/P, H, D] -> [B, S, H/P, D]
        def scatter_heads(x):
            return lax.all_to_all(x, seq_axis, split_axis=2, concat_axis=1, tiled=True)

        def gather_seq(x):
            return lax.all_to_all(x, seq_axis, split_axis=1, concat_axis=2, tiled=True)

        qf, kf, vf = scatter_heads(q), scatter_heads(k), scatter_heads(v)
        if impl == "flash":
            from psana_ray_tpu.parallel.flash import flash_attention

            of = flash_attention(qf, kf, vf, causal=causal)
        else:
            of = reference_attention(qf, kf, vf, causal=causal)
        return gather_seq(of)

    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
