"""Ring + Ulysses attention vs the single-device oracle on an 8-wide seq
mesh axis."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from psana_ray_tpu.parallel import create_mesh
from psana_ray_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention,
    ulysses_attention,
)


@pytest.fixture(scope="module")
def seq_mesh():
    return create_mesh(("data", "seq"), (1, 8))


def _qkv(b=2, s=64, h=8, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32)) * 0.3
    return mk(), mk(), mk()


def _shard(x, mesh):
    return jax.device_put(x, NamedSharding(mesh, P(None, "seq", None, None)))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_reference(seq_mesh, causal):
    q, k, v = _qkv()
    want = np.asarray(reference_attention(q, k, v, causal=causal))
    got = np.asarray(
        ring_attention(
            _shard(q, seq_mesh), _shard(k, seq_mesh), _shard(v, seq_mesh),
            seq_mesh, causal=causal,
        )
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(seq_mesh, causal):
    q, k, v = _qkv(seed=1)
    want = np.asarray(reference_attention(q, k, v, causal=causal))
    got = np.asarray(
        ulysses_attention(
            _shard(q, seq_mesh), _shard(k, seq_mesh), _shard(v, seq_mesh),
            seq_mesh, causal=causal,
        )
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_ulysses_rejects_bad_heads(seq_mesh):
    q, k, v = _qkv(h=6)  # 6 % 8 != 0
    with pytest.raises(ValueError, match="heads"):
        ulysses_attention(_shard(q, seq_mesh), _shard(k, seq_mesh), _shard(v, seq_mesh), seq_mesh)


def test_ring_under_jit_and_grad(seq_mesh):
    # ring attention must be differentiable and jittable (training path)
    q, k, v = _qkv(b=1, s=32, h=4, d=8)

    @jax.jit
    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, seq_mesh) ** 2)

    g = jax.grad(loss)(_shard(q, seq_mesh), _shard(k, seq_mesh), _shard(v, seq_mesh))
    assert np.isfinite(np.asarray(g)).all()

    @jax.jit
    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v) ** 2)

    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-3, atol=1e-4)


class TestRingFlashAttention:
    """ring_flash_attention (per-hop flash + LSE combining) must match the
    single-device oracle exactly — on the CPU test backend the hops run
    the XLA statistics fallback, which shares the combining math with the
    TPU Pallas path."""

    @pytest.fixture
    def seq_mesh(self):
        return create_mesh(("seq",), (8,))

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_oracle(self, rng, seq_mesh, causal):
        from psana_ray_tpu.parallel import ring_flash_attention
        from psana_ray_tpu.parallel.ring_attention import reference_attention

        b, s, h, d = 2, 32, 4, 8
        q = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        ref = reference_attention(q, k, v, causal=causal)
        got = ring_flash_attention(q, k, v, seq_mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_jit_sharded(self, rng, seq_mesh):
        from jax.sharding import NamedSharding

        from psana_ray_tpu.parallel import ring_flash_attention
        from psana_ray_tpu.parallel.ring_attention import reference_attention

        b, s, h, d = 1, 16, 2, 8
        mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        sh = NamedSharding(seq_mesh, P(None, "seq", None, None))
        q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
        f = jax.jit(
            lambda q, k, v: ring_flash_attention(q, k, v, seq_mesh, causal=True)
        )
        got = f(q, k, v)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_single_device_flash_wrapper(self, rng):
        from psana_ray_tpu.parallel import flash_attention
        from psana_ray_tpu.parallel.ring_attention import reference_attention

        b, s, h, d = 2, 24, 3, 8
        q = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, causal=True)),
            np.asarray(reference_attention(q, k, v, causal=True)),
            rtol=2e-5, atol=2e-5,
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_grad_parity_with_ring_attention(self, rng, seq_mesh, causal):
        """VERDICT r3 #9: ring_flash_attention must be trainable — its
        gradients (through the per-hop stats VJP, the LSE hop-combine,
        the causal lax.switch, and the ppermute rotation) must match the
        differentiable XLA ring on the 8-device mesh."""
        from psana_ray_tpu.parallel import ring_flash_attention
        from psana_ray_tpu.parallel.ring_attention import ring_attention

        b, s, h, d = 1, 32, 2, 8
        mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32)) * 0.4
        q, k, v = mk(), mk(), mk()
        q, k, v = (_shard(x, seq_mesh) for x in (q, k, v))
        w = _shard(jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32)), seq_mesh)

        def loss(attn):
            def f(q, k, v):
                return jnp.sum(attn(q, k, v, seq_mesh, causal=causal) * w)

            return f

        got = jax.grad(loss(ring_flash_attention), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(ring_attention), argnums=(0, 1, 2))(q, k, v)
        for name, g, r in zip("qkv", got, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=2e-4, atol=2e-5,
                err_msg=f"d{name} mismatch",
            )

    def test_grad_under_jit_sharded(self, rng, seq_mesh):
        from psana_ray_tpu.parallel import ring_flash_attention

        b, s, h, d = 1, 16, 2, 8
        mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        q, k, v = (_shard(mk(), seq_mesh) for _ in range(3))

        g = jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(
                    ring_flash_attention(q, k, v, seq_mesh, causal=True) ** 2
                ),
                argnums=(0, 1, 2),
            )
        )(q, k, v)
        for x in g:
            arr = np.asarray(x)
            assert np.isfinite(arr).all()
            assert np.abs(arr).max() > 0

    def test_bf16_ring_matches_oracle(self, rng, seq_mesh):
        """bf16 q/k/v through the ring: the f32 stats carry must keep the
        lax.switch branches dtype-stable (round-2 ADVICE: the kernel path
        emitted f32 lse while the causal skip branch returned bf16)."""
        from psana_ray_tpu.parallel import ring_flash_attention
        from psana_ray_tpu.parallel.ring_attention import reference_attention

        b, s, h, d = 2, 32, 4, 8
        mk = lambda: jnp.asarray(
            rng.normal(size=(b, s, h, d)).astype(np.float32)
        ).astype(jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        got = ring_flash_attention(q, k, v, seq_mesh, causal=True)
        assert got.dtype == jnp.bfloat16
        ref = reference_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32), np.asarray(ref), rtol=0.0, atol=3e-2
        )


class TestVendoredFlashKernel:
    """Interpret-mode equivalence of the vendored Pallas flash kernel
    (parallel/flash.py — replaces round 2's private
    ``fa._flash_attention_impl`` dependency) against the XLA statistics
    formulation, on the dtypes the serving path actually uses."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kernel_matches_xla_stats(self, rng, causal, dtype):
        from psana_ray_tpu.parallel.flash import (
            _pallas_attention_with_stats,
            _xla_attention_with_stats,
        )

        b, h, s, d = 2, 3, 256, 128
        mk = lambda: jnp.asarray(
            rng.normal(size=(b, h, s, d)).astype(np.float32) * 0.3
        ).astype(dtype)
        q, k, v = mk(), mk(), mk()
        o_ref, lse_ref = _xla_attention_with_stats(q, k, v, causal)
        o_pl, lse_pl = _pallas_attention_with_stats(q, k, v, causal, interpret=True)
        assert o_pl.dtype == dtype
        assert lse_pl.dtype == jnp.float32 and lse_ref.dtype == jnp.float32
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(o_pl, dtype=np.float32),
            np.asarray(o_ref, dtype=np.float32),
            rtol=0.0, atol=tol,
        )
        np.testing.assert_allclose(
            np.asarray(lse_pl), np.asarray(lse_ref), rtol=0.0, atol=1e-2
        )

    def test_uneven_kv_length(self, rng):
        from psana_ray_tpu.parallel.flash import (
            _pallas_attention_with_stats,
            _xla_attention_with_stats,
        )

        q = jnp.asarray(rng.normal(size=(1, 2, 128, 128)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 384, 128)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 2, 384, 128)).astype(np.float32))
        o_ref, lse_ref = _xla_attention_with_stats(q, k, v, False)
        o_pl, lse_pl = _pallas_attention_with_stats(q, k, v, False, interpret=True)
        np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref), atol=3e-5)
        np.testing.assert_allclose(np.asarray(lse_pl), np.asarray(lse_ref), atol=1e-3)

    @pytest.mark.parametrize("causal", [False, True])
    def test_stats_vjp_handles_lse_cotangent(self, rng, causal):
        """attention_with_stats' VJP must differentiate BOTH outputs —
        the lse cotangent folds into the backward's delta term. Oracle:
        plain autodiff of the XLA stats formulation (no custom_vjp)."""
        from psana_ray_tpu.parallel.flash import (
            _xla_attention_with_stats,
            attention_with_stats,
        )

        b, h, s, d = 1, 2, 8, 8
        mk = lambda: jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32)) * 0.4
        q, k, v = mk(), mk(), mk()
        wo = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
        wl = jnp.asarray(rng.normal(size=(b, h, s)).astype(np.float32))

        def loss(fn):
            def f(q, k, v):
                o, lse = fn(q, k, v, causal)
                # both outputs in the loss: a wrong/ignored lse cotangent
                # cannot hide
                return jnp.sum(o * wo) + jnp.sum(lse * wl)

            return f

        got = jax.grad(loss(attention_with_stats), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(_xla_attention_with_stats), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5)


class TestFlashBackward:
    """The flash VJP (tile-regenerated probabilities from saved lse):
    Pallas backward kernels in interpret mode vs the XLA backward from the
    same residuals, and the custom_vjp end-to-end vs autodiff of the
    reference formulation."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_pallas_bwd_matches_xla_bwd(self, rng, causal, dtype):
        from psana_ray_tpu.parallel.flash import (
            _pallas_attention_bwd,
            _xla_attention_bwd,
            _xla_attention_with_stats,
        )

        b, h, s, d = 2, 2, 256, 128
        mk = lambda: jnp.asarray(
            rng.normal(size=(b, h, s, d)).astype(np.float32) * 0.3
        ).astype(dtype)
        q, k, v = mk(), mk(), mk()
        o, lse = _xla_attention_with_stats(q, k, v, causal)
        do = mk()
        want = _xla_attention_bwd(q, k, v, o, lse, do, causal)
        got = _pallas_attention_bwd(q, k, v, o, lse, do, causal, interpret=True)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        for g, w, name in zip(got, want, ("dq", "dk", "dv")):
            assert g.dtype == dtype, name
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                rtol=0.0, atol=tol, err_msg=name,
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_bwd_dlse_matches_xla_bwd(self, rng, causal):
        """The lse-cotangent path (delta → delta − dlse) through the
        SAME backward kernels, interpret mode vs the XLA backward."""
        from psana_ray_tpu.parallel.flash import (
            _pallas_attention_bwd,
            _xla_attention_bwd,
            _xla_attention_with_stats,
        )

        b, h, s, d = 1, 2, 256, 128
        mk = lambda: jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32) * 0.3)
        q, k, v = mk(), mk(), mk()
        o, lse = _xla_attention_with_stats(q, k, v, causal)
        do = mk()
        dlse = jnp.asarray(rng.normal(size=(b, h, s)).astype(np.float32))
        want = _xla_attention_bwd(q, k, v, o, lse, do, causal, dlse=dlse)
        got = _pallas_attention_bwd(
            q, k, v, o, lse, do, causal, interpret=True, dlse=dlse
        )
        for g, w, name in zip(got, want, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=0.0, atol=1e-4, err_msg=name
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_attention_grad_matches_reference_autodiff(self, rng, causal):
        from psana_ray_tpu.parallel.flash import flash_attention

        b, s, h, d = 2, 64, 4, 16  # [B, S, H, D] repo layout; XLA paths on CPU
        mk = lambda: jnp.asarray(
            rng.normal(size=(b, s, h, d)).astype(np.float32) * 0.5
        )
        q, k, v = mk(), mk(), mk()

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5, err_msg=name
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_bwd_uneven_kv(self, rng, causal):
        # causal here exercises _tile_live tile-skipping where sk > sq:
        # key blocks entirely beyond every query row must contribute
        # exactly-zero dk/dv through the reset/finalize structure
        from psana_ray_tpu.parallel.flash import (
            _pallas_attention_bwd,
            _xla_attention_bwd,
            _xla_attention_with_stats,
        )

        q = jnp.asarray(rng.normal(size=(1, 2, 128, 128)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, 384, 128)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 2, 384, 128)).astype(np.float32))
        o, lse = _xla_attention_with_stats(q, k, v, causal)
        do = jnp.asarray(rng.normal(size=(1, 2, 128, 128)).astype(np.float32))
        want = _xla_attention_bwd(q, k, v, o, lse, do, causal)
        got = _pallas_attention_bwd(q, k, v, o, lse, do, causal, interpret=True)
        for g, w, name in zip(got, want, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=0.0, atol=1e-4, err_msg=name
            )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_flash_matches_reference_and_grads(seq_mesh, causal):
    q, k, v = _qkv(seed=3)
    want = np.asarray(reference_attention(q, k, v, causal=causal))
    qs, ks, vs = (_shard(x, seq_mesh) for x in (q, k, v))
    got = np.asarray(
        ulysses_attention(qs, ks, vs, seq_mesh, causal=causal, impl="flash")
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    # trainability: grads through the sharded flash path == reference grads
    def loss_flash(q, k, v):
        return jnp.sum(
            ulysses_attention(q, k, v, seq_mesh, causal=causal, impl="flash") ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    got_g = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(qs, ks, vs)
    want_g = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got_g, want_g, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5, err_msg=name
        )


class TestPickBlocks:
    """Block-shape selection invariants: picked blocks must divide the
    sequence lengths and respect both VMEM footprint caps."""

    def test_vit_serving_shape(self):
        from psana_ray_tpu.parallel.flash import _pick_blocks

        bq, bk = _pick_blocks(8448, 8448, 128)
        assert (bq, bk) == (384, 1408)  # measured near-plateau point

    @pytest.mark.parametrize("sq,sk,d", [
        (128, 128, 128), (512, 512, 128), (384, 1152, 128),
        (8448, 8448, 128), (256, 8192, 512), (128, 8192, 1024),
    ])
    def test_invariants(self, sq, sk, d):
        """Every shape the kernels ACCEPT (d <= _MAX_HEAD_DIM) satisfies
        the VMEM caps STRICTLY, forward and backward — the >=128 block
        floor can no longer void them because _kernel_shapes_ok routes
        larger head dims to the XLA fallback (ADVICE r4)."""
        from psana_ray_tpu.parallel.flash import (
            _MAX_KV_TILE_ELEMS, _MAX_TILE_ELEMS, _pick_blocks,
        )

        for backward, div in ((False, 1), (True, 2)):
            bq, bk = _pick_blocks(sq, sk, d, backward=backward)
            assert sq % bq == 0 and sk % bk == 0
            assert bq % 128 == 0 and bk % 128 == 0
            assert bq * bk <= _MAX_TILE_ELEMS // div
            assert bk * d <= _MAX_KV_TILE_ELEMS // div

    def test_large_head_dim_rejected(self):
        """d beyond _MAX_HEAD_DIM (where even a 128-wide block would blow
        the backward kv-tile cap) must not reach the kernel."""
        import jax.numpy as jnp

        from psana_ray_tpu.parallel.flash import (
            _MAX_HEAD_DIM, _kernel_shapes_ok,
        )

        ok = jnp.zeros((1, 1, 128, _MAX_HEAD_DIM), jnp.bfloat16)
        big = jnp.zeros((1, 1, 128, 2 * _MAX_HEAD_DIM), jnp.bfloat16)
        assert _kernel_shapes_ok(ok, ok)
        assert not _kernel_shapes_ok(big, big)
