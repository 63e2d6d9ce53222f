"""Models: shapes, dtypes, padding-independence, losses."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from psana_ray_tpu.models import PeakNetUNet, ResNet18, ResNet50, panels_to_nhwc
from psana_ray_tpu.models.heads import nhwc_to_panels
from psana_ray_tpu.models.losses import masked_sigmoid_focal, masked_softmax_xent


class TestHeads:
    def test_panels_to_channels(self):
        x = jnp.arange(2 * 3 * 4 * 5.0).reshape(2, 3, 4, 5)
        y = panels_to_nhwc(x, "channels")
        assert y.shape == (2, 4, 5, 3)
        np.testing.assert_array_equal(np.asarray(y[0, :, :, 1]), np.asarray(x[0, 1]))

    def test_panels_to_batch_roundtrip(self):
        x = jnp.arange(2 * 3 * 4 * 5.0).reshape(2, 3, 4, 5)
        y = panels_to_nhwc(x, "batch")
        assert y.shape == (6, 4, 5, 1)
        np.testing.assert_array_equal(np.asarray(nhwc_to_panels(y, 3)), np.asarray(x))


class TestResNet:
    def test_resnet18_forward(self):
        model = ResNet18(num_classes=2, width=16)
        x = jnp.ones((2, 64, 64, 4))
        vars_ = model.init(jax.random.key(0), x)
        out = model.apply(vars_, x)
        assert out.shape == (2, 2)
        assert out.dtype == jnp.float32  # logits in f32

    def test_resnet50_param_count(self):
        # full-width ResNet-50: ~25.6M params in the torchvision layout;
        # ours differs (GroupNorm, SiLU, panel channels) but must be same
        # order: check the 4-stage bottleneck structure produced ~23-30M
        model = ResNet50(num_classes=2, width=64)
        vars_ = jax.eval_shape(
            model.init, jax.random.key(0), jnp.ones((1, 224, 224, 3), jnp.float32)
        )
        n = sum(np.prod(v.shape) for v in jax.tree.leaves(vars_))
        assert 20e6 < n < 32e6, f"param count {n/1e6:.1f}M out of ResNet-50 range"

    def test_rows_independent(self):
        # GroupNorm: padded rows must not change real rows' logits
        model = ResNet18(num_classes=2, width=16)
        real = jnp.asarray(np.random.default_rng(0).normal(size=(1, 64, 64, 4)), jnp.float32)
        vars_ = model.init(jax.random.key(0), jnp.zeros((2, 64, 64, 4)))
        alone = model.apply(vars_, real)
        padded = model.apply(vars_, jnp.concatenate([real, jnp.zeros_like(real)]))
        np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(padded[0]), atol=2e-2)


class TestUNet:
    def test_forward_shape(self):
        model = PeakNetUNet(features=(8, 16, 32), num_classes=1)
        x = jnp.ones((2, 64, 96, 1))
        vars_ = model.init(jax.random.key(0), x)
        out = model.apply(vars_, x)
        assert out.shape == (2, 64, 96, 1)
        assert out.dtype == jnp.float32

    def test_epix_panel_geometry(self):
        # epix10k2M panel 352x384 through depth-4 U-Net (divisible by 8)
        model = PeakNetUNet(features=(4, 8, 16, 32))
        x = jnp.ones((1, 352, 384, 1))
        out = model.apply(model.init(jax.random.key(0), x), x)
        assert out.shape == (1, 352, 384, 1)

    def test_panel_as_batch_path(self):
        frames = jnp.ones((2, 4, 32, 64))  # [B,P,H,W]
        x = panels_to_nhwc(frames, "batch")
        model = PeakNetUNet(features=(4, 8))
        out = model.apply(model.init(jax.random.key(0), x), x)
        masks = nhwc_to_panels(out, 4)
        assert masks.shape == (2, 4, 32, 64)


class TestLosses:
    def test_xent_ignores_padding(self):
        logits = jnp.asarray([[10.0, -10.0], [0.0, 0.0], [-5.0, 5.0]])
        labels = jnp.asarray([0, 1, 0])
        full = masked_softmax_xent(logits, labels, jnp.asarray([1, 1, 0]))
        sub = masked_softmax_xent(logits[:2], labels[:2], jnp.asarray([1, 1]))
        assert float(full) == pytest.approx(float(sub))

    def test_xent_all_padded_finite(self):
        out = masked_softmax_xent(jnp.ones((2, 3)), jnp.zeros((2,), jnp.int32), jnp.zeros((2,)))
        assert np.isfinite(float(out))

    def test_focal_downweights_easy(self):
        t = jnp.zeros((1, 8, 8, 1))
        easy = jnp.full((1, 8, 8, 1), -9.0)  # confident background
        hard = jnp.full((1, 8, 8, 1), 0.0)
        assert float(masked_sigmoid_focal(easy, t)) < float(masked_sigmoid_focal(hard, t))

    def test_focal_padding(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(3, 4, 4, 1)), jnp.float32)
        targets = jnp.asarray(rng.random((3, 4, 4, 1)) < 0.1, jnp.float32)
        full = masked_sigmoid_focal(logits, targets, jnp.asarray([1, 1, 0]))
        sub = masked_sigmoid_focal(logits[:2], targets[:2], jnp.asarray([1, 1]))
        assert float(full) == pytest.approx(float(sub), rel=1e-5)


class TestMergeBlockEquivalence:
    def test_split_weights_equal_concat_conv(self, rng):
        """conv_a(up) + conv_b(skip) must equal conv(concat([up, skip]))
        with the kernel stitched along its input-channel axis — the
        identity MergeBlock relies on to skip the concat copy."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from flax.core import meta

        from psana_ray_tpu.models.unet import MergeBlock

        f = 8
        up = jnp.asarray(rng.normal(size=(2, 8, 8, f)).astype(np.float32))
        skip = jnp.asarray(rng.normal(size=(2, 8, 8, f)).astype(np.float32))
        block = MergeBlock(features=f, dtype=jnp.float32, norm="frozen")
        variables = block.init(jax.random.key(0), up, skip)
        got = block.apply(variables, up, skip)

        p = meta.unbox(variables)["params"]
        k = jnp.concatenate(
            [p["merge_up"]["kernel"], p["merge_skip"]["kernel"]], axis=2
        )  # [3,3,2f,f]
        y = jax.lax.conv_general_dilated(
            jnp.concatenate([up, skip], axis=-1), k, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        aff0 = p["FrozenAffine_0"]
        y = y * aff0["scale"] + aff0["bias"]
        y = jax.nn.silu(y)
        y = jax.lax.conv_general_dilated(
            y, p["Conv_0"]["kernel"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        aff1 = p["FrozenAffine_1"]
        ref = jax.nn.silu(y * aff1["scale"] + aff1["bias"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_unet_frozen_norm_runs(self, rng):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from psana_ray_tpu.models import PeakNetUNet

        model = PeakNetUNet(features=(8, 16), norm="frozen")
        x = jnp.asarray(rng.normal(size=(2, 16, 16, 1)).astype(np.float32))
        v = model.init(jax.random.key(0), x)
        out = model.apply(v, x)
        assert out.shape == (2, 16, 16, 1)
        assert np.isfinite(np.asarray(out)).all()

    def test_upsample2x_matches_resize_nearest(self, rng):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from psana_ray_tpu.models.unet import _upsample2x

        x = jnp.asarray(rng.normal(size=(2, 5, 6, 3)).astype(np.float32))
        ref = jax.image.resize(x, (2, 10, 12, 3), "nearest")
        np.testing.assert_array_equal(np.asarray(_upsample2x(x)), np.asarray(ref))


class TestUNetTPU:
    """PeakNet-TPU (models/unet_tpu.py): the MXU-shaped redesign — s2d
    stem, wide features at half resolution, depth-to-space logit head."""

    def test_s2d_d2s_roundtrip(self):
        from psana_ray_tpu.models.unet_tpu import depth_to_space, space_to_depth

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 8, 12, 3)).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(depth_to_space(space_to_depth(x, 2), 2)), np.asarray(x)
        )

    def test_s2d_is_pixel_unshuffle(self):
        from psana_ray_tpu.models.unet_tpu import space_to_depth

        x = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
        p = space_to_depth(x, 2)
        assert p.shape == (1, 2, 2, 4)
        # packed channels are the 2x2 neighborhood of each output pixel
        np.testing.assert_array_equal(np.asarray(p[0, 0, 0]), [0, 1, 4, 5])
        np.testing.assert_array_equal(np.asarray(p[0, 1, 1]), [10, 11, 14, 15])

    def test_forward_shape_per_pixel_logits(self):
        from psana_ray_tpu.models import PeakNetUNetTPU

        model = PeakNetUNetTPU(features=(8, 16, 32), num_classes=1)
        x = jnp.ones((2, 32, 48, 1))
        out = model.apply(model.init(jax.random.key(0), x), x)
        assert out.shape == (2, 32, 48, 1)  # one logit per ORIGINAL pixel
        assert out.dtype == jnp.float32

    def test_epix_panel_geometry(self):
        from psana_ray_tpu.models import PeakNetUNetTPU

        model = PeakNetUNetTPU(features=(4, 8, 16, 32))
        x = jnp.ones((1, 352, 384, 1))  # 16 | 352, 16 | 384
        out = model.apply(model.init(jax.random.key(0), x), x)
        assert out.shape == (1, 352, 384, 1)

    def test_rejects_misaligned_extents(self):
        from psana_ray_tpu.models import PeakNetUNetTPU

        model = PeakNetUNetTPU(features=(8, 16))
        x = jnp.ones((1, 30, 32, 1))  # 30 % 4 != 0
        with pytest.raises(ValueError, match="divisible"):
            model.init(jax.random.key(0), x)

    def test_trainable_group_norm_grads(self):
        from psana_ray_tpu.models import PeakNetUNetTPU

        model = PeakNetUNetTPU(features=(8, 16), norm="group")
        x = jnp.ones((1, 16, 16, 1))
        variables = model.init(jax.random.key(0), x)

        def loss(v):
            return jnp.sum(model.apply(v, x) ** 2)

        g = jax.grad(loss)(variables)
        leaves = jax.tree.leaves(g)
        assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
        assert any(float(jnp.max(jnp.abs(l))) > 0 for l in leaves)

    def test_classic_unet_rejects_misaligned_extents(self):
        model = PeakNetUNet(features=(8, 16, 32))
        x = jnp.ones((1, 34, 32, 1))  # 34 % 4 != 0: fail loudly at the door
        with pytest.raises(ValueError, match="divisible"):
            model.init(jax.random.key(0), x)


class TestHostInit:
    """host_init (jitted init on the CPU backend) and eval_shape_init
    (the explicit no-trace numpy build bench.py's serving export uses)."""

    def test_eval_shape_init_matches_real_init_structure(self):
        from psana_ray_tpu.models.init import eval_shape_init

        model = ResNet18(num_classes=2, width=16, norm="frozen")
        shape = (1, 32, 32, 4)
        fake = eval_shape_init(model, shape)
        real = model.init(jax.random.key(0), jnp.zeros(shape))
        assert jax.tree_util.tree_structure(fake) == jax.tree_util.tree_structure(real)
        for (pf, lf), (pr, lr) in zip(
            jax.tree_util.tree_leaves_with_path(fake),
            jax.tree_util.tree_leaves_with_path(real),
        ):
            assert pf == pr
            assert lf.shape == lr.shape, pf
            assert np.dtype(lf.dtype) == np.dtype(lr.dtype), pf

    def test_eval_shape_init_forward_is_sane(self):
        # conventions (kernel ~ 1/sqrt(fan_in), scale=1, bias=0) must keep
        # activations O(1) through the full stack: finite, nonzero logits
        from psana_ray_tpu.models.init import eval_shape_init

        model = ResNet18(num_classes=2, width=16, norm="frozen")
        fake = eval_shape_init(model, (1, 32, 32, 4))
        out = model.apply(fake, jnp.ones((2, 32, 32, 4)))
        arr = np.asarray(out, np.float32)
        assert np.isfinite(arr).all()
        assert np.abs(arr).max() > 0
        assert np.abs(arr).max() < 1e3

    def test_eval_shape_init_naming_conventions_fire(self):
        # the leaf-name heuristic must see through flax's partitioning
        # boxes (paths end in GetAttrKey('value')): norm scales exactly 1,
        # biases exactly 0, conv kernels fan-in-scaled — NOT the generic
        # 0.02*randn else-branch for everything
        from flax.core import meta

        from psana_ray_tpu.models.init import eval_shape_init

        model = ResNet18(num_classes=2, width=16, norm="frozen")
        fake = meta.unbox(eval_shape_init(model, (1, 32, 32, 4)))["params"]
        stem_norm = fake["stem_norm"]
        np.testing.assert_array_equal(np.asarray(stem_norm["scale"]), 1.0)
        np.testing.assert_array_equal(np.asarray(stem_norm["bias"]), 0.0)
        k = np.asarray(fake["stem"]["kernel"], np.float32)
        fan_in = float(np.prod(k.shape[:-1]))
        assert 0.5 / np.sqrt(fan_in) < k.std() < 2.0 / np.sqrt(fan_in)

    def test_eval_shape_init_unet_frozen(self):
        from psana_ray_tpu.models import PeakNetUNetTPU
        from psana_ray_tpu.models.init import eval_shape_init

        model = PeakNetUNetTPU(features=(8, 16), norm="frozen")
        fake = eval_shape_init(model, (1, 16, 16, 1))
        out = model.apply(fake, jnp.ones((1, 16, 16, 1)))
        assert out.shape == (1, 16, 16, 1)
        assert np.isfinite(np.asarray(out, np.float32)).all()

    def test_host_init_prefers_cpu_backend_when_available(self):
        # host_init must be bit-identical to the model's own jitted init
        from psana_ray_tpu.models import host_init

        model = ResNet18(num_classes=2, width=16)
        shape = (1, 32, 32, 4)
        got = host_init(model, shape)
        want = jax.jit(model.init)(jax.random.key(0), jnp.zeros(shape))
        for (pg, lg), (pw, lw) in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree_util.tree_leaves_with_path(want),
        ):
            assert pg == pw
            np.testing.assert_array_equal(np.asarray(lg), np.asarray(lw))
