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


def _upsample_then_conv(x, k):
    """The decoder's line as it was: nearest 2x upsample, 3x3 SAME."""
    up = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return jax.lax.conv_general_dilated(
        up, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


_UPCONV_EXTENTS = [(2, 2), (3, 5), (22, 24)]
_UPCONV_CHANNELS = [(1, 1), (8, 4), (16, 16)]

# every leaf of PeakNetUNetTPU(features=(8, 16, 32), s2d=2, norm='frozen'),
# written out: checkpoints, benchmark/reference/peaknet.py, sfx.infer_s2d
# and infer_features go by these paths
_TPU_FROZEN_TREE = {
    "ConvBlock_0/Conv_0/kernel": (3, 3, 4, 8), "ConvBlock_0/Conv_1/kernel": (3, 3, 8, 8),
    "ConvBlock_0/FrozenAffine_0/bias": (8,), "ConvBlock_0/FrozenAffine_0/scale": (8,),
    "ConvBlock_0/FrozenAffine_1/bias": (8,), "ConvBlock_0/FrozenAffine_1/scale": (8,),
    "ConvBlock_1/Conv_0/kernel": (3, 3, 8, 16), "ConvBlock_1/Conv_1/kernel": (3, 3, 16, 16),
    "ConvBlock_1/FrozenAffine_0/bias": (16,), "ConvBlock_1/FrozenAffine_0/scale": (16,),
    "ConvBlock_1/FrozenAffine_1/bias": (16,), "ConvBlock_1/FrozenAffine_1/scale": (16,),
    "ConvBlock_2/Conv_0/kernel": (3, 3, 16, 32), "ConvBlock_2/Conv_1/kernel": (3, 3, 32, 32),
    "ConvBlock_2/FrozenAffine_0/bias": (32,), "ConvBlock_2/FrozenAffine_0/scale": (32,),
    "ConvBlock_2/FrozenAffine_1/bias": (32,), "ConvBlock_2/FrozenAffine_1/scale": (32,),
    "Conv_0/kernel": (3, 3, 8, 8), "Conv_1/kernel": (3, 3, 16, 16),
    "Conv_2/kernel": (3, 3, 32, 16), "Conv_3/kernel": (3, 3, 16, 8),
    "MergeBlock_0/Conv_0/kernel": (3, 3, 16, 16),
    "MergeBlock_0/FrozenAffine_0/bias": (16,), "MergeBlock_0/FrozenAffine_0/scale": (16,),
    "MergeBlock_0/FrozenAffine_1/bias": (16,), "MergeBlock_0/FrozenAffine_1/scale": (16,),
    "MergeBlock_0/merge_skip/kernel": (3, 3, 16, 16), "MergeBlock_0/merge_up/kernel": (3, 3, 16, 16),
    "MergeBlock_1/Conv_0/kernel": (3, 3, 8, 8),
    "MergeBlock_1/FrozenAffine_0/bias": (8,), "MergeBlock_1/FrozenAffine_0/scale": (8,),
    "MergeBlock_1/FrozenAffine_1/bias": (8,), "MergeBlock_1/FrozenAffine_1/scale": (8,),
    "MergeBlock_1/merge_skip/kernel": (3, 3, 8, 8), "MergeBlock_1/merge_up/kernel": (3, 3, 8, 8),
    "logits/bias": (4,), "logits/kernel": (1, 1, 8, 4),
}


def _group_tree(frozen_tree, stem_in, head_out):
    """The same model with ``norm='group'``: GroupNorm_<n> where the
    frozen tree has FrozenAffine_<n>; the classic model has no s2d, so
    its stem reads ``stem_in`` channels and its head emits ``head_out``."""
    tree = {k.replace("FrozenAffine", "GroupNorm"): v for k, v in frozen_tree.items()}
    tree["ConvBlock_0/Conv_0/kernel"] = (3, 3, stem_in, 8)
    tree["logits/bias"], tree["logits/kernel"] = (head_out,), (1, 1, 8, head_out)
    return tree


def _leaf_shapes(variables):
    from flax.core import meta

    flat = jax.tree_util.tree_leaves_with_path(meta.unbox(variables)["params"])
    return {"/".join(k.key for k in path): tuple(leaf.shape) for path, leaf in flat}


class TestUpConv2x:
    """models/unet.upconv2x: the decoder's upsample + 3x3 convolution on
    the low-resolution map, from the same [3,3,Cin,Cout] kernel."""

    @staticmethod
    def _operands(rng, extent, channels):
        (h, w), (cin, cout) = extent, channels
        x = jnp.asarray(rng.normal(size=(2, h, w, cin)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(3, 3, cin, cout)).astype(np.float32))
        return x, k

    @pytest.mark.parametrize("channels", _UPCONV_CHANNELS)
    @pytest.mark.parametrize("extent", _UPCONV_EXTENTS)
    def test_equals_upsample_then_conv(self, rng, extent, channels):
        from psana_ray_tpu.models.unet import upconv2x

        x, k = self._operands(rng, extent, channels)
        want = np.asarray(_upsample_then_conv(x, k))
        got = np.asarray(upconv2x(x, k))
        assert got.shape == want.shape == (2, 2 * extent[0], 2 * extent[1], channels[1])
        # every output pixel, the border among them: float32 summation
        # order and nothing else (a wrong border tap reads ~1 RMS)
        assert np.abs(got - want).max() <= 1e-5 * np.sqrt((want ** 2).mean())

    @pytest.mark.parametrize("channels", _UPCONV_CHANNELS)
    @pytest.mark.parametrize("extent", _UPCONV_EXTENTS)
    def test_gradients_equal_upsample_then_conv(self, rng, extent, channels):
        from psana_ray_tpu.models.unet import upconv2x

        x, k = self._operands(rng, extent, channels)
        ct = jnp.asarray(
            rng.normal(size=(2, 2 * extent[0], 2 * extent[1], channels[1])).astype(np.float32)
        )
        want = jax.grad(lambda x, k: jnp.sum(_upsample_then_conv(x, k) * ct), (0, 1))(x, k)
        got = jax.grad(lambda x, k: jnp.sum(upconv2x(x, k) * ct), (0, 1))(x, k)
        for g, w_ in zip(got, want):
            w_ = np.asarray(w_)
            assert np.abs(np.asarray(g) - w_).max() <= 1e-5 * np.sqrt((w_ ** 2).mean())

    def test_bf16_taps_are_summed_in_float32(self, rng):
        """Compute dtype bf16: the summed taps are rounded ONCE, from
        their float32 sum (not a sum of rounded taps)."""
        from psana_ray_tpu.models.unet import _fold_taps

        k = jnp.asarray(rng.normal(size=(3, 3, 2, 2)).astype(np.float32))
        k4 = np.asarray(_fold_taps(_fold_taps(k, 0), 1))
        assert k4.shape == (4, 4, 2, 2) and k4.dtype == np.float32
        kn = np.asarray(k)
        np.testing.assert_array_equal(k4[0, 0], kn[0, 0])
        np.testing.assert_array_equal(k4[3, 3], kn[2, 2])
        np.testing.assert_allclose(
            k4[1, 2], kn[0, 1] + kn[0, 2] + kn[1, 1] + kn[1, 2], rtol=1e-6
        )

    @pytest.mark.parametrize("model_name", ["tpu_frozen", "tpu_group", "classic_group"])
    def test_parameter_tree_is_written_out(self, model_name):
        """The tree UpConv2x leaves behind is the one nn.Conv left:
        every path and shape, Conv_2 and Conv_3 (the up-convolutions,
        [3,3,Cin,Cout]) among them."""
        from psana_ray_tpu.models import PeakNetUNetTPU

        model, shape, want = {
            "tpu_frozen": (PeakNetUNetTPU(features=(8, 16, 32), norm="frozen"),
                           (1, 16, 16, 1), _TPU_FROZEN_TREE),
            "tpu_group": (PeakNetUNetTPU(features=(8, 16, 32), norm="group"),
                          (1, 16, 16, 1), _group_tree(_TPU_FROZEN_TREE, 4, 4)),
            "classic_group": (PeakNetUNet(features=(8, 16, 32), norm="group"),
                              (1, 8, 8, 1), _group_tree(_TPU_FROZEN_TREE, 1, 1)),
        }[model_name]
        assert _leaf_shapes(model.init(jax.random.key(0), jnp.zeros(shape))) == want

    def test_reference_forward_agrees_on_the_tree(self, rng):
        """benchmark/reference/peaknet.py reads the tree by path
        (params['Conv_<levels-1+j>']) and upsamples then convolves: it
        agrees with the model in float32."""
        from flax.core import meta

        from benchmark.reference import peaknet as reference
        from psana_ray_tpu.models import PeakNetUNetTPU

        model = PeakNetUNetTPU(features=(8, 16, 32), norm="frozen", dtype=jnp.float32)
        x = jnp.asarray(rng.normal(size=(2, 32, 48, 1)).astype(np.float32))
        variables = meta.unbox(model.init(jax.random.key(1), x))
        got = np.asarray(model.apply(variables, x))
        want = np.asarray(reference.forward(variables["params"], x, 2))
        assert np.abs(got - want).max() <= 1e-4 * np.sqrt((want ** 2).mean())

    @pytest.mark.parametrize("model_name", ["tpu", "classic"])
    def test_no_upsampled_tensor_in_the_program(self, model_name):
        """The old form's mark: a 6-D broadcast [N,H,2,W,2,C]."""
        import re

        from psana_ray_tpu.models import PeakNetUNetTPU

        model = {"tpu": PeakNetUNetTPU(features=(8, 16, 32)),
                 "classic": PeakNetUNet(features=(8, 16, 32))}[model_name]
        x = jnp.zeros((1, 16, 16, 1))
        text = jax.jit(model.apply).lower(model.init(jax.random.key(0), x), x).as_text()
        six_d = [ln for ln in text.splitlines() if "broadcast_in_dim" in ln
                 and re.search(r"-> tensor<(\d+x){6}", ln)]
        assert "conv" in text and not six_d


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
    (the explicit no-trace numpy build)."""

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
