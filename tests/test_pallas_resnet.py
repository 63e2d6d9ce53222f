"""Equivalence tests for the fused Pallas ResNet inference path.

The fused kernels (models/pallas_resnet.py) must match the flax
``ResNetClassifier(norm='frozen')`` oracle to bfloat16 tolerance. On the
CPU test backend the kernels run in Pallas interpret mode — same math,
same masking/padding logic, no Mosaic lowering — which is the prescribed
way to unit-test TPU kernels off-hardware (pallas_guide).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from psana_ray_tpu.models.pallas_resnet import fused_bottleneck, resnet_fused_infer
from psana_ray_tpu.models.resnet import BottleneckBlock, ResNetClassifier


def _randomized(variables, key):
    """Perturb params so affine scales/biases are not init constants —
    otherwise scale=1/bias=0 would hide broadcast/transpose mistakes."""
    leaves, treedef = jax.tree.flatten(variables)
    keys = jax.random.split(key, len(leaves))
    out = [
        l + 0.1 * jax.random.normal(k, l.shape, l.dtype)
        if hasattr(l, "dtype") and l.dtype == jnp.float32
        else l
        for l, k in zip(leaves, keys)
    ]
    return jax.tree.unflatten(treedef, out)


def _rel_err(ref, got):
    """Max error normalized by the tensor's scale (elementwise relative
    error is meaningless on near-zero activations under bf16 rounding)."""
    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    return float(np.max(np.abs(ref - got)) / max(np.max(np.abs(ref)), 1e-3))


class TestFusedBottleneck:
    @pytest.mark.parametrize(
        "cin,f,stride,proj",
        [
            (64, 16, 1, False),   # identity block (cin == 4f)
            (32, 16, 1, True),    # projection, stride 1
            (64, 32, 2, True),    # projection + downsample
        ],
    )
    def test_matches_flax_block(self, rng, cin, f, stride, proj):
        self._check_block(rng, cin, f, stride, proj)

    @pytest.mark.parametrize(
        "cin,f,stride,proj",
        [
            (64, 16, 1, False),
            (64, 32, 2, True),
        ],
    )
    def test_split_path_matches_flax_block(self, rng, monkeypatch, cin, f, stride, proj):
        """Starve the VMEM budget so the block takes the two-kernel split
        path (front conv1+conv3x3 | back conv1x1+residual) — the route
        real stage-4 projection blocks compile through."""
        import psana_ray_tpu.models.pallas_resnet as pr

        monkeypatch.setattr(pr, "_VMEM_BUDGET", 1 << 20)
        self._check_block(rng, cin, f, stride, proj)

    def _check_block(self, rng, cin, f, stride, proj):
        h = w = 16
        block = BottleneckBlock(
            features=f, strides=(stride, stride), norm="frozen"
        )
        x = jnp.asarray(rng.normal(size=(2, h, w, cin)).astype(np.float32))
        variables = _randomized(block.init(jax.random.key(0), x), jax.random.key(1))
        assert ("proj" in variables["params"]) == proj
        ref = block.apply(variables, x)

        from flax.core import meta

        p = meta.unbox(variables)["params"]
        w1 = p["Conv_0"]["kernel"].astype(jnp.bfloat16).reshape(cin, f)
        w2 = p["Conv_1"]["kernel"].astype(jnp.bfloat16).reshape(9, f, f)
        w3 = p["Conv_2"]["kernel"].astype(jnp.bfloat16).reshape(f, 4 * f)
        aff = []
        for name in ("FrozenAffine_0", "FrozenAffine_1", "FrozenAffine_2"):
            ap = p[name]
            ch = ap["scale"].shape[0]
            aff += [
                ap["scale"].astype(jnp.float32).reshape(1, ch),
                ap["bias"].astype(jnp.float32).reshape(1, ch),
            ]
        wp = None
        if proj:
            wp = p["proj"]["kernel"].astype(jnp.bfloat16).reshape(cin, 4 * f)
            aff += [
                p["proj_norm"]["scale"].astype(jnp.float32).reshape(1, 4 * f),
                p["proj_norm"]["bias"].astype(jnp.float32).reshape(1, 4 * f),
            ]

        got = fused_bottleneck(
            x.astype(jnp.bfloat16), w1, w2, w3, tuple(aff), wp=wp,
            stride=stride, interpret=True,
        )
        assert got.shape == ref.shape
        assert _rel_err(ref, got) < 0.05  # bf16 taps + f32 accumulation

    def test_unaligned_width_padding_is_exact(self, rng):
        """w_true < padded buffer width: padded columns must stay zero and
        not leak into 3x3 taps or the residual."""
        cin, f, h, w_true = 64, 16, 16, 12  # buffer width padded to 16
        block = BottleneckBlock(features=f, strides=(1, 1), norm="frozen")
        x = jnp.asarray(rng.normal(size=(2, h, w_true, cin)).astype(np.float32))
        variables = _randomized(block.init(jax.random.key(0), x), jax.random.key(1))
        ref = block.apply(variables, x)

        from flax.core import meta

        from psana_ray_tpu.models.pallas_resnet import _block_params, _pad_to, _up

        w1, w2, w3, aff, wp = _block_params(meta.unbox(variables)["params"])
        xpad = _pad_to(x.astype(jnp.bfloat16), 2, _up(w_true, 8))
        got = fused_bottleneck(
            xpad, w1, w2, w3, aff, wp=wp, stride=1, w_true=w_true, interpret=True
        )
        assert _rel_err(ref, got[:, :, :w_true]) < 0.05
        np.testing.assert_array_equal(np.asarray(got[:, :, w_true:]), 0.0)


class TestResNetFusedInfer:
    def test_matches_flax_resnet(self, rng):
        stage_sizes = (1, 1)
        model = ResNetClassifier(
            stage_sizes=stage_sizes, num_classes=2, width=8, norm="frozen"
        )
        x = jnp.asarray(rng.normal(size=(2, 32, 32, 3)).astype(np.float32))
        variables = _randomized(
            model.init(jax.random.key(0), x), jax.random.key(1)
        )
        ref = model.apply(variables, x)
        got = resnet_fused_infer(variables, x, stage_sizes=stage_sizes, interpret=True)
        assert got.shape == ref.shape
        assert _rel_err(ref, got) < 0.05

    def test_unaligned_input_width(self, rng):
        """Input width whose post-stem extent is not a multiple of 8."""
        stage_sizes = (1, 1)
        model = ResNetClassifier(
            stage_sizes=stage_sizes, num_classes=2, width=8, norm="frozen"
        )
        x = jnp.asarray(rng.normal(size=(1, 48, 40, 2)).astype(np.float32))
        variables = _randomized(
            model.init(jax.random.key(0), x), jax.random.key(1)
        )
        ref = model.apply(variables, x)
        got = resnet_fused_infer(variables, x, stage_sizes=stage_sizes, interpret=True)
        assert _rel_err(ref, got) < 0.05


def test_small_extent_falls_back_to_flax():
    """Inputs too small for the fused stage pipeline (deep stages would
    degenerate to 0 rows) must run the plain flax forward, not crash in a
    kernel slice — the smoke geometry (16x128) hit exactly this."""
    from psana_ray_tpu.models import ResNet50, host_init, panels_to_nhwc
    from psana_ray_tpu.models.pallas_resnet import resnet_fused_infer

    model = ResNet50(num_classes=2, norm="frozen")
    v = host_init(model, (1, 16, 128, 2))
    x = jnp.ones((3, 2, 16, 128))  # [B, panels, H, W]
    out = resnet_fused_infer(v, panels_to_nhwc(x))
    ref = model.apply(v, panels_to_nhwc(x))
    assert out.shape == (3, 2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=1e-5
    )
