"""Sharded init/infer/train over the virtual 8-device mesh: params land in
their TP shardings, inference is batch-DP, training reduces grads across
the data axis and actually learns."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from psana_ray_tpu.models import PeakNetUNet, ResNet18
from psana_ray_tpu.models.losses import masked_sigmoid_focal, masked_softmax_xent
from psana_ray_tpu.parallel import ShardingRules, create_mesh
from psana_ray_tpu.parallel.mesh import local_batch_slice
from psana_ray_tpu.parallel.steps import (
    create_train_state,
    init_sharded,
    make_infer_step,
    make_train_step,
)


@pytest.fixture(scope="module")
def mesh():
    return create_mesh(("data", "model"), (4, 2))


class TestMeshBasics:
    def test_axis_inference(self):
        m = create_mesh(("data", "model"), (-1, 2))
        assert m.shape == {"data": 4, "model": 2}

    def test_bad_shape_rejected(self):
        with pytest.raises(ValueError):
            create_mesh(("data",), (3,))
        with pytest.raises(ValueError):
            create_mesh(("a", "b"), (-1, -1))

    def test_local_batch_slice_validates_data_axis(self, mesh):
        assert local_batch_slice(16, mesh) == 16  # single process
        with pytest.raises(ValueError, match="data axis"):
            local_batch_slice(6, mesh)  # 6 % 4 != 0


class TestShardingRules:
    def test_spec_degrades_missing_axes(self, mesh):
        rules = ShardingRules()
        # 'seq' axis not on this mesh -> replicated, not an error
        spec = rules.spec(("batch", "seq"), mesh)
        assert spec == P("data", None)

    def test_channels_out_to_model(self, mesh):
        spec = ShardingRules().spec(("height", "width", "channels_in", "channels_out"), mesh)
        assert spec == P(None, None, None, "model")


class TestShardedInitAndInfer:
    def test_params_are_tp_sharded(self, mesh):
        model = ResNet18(num_classes=2, width=32)
        sample = jnp.ones((8, 32, 32, 4))
        variables = init_sharded(model, jax.random.key(0), sample, mesh)
        # find a conv kernel and check its output-channel axis is split
        kernel = variables["params"]["stem"]["kernel"]
        spec = kernel.sharding.spec
        assert spec[-1] == "model", f"stem kernel spec {spec}"
        # each shard holds half the output channels
        shard = next(iter(kernel.addressable_shards)).data
        assert shard.shape[-1] == kernel.shape[-1] // 2

    def test_infer_matches_unsharded(self, mesh):
        # float32 so sharded-vs-host differences are pure reduction-order
        # noise (bf16 would add ~1e-2 scatter and mask real bugs)
        model = ResNet18(num_classes=3, width=16, dtype=jnp.float32)
        sample = jnp.ones((8, 32, 32, 2))
        variables = init_sharded(model, jax.random.key(1), sample, mesh)
        step = make_infer_step(model, mesh)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 32, 32, 2)), jnp.float32)
        sharded_out = np.asarray(step(variables, x))
        # same params gathered to host, plain apply
        host_vars = jax.tree.map(np.asarray, variables)
        plain_out = np.asarray(model.apply(host_vars, x))
        np.testing.assert_allclose(sharded_out, plain_out, atol=1e-4)


class TestShardedTraining:
    def test_resnet_loss_decreases(self, mesh):
        model = ResNet18(num_classes=2, width=16)
        sample = jnp.ones((8, 32, 32, 1))
        opt = optax.adam(1e-3)
        state = create_train_state(model, opt, jax.random.key(0), sample, mesh)

        rng = np.random.default_rng(0)
        # learnable rule: class = 1 if mean intensity > 0
        x = rng.normal(size=(8, 32, 32, 1)).astype(np.float32)
        x[:4] += 0.8
        labels = jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0])
        valid = jnp.ones((8,), jnp.uint8)
        xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data")))

        step = make_train_step(
            model, opt, lambda logits, aux: masked_softmax_xent(logits, aux[0], aux[1])
        )
        losses = []
        for _ in range(12):
            state, loss = step(state, xs, (labels, valid))
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.7, f"no learning: {losses}"
        assert int(state.step) == 12

    def test_unet_train_step_runs(self, mesh):
        model = PeakNetUNet(features=(4, 8), num_classes=1)
        sample = jnp.ones((8, 16, 32, 1))
        opt = optax.sgd(1e-2)
        state = create_train_state(model, opt, jax.random.key(0), sample, mesh)
        x = jax.device_put(sample, NamedSharding(mesh, P("data")))
        targets = jnp.zeros((8, 16, 32, 1))
        step = make_train_step(
            model, opt, lambda logits, aux: masked_sigmoid_focal(logits, aux[0], aux[1])
        )
        state, loss = step(state, x, (targets, jnp.ones((8,))))
        assert np.isfinite(float(loss))


def test_train_step_remat_matches_plain(mesh):
    """jax.checkpoint must change memory, not math: one remat step equals
    one plain step bit-for-bit given identical init."""
    import numpy as np
    import optax

    from psana_ray_tpu.models import ResNet18, panels_to_nhwc
    from psana_ray_tpu.models.losses import masked_softmax_xent
    from psana_ray_tpu.parallel.steps import create_train_state, make_train_step

    model = ResNet18(num_classes=2, width=16)
    frames = jnp.asarray(
        np.random.default_rng(0).normal(size=(8, 2, 16, 16)).astype(np.float32)
    )
    x = panels_to_nhwc(frames)
    labels = jnp.asarray(np.arange(8) % 2)
    valid = jnp.ones((8,), jnp.uint8)
    opt = optax.sgd(1e-2)
    loss_fn = lambda logits, aux: masked_softmax_xent(logits, aux[0], aux[1])  # noqa: E731

    out = {}
    for name, use_remat in (("plain", False), ("remat", True)):
        state = create_train_state(model, opt, jax.random.key(0), x, mesh)
        step = make_train_step(model, opt, loss_fn, donate=False, remat=use_remat)
        state, loss = step(state, x, (labels, valid))
        out[name] = (float(loss), state)
    assert out["plain"][0] == out["remat"][0]
    flat_p = jax.tree.leaves(out["plain"][1].variables)
    flat_r = jax.tree.leaves(out["remat"][1].variables)
    for a, b in zip(flat_p, flat_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unet_tpu_train_step_runs(mesh):
    """The MXU-shaped PeakNet-TPU (models/unet_tpu.py) must be trainable
    with the same sharded train-step machinery as the classic model —
    GroupNorm form, focal segmentation loss, batch sharded P('data')."""
    import optax

    from psana_ray_tpu.models import PeakNetUNetTPU

    model = PeakNetUNetTPU(features=(4, 8), num_classes=1, norm="group")
    sample = jnp.ones((8, 16, 32, 1))
    opt = optax.sgd(1e-2)
    state = create_train_state(model, opt, jax.random.key(0), sample, mesh)
    x = jax.device_put(sample, NamedSharding(mesh, P("data")))
    targets = jnp.zeros((8, 16, 32, 1))
    step = make_train_step(
        model, opt, lambda logits, aux: masked_sigmoid_focal(logits, aux[0], aux[1])
    )
    state, loss = step(state, x, (targets, jnp.ones((8,))))
    assert np.isfinite(float(loss))
    assert int(state.step) == 1


def test_train_step_compiles_once_on_one_device(caplog):
    """The state ``create_train_state`` builds must already have the types
    the step returns: a second step that retraces recompiles the whole
    train program (33 s of PeakNet-TPU on one v5e chip, where adam's
    count started without a mesh sharding because the mesh had one
    device)."""
    import logging

    mesh = create_mesh(("data", "model"), (1, 1), devices=jax.devices()[:1])
    model = ResNet18(num_classes=2, width=8)
    x = jax.device_put(jnp.ones((4, 16, 16, 2)), NamedSharding(mesh, P("data")))
    opt = optax.adamw(1e-3)
    state = create_train_state(model, opt, jax.random.key(0), x, mesh)
    step = make_train_step(
        model, opt, lambda logits, aux: masked_softmax_xent(logits, aux[0], aux[1])
    )
    aux = (jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.uint8))
    with jax.log_compiles(), caplog.at_level(logging.WARNING, logger="jax"):
        for _ in range(3):
            state, _ = step(state, x, aux)
    compiles = [
        r for r in caplog.records
        if "Finished XLA compilation of jit(_step)" in r.getMessage()
    ]
    assert len(compiles) == 1
