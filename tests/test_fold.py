"""Train→serve continuity: norm='batch' training form folds EXACTLY into
the norm='frozen' serving form (models/fold.py).

This is the supported route from a trained checkpoint to the parameter
form every fused serving kernel consumes — the capability the reference's
mission statement implies ("Stream psana data ... for ... inference",
reference ``project.toml:4``) but never builds.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from psana_ray_tpu.models import (
    PeakNetUNetTPU,
    ResNet18,
    fold_batchnorm,
)


def _train_mode_stats(model, x, steps=3, key=0):
    """Init a norm='batch' model and run a few train-mode passes so the
    running statistics move away from their (0, 1) init — the fold must
    be exact for NON-trivial stats."""
    variables = model.init(jax.random.key(key), x)
    for i in range(steps):
        xi = x + 0.3 * jax.random.normal(jax.random.key(100 + i), x.shape, x.dtype)
        _, mutated = model.apply(variables, xi, mutable=("batch_stats",))
        variables = {**variables, **mutated}
    return variables


class TestFoldResNet:
    def test_fold_matches_eval_batchnorm_exactly(self, rng):
        # f32 end to end so the only differences are op-ordering ulps
        train_model = ResNet18(num_classes=2, width=8, norm="batch", dtype=jnp.float32)
        x = jnp.asarray(rng.normal(size=(4, 32, 32, 2)).astype(np.float32))
        variables = _train_mode_stats(train_model, x)
        assert "batch_stats" in variables  # the form fold consumes

        eval_model = ResNet18(num_classes=2, width=8, norm="batch_eval", dtype=jnp.float32)
        ref = eval_model.apply(variables, x)

        folded = fold_batchnorm(variables)
        frozen_model = ResNet18(num_classes=2, width=8, norm="frozen", dtype=jnp.float32)
        got = frozen_model.apply(folded, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)

    def test_folded_tree_is_frozen_layout(self, rng):
        # the folded tree must be structurally identical to a norm='frozen'
        # init — that's what makes it consumable by the fused kernels'
        # _block_params extractors without any adaptation
        from flax.core import meta

        train_model = ResNet18(num_classes=2, width=8, norm="batch")
        x = jnp.zeros((2, 32, 32, 2))
        folded = fold_batchnorm(train_model.init(jax.random.key(0), x))
        frozen = meta.unbox(
            ResNet18(num_classes=2, width=8, norm="frozen").init(jax.random.key(0), x)
        )
        assert jax.tree_util.tree_structure(folded) == jax.tree_util.tree_structure(frozen)

    def test_fold_requires_batch_stats(self):
        with pytest.raises(ValueError, match="batch_stats"):
            fold_batchnorm({"params": {}})


class TestFoldPeakNetTPU:
    def test_fold_matches_eval_batchnorm_exactly(self, rng):
        features = (8, 16)
        train_model = PeakNetUNetTPU(features=features, norm="batch", dtype=jnp.float32)
        x = jnp.asarray(rng.normal(size=(2, 16, 32, 1)).astype(np.float32))
        variables = _train_mode_stats(train_model, x)

        eval_model = PeakNetUNetTPU(features=features, norm="batch_eval", dtype=jnp.float32)
        ref = eval_model.apply(variables, x)

        folded = fold_batchnorm(variables)
        frozen_model = PeakNetUNetTPU(features=features, norm="frozen", dtype=jnp.float32)
        got = frozen_model.apply(folded, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)


class TestBatchNormTraining:
    def test_train_step_updates_stats_and_params(self):
        import optax

        from psana_ray_tpu.parallel import create_mesh
        from psana_ray_tpu.parallel.steps import create_train_state, make_train_step

        model = PeakNetUNetTPU(features=(8, 16), norm="batch")
        mesh = create_mesh(("data", "model"), (jax.device_count(), 1))
        opt = optax.adam(1e-3)
        x = jnp.ones((2, 16, 16, 1))
        state = create_train_state(model, opt, jax.random.key(0), x, mesh)
        assert "batch_stats" in state.variables

        def loss_fn(logits, _aux):
            return jnp.mean(logits**2)

        step = make_train_step(model, opt, loss_fn, donate=False)
        before_stats = jax.tree.map(np.asarray, state.variables["batch_stats"])
        before_params = jax.tree.map(np.asarray, state.variables["params"])
        xb = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 16, 1)), jnp.float32)
        new_state, loss = step(state, xb, None)
        assert np.isfinite(float(loss))
        # running stats moved (mean update from a non-zero batch)...
        moved = jax.tree.map(
            lambda a, b: float(np.max(np.abs(np.asarray(a) - b))),
            new_state.variables["batch_stats"], before_stats,
        )
        assert max(jax.tree.leaves(moved)) > 0
        # ...and so did the params (gradients flowed to 'params' only)
        pmoved = jax.tree.map(
            lambda a, b: float(np.max(np.abs(np.asarray(a) - b))),
            new_state.variables["params"], before_params,
        )
        assert max(jax.tree.leaves(pmoved)) > 0

    def test_train_step_with_remat(self):
        import optax

        from psana_ray_tpu.parallel import create_mesh
        from psana_ray_tpu.parallel.steps import create_train_state, make_train_step

        model = PeakNetUNetTPU(features=(8, 16), norm="batch")
        mesh = create_mesh(("data", "model"), (jax.device_count(), 1))
        opt = optax.adam(1e-3)
        x = jnp.ones((2, 16, 16, 1))
        state = create_train_state(model, opt, jax.random.key(0), x, mesh)
        step = make_train_step(
            model, opt, lambda logits, _aux: jnp.mean(logits**2), donate=False,
            remat=True,
        )
        _, loss = step(state, x, None)
        assert np.isfinite(float(loss))


class TestExportRoundtrip:
    def test_export_serving_params_orbax_roundtrip(self, rng, tmp_path):
        from psana_ray_tpu.checkpoint import load_params
        from psana_ray_tpu.models import export_serving_params

        model = PeakNetUNetTPU(features=(8, 16), norm="batch", dtype=jnp.float32)
        x = jnp.asarray(rng.normal(size=(2, 16, 16, 1)).astype(np.float32))
        variables = _train_mode_stats(model, x)

        path = str(tmp_path / "serving")
        folded = export_serving_params(variables, path)
        restored = load_params(path)
        assert jax.tree_util.tree_structure(restored) == jax.tree_util.tree_structure(
            jax.tree.map(np.asarray, folded)
        )

        frozen = PeakNetUNetTPU(features=(8, 16), norm="frozen", dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(frozen.apply(restored, x)),
            np.asarray(frozen.apply(folded, x)),
            rtol=1e-6, atol=1e-6,
        )
