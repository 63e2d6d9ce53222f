"""Parameter initialization off the accelerator.

:func:`host_init` jits ``model.init`` on the CPU backend — which stock
JAX registers beside the accelerator — and moves the finished tree to the
target device in one ``device_put``: bit-identical to the model's own
initializers, and the accelerator never compiles an init graph it runs
once.

:func:`eval_shape_init` is the explicit zero-device-work alternative: a
numpy tree shaped by ``jax.eval_shape`` with magnitudes by flax leaf
naming conventions — ``kernel`` → fan-in-scaled normal, ``scale``/``var``
→ ones, ``bias``/``mean`` → zeros.  It does not reproduce flax's exact
initializer distributions; it reproduces their *statistics*, which is
what throughput benchmarks need (activations stay O(1) through
arbitrarily deep stacks, logits finite).  Nothing selects it silently: a
caller that wants it calls it by name.
"""

from __future__ import annotations

from typing import Any, Sequence

import numpy as np


def _leaf_name(path) -> str:
    """Parameter name for a key path: the LAST dict key in it.

    Boxed params (flax ``LogicallyPartitioned`` from ``with_partitioning``)
    append a ``GetAttrKey(name='value')`` entry after the real name, so
    ``path[-1]`` would be ``'value'`` for every leaf — walk backwards to
    the last DictKey instead."""
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return str(entry.key)
    return str(path[-1]) if path else ""


def host_init(
    model,
    sample_shape: Sequence[int],
    sample_dtype=None,
    seed: int = 0,
    device=None,
    method=None,
):
    """Initialize ``model`` variables on the CPU backend and return the
    pytree resident on ``device`` (default: ``jax.devices()[0]``).

    ``sample_shape``/``sample_dtype`` describe the model input.
    """
    import jax
    import jax.numpy as jnp

    if sample_dtype is None:
        sample_dtype = jnp.float32
    if device is None:
        device = jax.devices()[0]
    init_fn = model.init if method is None else method
    with jax.default_device(jax.devices("cpu")[0]):
        variables = jax.jit(init_fn)(
            jax.random.key(seed), jnp.zeros(tuple(sample_shape), sample_dtype)
        )
    return jax.device_put(variables, device)


def eval_shape_init(
    model,
    sample_shape: Sequence[int],
    sample_dtype=None,
    seed: int = 0,
    method=None,
):
    """Numpy arrays shaped by ``jax.eval_shape(model.init, ...)``,
    magnitudes by flax leaf naming conventions (module docstring): no
    init graph is traced, compiled or run on any backend."""
    import jax
    import jax.numpy as jnp

    if sample_dtype is None:
        sample_dtype = jnp.float32
    rngkey = jax.random.key(seed)
    init_fn = model.init if method is None else method

    shapes = jax.eval_shape(
        init_fn, rngkey, jax.ShapeDtypeStruct(tuple(sample_shape), sample_dtype)
    )
    rng = np.random.default_rng(seed)

    def build(path, sd):
        name = _leaf_name(path).lower()
        shape = tuple(sd.shape)
        dtype = np.dtype(sd.dtype)
        if "scale" in name or "var" in name:
            arr = np.ones(shape, dtype)
        elif "bias" in name or "mean" in name:
            arr = np.zeros(shape, dtype)
        elif "kernel" in name or "embedding" in name:
            fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
            arr = (rng.standard_normal(shape) / np.sqrt(max(fan_in, 1))).astype(dtype)
        else:
            arr = (0.02 * rng.standard_normal(shape)).astype(dtype)
        return arr

    return jax.tree_util.tree_map_with_path(build, shapes)
