"""Sharded init / inference / training steps over a mesh.

The bridge between mesh-agnostic flax models (models/) and the device mesh:
params are initialized directly into their mesh shardings (no host-side
giant pytree), inference and train steps are jit'd with explicit
in/out shardings, and gradient reduction across the data axis is implicit
in the shardings — XLA inserts the psums over ICI (scaling-book recipe:
annotate, don't hand-write collectives).

The reference has no counterpart (its consumers are opaque torch loops);
this is the "pjit'd model" half of the BASELINE north star.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax.core import meta as nn_meta
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from psana_ray_tpu.parallel.sharding import ShardingRules


def _mesh_shardings_for_variables(abstract_vars, mesh: Mesh, rules: ShardingRules):
    """Logical-axis metadata (nn.with_logical_partitioning) -> NamedShardings.
    Unannotated leaves replicate; rules naming a mesh axis the mesh lacks
    degrade to replication on that axis (ShardingRules.spec), so e.g. an
    'expert'-annotated MoE still initializes on a plain ('data','model')
    mesh."""
    logical = nn.get_partition_spec(abstract_vars)
    return jax.tree.map(
        lambda spec: rules.sharding(tuple(spec), mesh)
        if isinstance(spec, P)
        else NamedSharding(mesh, P()),
        logical,
        is_leaf=lambda x: isinstance(x, P),
    )


def init_sharded(
    model: nn.Module,
    rng: jax.Array,
    sample: jax.Array,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
):
    """Initialize variables directly into their mesh shardings.

    Returns an *unboxed* params pytree (plain arrays, each carrying its
    NamedSharding) — optax and checkpointing consume it directly."""
    rules = rules or ShardingRules()
    abstract = jax.eval_shape(model.init, rng, sample)
    shardings = _mesh_shardings_for_variables(abstract, mesh, rules)
    variables = jax.jit(model.init, out_shardings=shardings)(rng, sample)
    return nn_meta.unbox(variables)


def make_infer_step(model: nn.Module, mesh: Mesh, data_axis: str = "data"):
    """jit'd ``(variables, x) -> logits`` with batch rows over the data axis.

    Host arrays are ``device_put`` on the caller's thread each call — one
    synchronous full-frame H2D copy per batch. That is fine for scripts
    and tests; a streaming loop should feed pre-placed ``jax.Array``s
    (which pass through untouched) from the double-buffered prefetcher
    (``infeed.pipeline.DevicePrefetcher``) so transfers overlap compute."""
    x_sharding = NamedSharding(mesh, P(data_axis))

    @jax.jit
    def infer(variables, x):
        return model.apply(variables, x)

    def step(variables, x):
        return infer(variables, jax.device_put(x, x_sharding) if not isinstance(x, jax.Array) else x)

    return step


@dataclasses.dataclass
class TrainState:
    """Minimal train state (params + opt state + step counter)."""

    variables: Any
    opt_state: Any
    step: jax.Array


def make_train_step(
    model: nn.Module,
    optimizer: optax.GradientTransformation,
    loss_fn: Callable[..., jax.Array],
    donate: bool = True,
    remat: bool = False,
    aux_loss_weight: float = 0.0,
):
    """Build ``(state, batch) -> (state, loss)``.

    ``loss_fn(logits, batch) -> scalar``. Gradient reduction over the data
    axis happens inside jit via the sharding propagation (batch sharded on
    'data', params replicated/TP -> XLA inserts psum on the grads).
    ``donate=True`` donates the state buffers, so params update in place —
    essential at ResNet-50 scale on a 16 GB chip. ``remat=True`` wraps the
    forward in ``jax.checkpoint`` so the backward pass recomputes
    activations instead of storing them — the FLOPs-for-HBM trade that
    makes long-sequence / deep-model training fit on chip.

    ``aux_loss_weight>0`` runs the forward with the ``intermediates``
    collection mutable and adds ``weight · Σ`` of every sown ``aux_loss``
    to the objective — the MoE router's load-balancing term
    (:mod:`psana_ray_tpu.parallel.moe`). Intermediates are consumed here,
    never carried into the returned state."""

    def _step(state: TrainState, x: jax.Array, batch_aux) -> Tuple[TrainState, jax.Array]:
        # Gradients flow to the 'params' collection only. norm='batch'
        # models additionally carry running statistics in a mutable
        # 'batch_stats' collection (the train→serve export form,
        # models/fold.py): the updated stats ride back in the new state.
        # Stats are computed on the GLOBAL (sharded) batch inside jit —
        # XLA inserts the cross-device mean reductions, so multi-host
        # training needs no axis_name plumbing.
        params = state.variables["params"]
        other = {k: v for k, v in state.variables.items() if k != "params"}
        has_stats = "batch_stats" in other

        def fwd(p, x):
            variables = {**other, "params": p}
            mutable = (("batch_stats",) if has_stats else ()) + (
                ("intermediates",) if aux_loss_weight else ()
            )
            if mutable:
                return model.apply(variables, x, mutable=mutable)
            return model.apply(variables, x), {}

        if remat:
            fwd = jax.checkpoint(fwd)

        def loss_of(p):
            logits, mutated = fwd(p, x)
            loss = loss_fn(logits, batch_aux)
            if aux_loss_weight:
                from psana_ray_tpu.parallel.moe import total_aux_loss

                mutated = dict(mutated)
                loss = loss + aux_loss_weight * total_aux_loss(
                    mutated.pop("intermediates", {})
                )
            return loss, mutated

        (loss, mutated), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
        updates, opt_state = optimizer.update(
            {"params": grads}, state.opt_state, {"params": params}
        )
        params = optax.apply_updates({"params": params}, updates)["params"]
        variables = {**other, "params": params, **mutated}
        return TrainState(variables, opt_state, state.step + 1), loss

    return jax.jit(_step, donate_argnums=(0,) if donate else ())


def create_train_state(
    model: nn.Module,
    optimizer: optax.GradientTransformation,
    rng: jax.Array,
    sample: jax.Array,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
) -> TrainState:
    variables = init_sharded(model, rng, sample, mesh, rules)
    # Moment buffers inherit the param shardings; scalar leaves (e.g. adam's
    # count) must be explicitly replicated across the mesh — left on a
    # single device, the first train step after a checkpoint restore fails
    # with "incompatible devices" (restore preserves committed shardings).
    # Judged by the sharding's KIND, not its device count: on a one-device
    # mesh a leaf without a mesh sharding covers "every device" yet still
    # differs in type from what the step returns, and the second step
    # would retrace and compile the whole train program again.
    # Optimizer state covers the 'params' collection only (make_train_step
    # updates {'params': ...}); non-param collections like 'batch_stats'
    # are carried by the train step, not the optimizer.
    opt_state = jax.jit(optimizer.init)({"params": variables["params"]})
    replicated = NamedSharding(mesh, P())
    opt_state = jax.tree.map(
        lambda x: jax.device_put(x, replicated)
        if hasattr(x, "sharding") and not isinstance(x.sharding, NamedSharding)
        else x,
        opt_state,
    )
    step = jax.device_put(jnp.zeros((), jnp.int32), replicated)
    return TrainState(variables, opt_state, step)


jax.tree_util.register_dataclass(
    TrainState, data_fields=["variables", "opt_state", "step"], meta_fields=[]
)
