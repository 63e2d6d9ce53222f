"""Pipeline parallelism: GPipe microbatch schedule over a 'pipe' mesh axis.

The reference has no model code, hence no pipeline parallelism beyond the
macro produce→queue→consume pipe (SURVEY.md §2 "Parallelism strategies");
the task spec makes PP a first-class sharding for the TPU build. This is
the TPU-idiomatic realization: no per-stage processes, no send/recv
threads — ONE SPMD program over a ``pipe`` mesh axis where

- stage parameters are stacked along a leading axis sharded
  ``P('pipe')`` (each device physically holds only its own stage);
- the microbatch schedule is a ``lax.scan`` over ``M + S - 1`` ticks;
- activations hop stage→stage with ``lax.ppermute`` — neighbor ICI
  traffic, overlapped with the next tick's compute by XLA;
- the bubble is the standard GPipe ``(S-1)/(M+S-1)`` and shrinks as the
  microbatch count grows.

Because every collective here (``ppermute``, the final masked ``psum``)
has a registered transpose, ``jax.grad`` THROUGH :func:`pipeline_apply`
yields the reverse pipeline schedule automatically — the backward pass
runs the same scan in reverse with cotangents hopping the ring the other
way. One definition, forward and backward pipelining both real.

Composition: the batch dim may simultaneously be sharded over a ``data``
axis (DP×PP) — each data-group runs an independent pipeline. TP inside a
stage composes the same way (stage params additionally sharded on
``model``), giving the full DP×PP×TP layout on a 3-axis mesh.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    pipe_axis: str = "pipe",
    microbatches: Optional[int] = None,
    data_axis: Optional[str] = None,
) -> jax.Array:
    """Run ``x`` through ``S`` pipeline stages with GPipe microbatching.

    ``stage_fn(params_slice, x_mb) -> y_mb`` applies ONE stage; output
    shape must equal input shape (true of transformer blocks — the hop
    buffer that rides the ring is shape-uniform). ``stacked_params`` is a
    pytree whose leaves carry a leading stage axis of size
    ``S = mesh.shape[pipe_axis]``; under jit they should be sharded
    ``P(pipe_axis)`` so each device materializes only its stage.

    ``x`` is the global batch ``[B, ...]`` with ``B`` divisible by
    ``microbatches`` (default ``S``, the smallest count that fills the
    pipeline). The result is ``stage_S(...stage_1(x))``, replicated over
    ``pipe_axis`` (a masked ``psum`` fans the last stage's outputs back
    out — activations-sized, the price of returning a mesh-global value).
    """
    n_stages = mesh.shape[pipe_axis]
    m = microbatches or n_stages
    b_local = x.shape[0] // (mesh.shape[data_axis] if data_axis else 1)
    if b_local % m:
        raise ValueError(
            f"per-data-group batch {b_local} not divisible by microbatches={m} "
            f"(each data group runs its own pipeline over its local rows)"
        )

    def local(params, x):
        # params: leaves [1, ...] (this device's stage slice); x: [B_local, ...]
        params = jax.tree.map(lambda p: p[0], params)
        idx = lax.axis_index(pipe_axis)
        mb = x.shape[0] // m
        xs = x.reshape(m, mb, *x.shape[1:])
        hop = jnp.zeros((mb, *x.shape[1:]), x.dtype)  # activation arriving on the ring
        outs = jnp.zeros_like(xs)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            hop, outs = carry
            # stage 0 feeds microbatch t (clipped reads past the end are
            # bubble work whose result is never written or hopped onward
            # into anything real)
            x_t = lax.dynamic_index_in_dim(xs, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            y = stage_fn(params, jnp.where(idx == 0, x_t, hop))
            # last stage finishes microbatch t-(S-1) at tick t
            o = jnp.clip(t - (n_stages - 1), 0, m - 1)
            cur = lax.dynamic_index_in_dim(outs, o, 0, keepdims=False)
            write = jnp.logical_and(idx == n_stages - 1, t >= n_stages - 1)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, y, cur), o, 0
            )
            return (lax.ppermute(y, pipe_axis, perm), outs), None

        (_, outs), _ = lax.scan(tick, (hop, outs), jnp.arange(m + n_stages - 1))
        # only the last stage holds real outputs; masked psum replicates them
        outs = lax.psum(jnp.where(idx == n_stages - 1, outs, 0), pipe_axis)
        return outs.reshape(x.shape)

    param_spec = jax.tree.map(lambda _: P(pipe_axis), stacked_params)
    x_spec = P(data_axis)
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(param_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stacked_params, x)


def stack_stages(stacked_depth_params: Any, n_stages: int) -> Any:
    """Regroup depth-stacked params ``[D, ...] -> [S, D/S, ...]``.

    Flax's ``nn.scan`` trunk (``models.vit.ViTHitClassifier(scan_trunk=
    True)``) produces one leading ``depth`` axis; pipeline stages each own
    ``D/S`` consecutive blocks, so the stage axis is the outer factor."""

    def regroup(p):
        d = p.shape[0]
        if d % n_stages:
            raise ValueError(f"depth {d} not divisible by {n_stages} stages")
        return p.reshape(n_stages, d // n_stages, *p.shape[1:])

    return jax.tree.map(regroup, stacked_depth_params)
