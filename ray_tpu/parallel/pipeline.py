"""Pipeline parallelism as a single SPMD program.

The reference expresses pipeline schedules as compiled actor DAGs with NCCL
channels (``dag/compiled_dag_node.py:809``, ``dag/collective_node.py``;
schedule construction ``dag/dag_node_operation.py``). On TPU the idiomatic
equivalent is radically simpler: the pipeline is a *single jitted SPMD
program* over a ``pp`` mesh axis — each device group holds one stage's
weights, microbatch activations rotate between neighbors with
``lax.ppermute`` (ICI neighbor exchange), and the whole schedule is a
``lax.scan``. Autodiff through the scan gives the backward pipeline schedule
for free; XLA overlaps the ppermute with compute.

Schedule: GPipe-style fill/drain — ``num_microbatches + num_stages - 1``
ticks. Device i computes stage i; at tick t stage 0 ingests microbatch t and
the last stage emits microbatch ``t - (num_stages-1)``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable,
                   local_params: Any,
                   microbatches: jnp.ndarray,
                   *,
                   axis_name: str = "pp",
                   num_stages: int,
                   num_microbatches: int) -> jnp.ndarray:
    """Run microbatches through the stage pipeline. Call INSIDE shard_map.

    Args:
      stage_fn: ``(params, x) -> y`` with ``y.shape == x.shape`` at stage
        boundaries (the transformer hidden-state contract).
      local_params: this device group's stage parameters (stage dim already
        stripped by shard_map).
      microbatches: ``[num_microbatches, ...]`` batch of stage-0 inputs,
        replicated over the pp axis.
      num_stages / num_microbatches: static schedule sizes.

    Returns:
      ``[num_microbatches, ...]`` outputs of the LAST stage, valid on every
      pp rank (broadcast at the end).
    """
    stage_idx = lax.axis_index(axis_name)
    n_ticks = num_microbatches + num_stages - 1
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    mb_shape = microbatches.shape[1:]
    act0 = jnp.zeros(mb_shape, microbatches.dtype)
    out0 = jnp.zeros((num_microbatches,) + mb_shape, microbatches.dtype)

    def tick(carry, t):
        act, outputs = carry
        mb_idx = jnp.clip(t, 0, num_microbatches - 1)
        fresh = lax.dynamic_index_in_dim(microbatches, mb_idx, 0,
                                         keepdims=False)
        x = jnp.where(stage_idx == 0, fresh, act)
        y = stage_fn(local_params, x)
        out_idx = t - (num_stages - 1)
        valid = jnp.logical_and(stage_idx == num_stages - 1, out_idx >= 0)
        oi = jnp.clip(out_idx, 0, num_microbatches - 1)
        prev = lax.dynamic_index_in_dim(outputs, oi, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, y, prev), oi, 0)
        act_next = lax.ppermute(y, axis_name, perm)
        return (act_next, outputs), None

    (_, outputs), _ = lax.scan(tick, (act0, out0), jnp.arange(n_ticks))
    # Broadcast the last stage's outputs to every pp rank so downstream
    # (loss, metrics) is uniform SPMD: psum of a one-hot-masked buffer.
    is_last = (stage_idx == num_stages - 1).astype(outputs.dtype)
    outputs = lax.psum(outputs * is_last, axis_name)
    return outputs


def pipelined(stage_fn: Callable,
              mesh: Mesh,
              *,
              num_microbatches: int,
              axis_name: str = "pp",
              param_specs: Optional[Any] = None,
              batch_axes: Tuple[str, ...] = ("dp", "fsdp")) -> Callable:
    """Wrap a stage function into a full-batch pipelined forward.

    Returns ``f(stacked_params, batch) -> outputs`` jittable over the mesh:
      - ``stacked_params``: pytree with a leading ``num_stages`` dim,
        sharded along ``pp``.
      - ``batch``: ``[global_batch, ...]`` sharded along the data axes;
        reshaped to microbatches internally.
      - ``param_specs``: optional pytree of ``PartitionSpec`` (leading dim
        must be the pp axis) so stage weights can ALSO shard over other
        axes (e.g. Megatron tp) — inside the shard_map the stage_fn sees
        its local shard and owns the matching collectives.
    """
    num_stages = mesh.shape[axis_name]

    def in_params_spec(leaf_ndim):
        return P(axis_name, *([None] * (leaf_ndim - 1)))

    def run(stacked_params, batch):
        def inner(params, mb):
            # shard_map gives params with a leading stage dim of size 1.
            local = jax.tree_util.tree_map(lambda p: p[0], params)
            return pipeline_apply(
                stage_fn, local, mb, axis_name=axis_name,
                num_stages=num_stages,
                num_microbatches=num_microbatches)

        p_specs = (param_specs if param_specs is not None
                   else jax.tree_util.tree_map(
                       lambda p: in_params_spec(p.ndim), stacked_params))
        # microbatch the (locally sharded) batch dim
        mb = batch.reshape((num_microbatches, -1) + batch.shape[1:])
        mb_spec = P(None, batch_axes, *([None] * (batch.ndim - 1)))
        out = jax.shard_map(
            inner, mesh=mesh, in_specs=(p_specs, mb_spec),
            out_specs=mb_spec, check_vma=False,
        )(stacked_params, mb)
        return out.reshape((-1,) + out.shape[2:])

    return run
