"""SPMD train step: the TPU-native replacement for DDP/FSDP wrappers.

Reference capability: Train v1 wraps torch DDP/FSDP (`train/torch/
train_loop_utils.py`, `train/torch/config.py:66` init_process_group). Here
sharded data parallelism IS the compiler's job: params get NamedShardings
from logical axes, batches shard over (dp, fsdp), and jit emits the
all-reduce / reduce-scatter / all-gather over ICI.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec


@dataclasses.dataclass
class TrainStep:
    """A compiled sharded train step plus its companion state tools."""

    step_fn: Callable          # (params, opt_state, batch) -> (p, o, metrics)
    init_fn: Callable          # (rng) -> (params, opt_state) [sharded]
    mesh: Any
    param_shardings: Any
    batch_sharding: Any


def make_train_step(model, optimizer: Optional[optax.GradientTransformation]
                    = None, mesh=None, *, donate: bool = True,
                    batch_axes=("dp", "fsdp")) -> TrainStep:
    """Build a jitted sharded train step for a model exposing
    ``init(rng)``, ``loss(params, *batch)`` and (optionally)
    ``param_shardings()``.

    With ``mesh=None`` runs single-device (bench path on one real chip).
    """
    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.1)

    if mesh is not None and hasattr(model, "param_shardings"):
        p_sh = model.param_shardings()
        batch_sh = NamedSharding(mesh, PartitionSpec(batch_axes))
    else:
        p_sh = batch_sh = None

    def init_fn(rng):
        params = model.init(rng)
        opt_state = optimizer.init(params)
        return params, opt_state

    def loss_fn(params, batch):
        return model.loss(params, *batch)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(updates=grads,
                                                  state=opt_state,
                                                  params=params)
            params = optax.apply_updates(params, updates)
            gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    if mesh is not None and p_sh is not None:
        # jit the whole init with sharded out_shardings so every leaf is
        # CREATED already sharded — a model that needs fsdp/tp sharding
        # must never materialize unsharded on one device.
        def sharded_init(rng):
            shapes = jax.eval_shape(init_fn, rng)
            o_sh = _mirror_shardings(shapes[1], shapes[0], p_sh, mesh)
            return jax.jit(init_fn, out_shardings=(p_sh, o_sh))(rng)

        step_fn = jax.jit(step, donate_argnums=(0, 1) if donate else ())
        return TrainStep(step_fn=step_fn, init_fn=sharded_init, mesh=mesh,
                         param_shardings=p_sh, batch_sharding=batch_sh)

    step_fn = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    return TrainStep(step_fn=step_fn, init_fn=jax.jit(init_fn), mesh=None,
                     param_shardings=None, batch_sharding=None)


def _mirror_shardings(opt_state, params, p_sh, mesh):
    """Give optimizer-state leaves the sharding of the param they mirror
    (same shape) or replicate them."""
    repl = NamedSharding(mesh, PartitionSpec())
    shape_to_sh = {}
    for p_leaf, sh in zip(jax.tree.leaves(params), jax.tree.leaves(p_sh)):
        shape_to_sh.setdefault(p_leaf.shape, sh)

    def pick(leaf):
        if hasattr(leaf, "shape") and leaf.shape in shape_to_sh:
            return shape_to_sh[leaf.shape]
        return repl
    return jax.tree.map(pick, opt_state)


def shard_batch(batch, train_step: TrainStep):
    """Place a host batch onto the mesh with (dp, fsdp) batch sharding."""
    if train_step.batch_sharding is None:
        return jax.device_put(batch)
    return jax.tree.map(
        lambda x: jax.device_put(x, train_step.batch_sharding), batch)
