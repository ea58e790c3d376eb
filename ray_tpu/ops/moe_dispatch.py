"""Explicit capacity-bounded expert-parallel MoE dispatch (all-to-all).

SURVEY.md §2.3: EP is absent in the reference (vLLM internals handle it);
this is native design. Two selectable schemes in :class:`MoEModel`:

- ``einsum`` (models/moe.py): dense one-hot dispatch/combine einsums;
  XLA's SPMD partitioner turns the [T,E,C]x[T,D] contractions into
  collectives. Zero custom communication code, but the compiler chooses
  the schedule.
- ``alltoall`` (this module): GShard-style explicit dispatch inside
  shard_map — tokens are bucketed per expert with a hard capacity,
  buffers cross the ``ep`` axis as two `jax.lax.all_to_all` collectives
  (dispatch and return), and expert FFNs run exactly where their weights
  live. The communication volume is explicit and capacity-bounded:
  2 * E * C_local * D per device per layer, independent of routing skew.

Sharding contract (enforced by the shard_map specs): tokens arrive
sharded [batch -> (dp, fsdp), seq -> (sp, ep)], expert weights sharded
[E -> ep]. Expert FFN weights are NOT additionally tensor-parallel in
this path — use the einsum scheme when tp-sharded experts matter more
than explicit dispatch.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def topk_dispatch(xf, router, num_experts: int, top_k: int,
                   capacity: int, z_coef: float, lb_coef: float):
    """Shared router math: returns (dispatch [T,E,C] bool,
    combine [T,E,C] f32, aux scalar)."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    z_loss = jnp.mean(z ** 2) * z_coef
    me = jnp.mean(probs, axis=0)
    top1 = jnp.argmax(probs, axis=-1)
    ce = jnp.mean(jax.nn.one_hot(top1, num_experts), axis=0)
    aux = z_loss + lb_coef * num_experts * jnp.sum(me * ce)

    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    T = xf.shape[0]
    combine = jnp.zeros((T, num_experts, capacity), jnp.float32)
    dispatch = jnp.zeros((T, num_experts, capacity), jnp.bool_)
    # Slot positions must be unique per expert ACROSS the k passes:
    # choice-k tokens start after every earlier pass's assignments to the
    # same expert (GShard top-2 priority order), or two tokens land in
    # one slot and the expert sees their SUM.
    expert_count = jnp.zeros((num_experts,), jnp.float32)
    for j in range(top_k):
        onehot = jax.nn.one_hot(gate_idx[:, j], num_experts)
        pos_in_pass = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum((pos_in_pass + expert_count[None, :]) * onehot,
                      axis=-1)
        expert_count = expert_count + jnp.sum(onehot, axis=0)
        in_cap = pos < capacity
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity)
        slot = onehot[:, :, None] * pos_oh[:, None, :]
        slot = slot * in_cap[:, None, None]
        dispatch = dispatch | (slot > 0)
        combine = combine + slot * gate_vals[:, j][:, None, None]
    return dispatch, combine, aux


def expert_alltoall_ffn(h, router, e_gate, e_up, e_down, mesh, *,
                        num_experts: int, top_k: int,
                        capacity_factor: float, z_coef: float,
                        lb_coef: float, dtype,
                        axis_name: str = "ep") -> Tuple[jax.Array,
                                                        jax.Array]:
    """MoE FFN with explicit expert all-to-all over ``axis_name``.

    h: [B, S, D] (global, inside pjit). router: [D, E].
    e_gate/e_up: [E, D, F]; e_down: [E, F, D].
    Returns (out [B, S, D], aux [n_shards] — mean it for the loss).
    """
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape.get(axis_name, 1)

    def body(x, rtr, eg, eu, ed):
        # x: [B_l, S_l, D] local; eg/eu/ed: [E_l, D|F, F|D] local experts
        B_l, S_l, D = x.shape
        T_l = B_l * S_l
        C = max(1, int(capacity_factor * T_l * top_k / num_experts))
        xf = x.reshape(T_l, D)
        dispatch, combine, aux = topk_dispatch(
            xf, rtr, num_experts, top_k, C, z_coef, lb_coef)
        if ep > 1:
            aux = jax.lax.pmean(aux, axis_name)

        # bucket per GLOBAL expert: [E, C, D]
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dtype),
                               xf.astype(dtype))
        if ep > 1:
            # dispatch all-to-all: [E=ep*E_l, C, D] -> [E_l, ep*C, D]
            expert_in = jax.lax.all_to_all(
                expert_in, axis_name, split_axis=0, concat_axis=1,
                tiled=True)
        gate = jnp.einsum("ecd,edf->ecf", expert_in, eg.astype(dtype))
        up = jnp.einsum("ecd,edf->ecf", expert_in, eu.astype(dtype))
        act = jax.nn.silu(gate) * up
        out = jnp.einsum("ecf,efd->ecd", act, ed.astype(dtype))
        if ep > 1:
            # return all-to-all: [E_l, ep*C, D] -> [E, C, D]
            out = jax.lax.all_to_all(out, axis_name, split_axis=1,
                                     concat_axis=0, tiled=True)
        y = jnp.einsum("tec,ecd->td", combine.astype(dtype), out)
        return y.reshape(B_l, S_l, D), aux.reshape(1)

    present = set(mesh.shape.keys())
    batch_axes = tuple(a for a in ("dp", "fsdp") if a in present)
    seq_axes = tuple(a for a in ("sp", axis_name) if a in present)
    x_spec = P(batch_axes or None, seq_axes or None, None)
    w_spec = P(axis_name if axis_name in present else None, None, None)
    aux_spec = P(batch_axes + seq_axes or None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(x_spec, aux_spec), check_vma=False)
    return fn(h, router, e_gate, e_up, e_down)
