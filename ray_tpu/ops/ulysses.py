"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all head/seq swap.

SURVEY.md §5.7 requires BOTH context-parallel schemes natively (the
reference has neither — its long-sequence story is delegated to vLLM /
DeepSpeed wrappers):

- ring attention (:mod:`ray_tpu.ops.ring_attention`): K/V rotate around
  the ``sp`` ring via ``ppermute``; communication is O(S·D) per step and
  overlaps with compute. Best when heads are few or already sharded.
- Ulysses (this module): two ``all_to_all`` collectives swap the sharded
  dimension — devices trade their sequence shard for a head shard, run
  ordinary FULL-sequence attention on their subset of heads, and swap
  back. Communication is 2 all-to-alls of the activations; attention
  itself is completely local, so any local kernel (XLA fused attention,
  Pallas flash) applies unchanged. Best when H is divisible by sp and the
  per-device full-sequence fits HBM.

TPU mapping: `jax.lax.all_to_all` over a mesh axis lowers to an ICI
all-to-all; on a torus this rides the same links as the ring but as one
fused transfer. Both schemes are selectable per-model
(``LlamaConfig.attention_impl``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str = "sp", causal: bool = True,
                      scale: Optional[float] = None) -> jax.Array:
    """Attention over a sequence sharded on ``axis_name``.

    Call inside shard_map with q/k/v per-device chunks
    [B, S_local, H|H_kv, D]. Requires H % sp == 0 (and H_kv % sp == 0, so
    grouped-query K/V are repeated up to H first when needed).
    """
    from ray_tpu.ops.attention import _repeat_kv, blockwise_attention

    sp = jax.lax.axis_size(axis_name)
    heads = q.shape[2]
    if sp == 1:
        k = _repeat_kv(k, heads)
        v = _repeat_kv(v, heads)
        return blockwise_attention(q, k, v, causal=causal, scale=scale)
    if heads % sp != 0:
        raise ValueError(
            f"ulysses needs n_heads ({heads}) divisible by sp ({sp}); "
            f"use attention_impl='ring' for this shape")
    if k.shape[2] % sp != 0:
        # Grouped-query KV with too few kv-heads for the swap: repeat only
        # up to lcm(H_kv, sp) — the contiguous q-to-kv group alignment is
        # preserved across the swap (device j's q heads map onto exactly
        # the kv heads it receives), and the remaining repeat up to H
        # happens locally after the swap, not on the wire.
        import math

        target = math.lcm(k.shape[2], sp)
        k = _repeat_kv(k, target)
        v = _repeat_kv(v, target)

    # [B, S/sp, H, D] -> (split heads, concat seq) -> [B, S, H/sp, D]
    swap = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                             split_axis=2, concat_axis=1, tiled=True)
    q_full = swap(q)
    k_full = swap(k)
    v_full = swap(v)
    k_full = _repeat_kv(k_full, q_full.shape[2])
    v_full = _repeat_kv(v_full, q_full.shape[2])
    out = blockwise_attention(q_full, k_full, v_full, causal=causal,
                              scale=scale)
    # [B, S, H/sp, D] -> (split seq, concat heads) -> [B, S/sp, H, D]
    return jax.lax.all_to_all(out, axis_name=axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


def ulysses_attention_sharded(q, k, v, mesh, *, axis_name: str = "sp",
                              causal: bool = True,
                              batch_axes=("dp", "fsdp"),
                              head_axis: str = "tp"):
    """Convenience wrapper: shard_map ulysses_attention over ``mesh``
    (mirror of ``ring_attention_sharded``)."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, axis_name, head_axis, None)
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                           causal=causal)
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)
    return wrapped(q, k, v)
