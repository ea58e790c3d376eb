"""Ring attention: context/sequence parallelism over an ICI mesh axis.

SURVEY.md §5.7: the reference has NO sequence parallelism — this is native
new design. Each device on the ``sp`` axis holds a contiguous sequence chunk
of q/k/v. K/V chunks rotate around the ring via ``jax.lax.ppermute``
(neighbor exchange rides the shortest ICI links) while each device
accumulates online-softmax partial results for its local queries —
blockwise attention with O(S/sp) memory per device and compute/communication
overlap left to XLA's latency-hiding scheduler.

Usage: call inside ``shard_map`` (or via ``ring_attention_sharded`` which
wraps itself) with q/k/v already sharded on the sequence dim.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import NEG_INF, _repeat_kv


def _block_attn(q, k, v, q_offset, k_offset, scale, causal):
    """One blockwise step: returns (unnormalized acc [B,S,H,D] f32,
    row-max m, row-sum l with shapes [B,H,S,1])."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        rows = q_offset + jnp.arange(q.shape[1])
        cols = k_offset + jnp.arange(k.shape[1])
        mask = rows[:, None] >= cols[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    # NB: no stop_gradient on m — alpha/beta in the combine step also
    # differentiate through m and autodiff relies on the cancellation.
    m = jnp.max(s, axis=-1, keepdims=True)                    # [B,H,Sq,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return acc, m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Attention over a sequence sharded on ``axis_name``.

    Must be called inside shard_map/pjit-SPMD context where ``axis_name``
    is bound. q/k/v: per-device chunks [B, S_local, H|Hkv, D].
    """
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    k = _repeat_kv(k, q.shape[-2])
    v = _repeat_kv(v, q.shape[-2])

    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    chunk = q.shape[1]
    q_offset = idx * chunk

    batch, _, heads, _ = q.shape

    def body(step, carry):
        acc, m, l, kc, vc = carry
        # The kv chunk currently held arrived from device (idx - step) % sp.
        k_offset = ((idx - step) % sp) * chunk
        a, m_c, l_c = _block_attn(q, kc, vc, q_offset, k_offset, scale,
                                  causal)
        m_new = jnp.maximum(m, m_c)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_c - m_new)
        l = alpha * l + beta * l_c
        # acc is [B,S,H,D]; alpha/beta are [B,H,S,1] -> transpose to match.
        alpha_t = jnp.swapaxes(alpha, 1, 2)
        beta_t = jnp.swapaxes(beta, 1, 2)
        acc = acc * alpha_t + a * beta_t
        m = m_new
        # Rotate kv to the next ring neighbor (ICI nearest-neighbor).
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return acc, m, l, kc, vc

    acc = jnp.zeros(q.shape[:3] + (head_dim,), jnp.float32)
    m = jnp.full((batch, heads, chunk, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((batch, heads, chunk, 1), jnp.float32)
    if sp == 1:
        acc, m, l, _, _ = body(0, (acc, m, l, k, v))
    else:
        acc, m, l, _, _ = jax.lax.fori_loop(
            0, sp, body, (acc, m, l, k, v))
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / jnp.swapaxes(l, 1, 2)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, *, axis_name: str = "sp",
                           causal: bool = True,
                           batch_axes=("dp", "fsdp"), head_axis: str = "tp"):
    """Convenience wrapper: shard_map ring_attention over ``mesh``."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, axis_name, head_axis, None)
    ring = functools.partial(ring_attention, axis_name=axis_name,
                             causal=causal)
    fn = jax.shard_map(ring, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
