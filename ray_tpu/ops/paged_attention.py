"""Paged decode attention: one query token vs a block-pool KV cache.

Reference capability: vLLM's paged-attention kernel (the engine behind
`ray.llm`'s serving tier, outside the reference tree; config surface at
`python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py:126`).
TPU-native design:

- K/V live in a shared BLOCK POOL ``[num_blocks, block_size, Hkv, D]``;
  each slot's logical sequence is a list of physical block ids (the
  block table). Blocks are immutable once full, so identical prompt
  prefixes SHARE physical blocks (see ``llm/paged_cache.py``).
- ``paged_decode_attention`` — dispatcher (XLA gather fallback or the
  Pallas kernel).
- ``paged_decode_attention_pallas`` — flash-style online-softmax,
  grid (batch, logical_block). The block table and lengths ride scalar
  prefetch: the KV BlockSpec index map translates LOGICAL block ``kb``
  of slot ``b`` to PHYSICAL ``tables[b, kb]`` — the kernel never sees
  more than ``ceil(length/bs)`` blocks per slot, and no gather of the
  pool into a dense cache ever materializes.
- GQA stays grouped: the pool keeps Hkv heads and nothing is repeated,
  in HBM or in VMEM — each KV head's tile meets its own group of query
  rows in a 2-D dot.

Shapes: q [B, H, D]; k_pool/v_pool [NB, bs, Hkv, D];
block_tables [B, MAXB] int32 (physical ids; entries past a slot's
length are ignored); lengths [B] int32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu._private.platform import pallas_interpret
from ray_tpu.ops.attention import NEG_INF
from ray_tpu.ops.decode_attention import ragged_decode_attention_reference


def paged_decode_attention_reference(q, k_pool, v_pool, block_tables,
                                     lengths, *,
                                     scale: Optional[float] = None):
    """XLA fallback: gather the slot's blocks into a dense view, then
    run the masked ragged reference. One extra HBM round-trip of the
    active context vs the Pallas path — correct everywhere, slower."""
    B, maxb = block_tables.shape
    bs = k_pool.shape[1]
    k = k_pool[block_tables]                     # [B, MAXB, bs, Hkv, D]
    v = v_pool[block_tables]
    k = k.reshape(B, maxb * bs, *k.shape[3:])
    v = v.reshape(B, maxb * bs, *v.shape[3:])
    return ragged_decode_attention_reference(q, k, v, lengths, scale=scale)


def _paged_kernel(lens_ref, tables_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block_size: int, scale: float,
                  num_kb: int, kv_heads: int, head_dim: int):
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    kb = pl.program_id(1)
    length = lens_ref[b]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = kb * block_size

    @pl.when(start < length)
    def _compute():
        # One plain 2-D dot pair per KV head: Mosaic has no dot with a
        # batch dim and no free lhs dim (what "hd,khd->hk" asks for).
        # The head's G query rows sit on a leading ref dim and its K/V
        # columns are a static lane slice of the [bs, Hkv*D] tile, so
        # GQA needs no repeat and no in-kernel reshape. Operands stay in
        # the pool dtype (bf16 on the chip), accumulation is f32.
        for h in range(kv_heads):
            cols = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, h]                                # [G, D]
            k = k_ref[0, :, cols]                          # [bs, D]
            v = v_ref[0, :, cols]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # [G, bs]
            idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(idx < length, s, NEG_INF)
            m_prev = m_ref[h, :, :1]                       # [G, 1]
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                         # [G, bs]
            l_new = alpha * l_prev + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [G, D]
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(kb == num_kb - 1)
    def _finish():
        denom = l_ref[:, :, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables,
                                  lengths, *,
                                  scale: Optional[float] = None,
                                  interpret: bool = False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    NB, bs, Hkv, _ = k_pool.shape
    maxb = block_tables.shape[1]
    groups = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    def q_map(b, kb, lens, tables):
        return (b, 0, 0, 0)

    def kv_map(b, kb, lens, tables):
        # logical->physical translation; past-length logical blocks clamp
        # to the slot's last valid entry so the skipped iteration re-DMAs
        # one already-resident block at worst
        last_valid = jnp.maximum((lens[b] + bs - 1) // bs - 1, 0)
        return (tables[b, jnp.minimum(kb, last_valid)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, maxb),
        in_specs=[
            pl.BlockSpec((1, Hkv, groups, D), q_map),
            pl.BlockSpec((1, bs, Hkv * D), kv_map),
            pl.BlockSpec((1, bs, Hkv * D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hkv, groups, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, groups, 128), jnp.float32),
            pltpu.VMEM((Hkv, groups, 128), jnp.float32),
            pltpu.VMEM((Hkv, groups, D), jnp.float32),
        ],
    )
    # free (contiguous) views: q heads grouped by their KV head, and each
    # pool block as one 2-D [bs, Hkv*D] tile
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, scale=scale,
                          num_kb=maxb, kv_heads=Hkv, head_dim=D),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, groups, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, block_tables, q.reshape(B, Hkv, groups, D),
      k_pool.reshape(NB, bs, Hkv * D), v_pool.reshape(NB, bs, Hkv * D))
    return out.reshape(B, H, D)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           impl: str = "xla",
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    if impl == "pallas":
        if interpret is None:
            interpret = pallas_interpret()
        return paged_decode_attention_pallas(
            q, k_pool, v_pool, block_tables, lengths, scale=scale,
            interpret=interpret)
    return paged_decode_attention_reference(
        q, k_pool, v_pool, block_tables, lengths, scale=scale)
