"""Attention: reference JAX implementation + Pallas TPU flash kernel.

Reference capability: the reference repo delegates attention to vLLM /
flash-attn CUDA kernels (outside its tree). Here it is in-tree and
TPU-native:

- ``attention``      — dispatcher; GQA-aware, causal, autodiff-friendly.
- ``flash_attention``— Pallas online-softmax kernel (HBM→VMEM tiled,
  MXU matmuls, O(S) memory). Forward kernel + recompute-based VJP.

Shapes follow the JAX convention [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu._private.platform import on_chip, pallas_interpret

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """GQA: repeat kv heads to match q heads. [B,S,Hkv,D] -> [B,S,H,D]."""
    num_kv = k.shape[-2]
    if num_kv == num_q_heads:
        return k
    return jnp.repeat(k, num_q_heads // num_kv, axis=-2)


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        positions_q: Optional[jax.Array] = None,
                        positions_k: Optional[jax.Array] = None,
                        scale: Optional[float] = None,
                        window=None) -> jax.Array:
    """Plain softmax attention in f32; XLA fuses this well on TPU for
    moderate sequence lengths and it is fully differentiable.

    A query sees a key iff its position is >= the key's. Positions are
    one vector for the batch ([T] / [S]) or PER ROW ([B, T] / [B, S]):
    the serving prefills' masked attention over a slot cache or a
    gathered prefix, where every row has its own offset (a key to be
    dropped carries a position past every query's). ``window`` (an int,
    it may be traced) is a sliding-window layer's: a query at ``i``
    sees a key at ``j`` only if ``i - j < window`` besides."""
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    k = _repeat_kv(k, q.shape[-2])
    v = _repeat_kv(v, q.shape[-2])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        if positions_q is None:
            positions_q = jnp.arange(q.shape[1])
        if positions_k is None:
            positions_k = jnp.arange(k.shape[1])
        mask = positions_q[..., :, None] >= positions_k[..., None, :]
        if window is not None:
            mask &= (positions_q[..., :, None] - positions_k[..., None, :]
                     < window)
        # [T, S] or [B, T, S] -> broadcast over heads
        s = jnp.where(jnp.expand_dims(mask, -3), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_k: int = 512,
                        scale: Optional[float] = None) -> jax.Array:
    """Memory-efficient differentiable attention: online-softmax scan over
    key chunks with a rematerialized body, so both forward AND backward are
    O(S·block_k) memory instead of O(S²). This is the training path for
    long sequences (and the flash kernel's VJP)."""
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    k = _repeat_kv(k, q.shape[-2])
    v = _repeat_kv(v, q.shape[-2])
    seq_k = k.shape[1]
    bk = min(block_k, seq_k)
    if seq_k % bk != 0:  # pad keys; padding masked out below
        pad = bk - seq_k % bk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = k.shape[1] // bk
    rows = jnp.arange(q.shape[1])
    batch, seq_q, heads, _ = q.shape

    # [nk, B, bk, H, D] chunks scanned as the leading axis.
    kc = k.reshape(batch, nk, bk, heads, head_dim).swapaxes(0, 1)
    vc = v.reshape(batch, nk, bk, heads, head_dim).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, chunk):
        acc, m, l = carry
        ki, kb, vb = chunk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                       preferred_element_type=jnp.float32) * scale
        cols = ki * bk + jnp.arange(bk)
        mask = cols[None, :] < seq_k
        if causal:
            mask = mask & (rows[:, None] >= cols[None, :])
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_c = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_c)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, vb.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        acc = acc * jnp.swapaxes(alpha, 1, 2) + a
        return (acc, m_new, l), None

    acc = jnp.zeros((batch, seq_q, heads, head_dim), jnp.float32)
    m = jnp.full((batch, heads, seq_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((batch, heads, seq_q, 1), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc, m, l), (jnp.arange(nk), kc, vc))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / jnp.swapaxes(l, 1, 2)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash-attention forward kernel
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      scale: float, block_q: int, block_k: int, causal: bool,
                      num_k_blocks: int, seq_k: int):
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Fully-masked blocks (k strictly above the causal diagonal) are skipped.
    should_run = True
    if causal:
        should_run = ki * block_k < (qi + 1) * block_q

    @pl.when(should_run)
    def _compute():
        # feed the MXU native dtypes (bf16 in, f32 accumulate) — no
        # explicit f32 casts of the operands
        q = q_ref[0]                               # [bq, D]
        k = k_ref[0]                               # [bk, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, bk]
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = cols < seq_k  # tail block: don't attend to padding keys
        # Zero padded V rows: their p weights are exp(NEG_INF)≈0, but
        # 0 * <uninitialized> is NaN when the pad is NaN (interpret mode),
        # and garbage-dependent on hardware — make the product exact 0.
        kvalid = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0) < seq_k
        v = jnp.where(kvalid, v, 0)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = mask & (rows >= cols)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]                      # [bq, 1]
        l_prev = l_ref[:, :1]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def _flash_forward(q, k, v, *, causal: bool, block_q: int, block_k: int,
                   interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, num_heads, head_dim = q.shape
    seq_k = k.shape[1]
    num_kv = k.shape[2]
    group = num_heads // num_kv
    scale = head_dim ** -0.5

    bq = min(block_q, seq_q)
    bk = min(block_k, seq_k)
    nq = pl.cdiv(seq_q, bq)
    nk = pl.cdiv(seq_k, bk)

    # Layout [B*H, S, D]: one grid row per (batch, head) pair.
    qt = q.transpose(0, 2, 1, 3).reshape(batch * num_heads, seq_q, head_dim)
    kt = k.transpose(0, 2, 1, 3).reshape(batch * num_kv, seq_k, head_dim)
    vt = v.transpose(0, 2, 1, 3).reshape(batch * num_kv, seq_k, head_dim)

    def kv_index(bh, qi, ki):
        return (bh // num_heads) * num_kv + (bh % num_heads) // group, ki, 0

    out = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, block_q=bq,
                          block_k=bk, causal=causal, num_k_blocks=nk,
                          seq_k=seq_k),
        grid=(batch * num_heads, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, head_dim), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, head_dim), kv_index),
            pl.BlockSpec((1, bk, head_dim), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, head_dim),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((batch * num_heads, seq_q, head_dim),
                                       q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, head_dim), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(batch, num_heads, seq_q, head_dim).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """Pallas TPU flash attention. O(S) memory forward; backward recomputes
    blockwise (remat scan), so training memory stays O(S·block) too."""
    if interpret is None:
        interpret = pallas_interpret()
    return _flash_forward(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    if interpret is None:
        interpret = pallas_interpret()
    out = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                         block_k=block_k, interpret=interpret)
    return out, (q, k, v)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(q_, k_, v_, causal=causal),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              positions_q: Optional[jax.Array] = None,
              positions_k: Optional[jax.Array] = None,
              use_flash: Optional[bool] = None) -> jax.Array:
    """Dispatcher: Pallas flash kernel on TPU when shapes tile cleanly,
    reference otherwise. Explicit position vectors force the reference path
    (the kernel assumes contiguous 0..S-1 positions)."""
    if use_flash is None:
        use_flash = (on_chip() and positions_q is None and positions_k is None
                     and q.shape[-1] % 128 == 0 and q.shape[1] >= 128)
    if use_flash:
        return flash_attention(q, k, v, causal)
    return reference_attention(q, k, v, causal=causal,
                               positions_q=positions_q,
                               positions_k=positions_k)
