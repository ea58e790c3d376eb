"""EVA attention (Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023, in the form EvaByte ships): an exact window that
RESETS, chunk summaries of everything before it, one softmax over both.

With ``W`` the window, ``c`` the chunk, ``s = D^-1/2`` and q, k already
turned by RoPE, per KV head ``h`` with two learned vectors ``phi_h``,
``mu_h`` [D]:

- chunk ``j`` holds positions ``[c*j, c*j + c)``; its SUMMARY is
  ``a = softmax_m(s * phi_h . k_m)``, ``k~_j = sum_m a_m k_m + mu_h``,
  ``v~_j = sum_m a_m v_m`` (``chunk_summaries``);
- a query at ``i`` lies in window ``w = i // W``. It sees exactly the
  keys ``m`` of its own window with ``m <= i`` (the window does not
  slide: it starts anew at every multiple of ``W``) and the summaries of
  every chunk of every EARLIER window, ``j < w*W/c``; one softmax over
  both (``eva_attention``, the dense masked form of the prefills and of
  training; the paged decode step walks two page lists and joins them
  with ``merge_softmax_parts``).

Everything here is plain XLA; the arithmetic is float32, operands and
results in the dtype they come in (the K/V pools': bf16 on the chip).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import NEG_INF, _repeat_kv


def chunk_summaries(k, v, phi, mu, chunk: int, scale: Optional[float] = None):
    """k, v [..., T, Hkv, D] (``T`` a multiple of ``chunk``, chunks cut
    from row 0), phi, mu [Hkv, D] -> ``(k~, v~)`` [..., T/chunk, Hkv, D]
    in k's and v's dtypes."""
    with jax.named_scope("eva_chunk_summary"):
        *lead, T, H, D = k.shape
        scale = scale if scale is not None else D ** -0.5
        kc = k.reshape(*lead, T // chunk, chunk, H, D).astype(jnp.float32)
        vc = v.reshape(*lead, T // chunk, chunk, H, D).astype(jnp.float32)
        phi, mu = phi.astype(jnp.float32), mu.astype(jnp.float32)
        a = jax.nn.softmax(
            jnp.einsum("...chd,hd->...ch", kc, phi) * scale, axis=-2)
        ks = jnp.einsum("...ch,...chd->...hd", a, kc) + mu
        vs = jnp.einsum("...ch,...chd->...hd", a, vc)
        return ks.astype(k.dtype), vs.astype(v.dtype)


def visible_summaries(positions, window: int, chunk: int):
    """How many summaries a query at each of ``positions`` sees: those
    of the windows before its own."""
    return (positions // window) * (window // chunk)


def eva_attention(q, k, v, ks, vs, positions_q, positions_k, index_s, *,
                  window: int, chunk: int, scale: Optional[float] = None):
    """The dense masked form. q [B, T, H, D]; k, v [B, S, Hkv, D] with
    ``positions_k`` [S] or [B, S] (a row to be dropped carries a
    position past every query's); ks, vs [B, Sc, Hkv, D] with
    ``index_s`` [Sc] or [B, Sc], each row's chunk index; ``positions_q``
    [T] or [B, T]. -> [B, T, H, D]."""
    with jax.named_scope("eva_attention"):
        H = q.shape[-2]
        scale = scale if scale is not None else q.shape[-1] ** -0.5
        k, v, ks, vs = (_repeat_kv(a, H) for a in (k, v, ks, vs))
        pq = positions_q[..., :, None]
        pk = positions_k[..., None, :]
        seen = (pk // window == pq // window) & (pk <= pq)
        seen_s = index_s[..., None, :] < visible_summaries(pq, window, chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s_s = jnp.einsum("bqhd,bkhd->bhqk", q, ks,
                         preferred_element_type=jnp.float32) * scale
        s = jnp.where(jnp.expand_dims(seen, -3), s, NEG_INF)
        s_s = jnp.where(jnp.expand_dims(seen_s, -3), s_s, NEG_INF)
        p = jax.nn.softmax(jnp.concatenate([s, s_s], axis=-1), axis=-1)
        S = k.shape[1]
        return (jnp.einsum("bhqk,bkhd->bqhd", p[..., :S].astype(v.dtype), v)
                + jnp.einsum("bhqk,bkhd->bqhd", p[..., S:].astype(vs.dtype),
                             vs))


def merge_softmax_parts(parts):
    """One softmax over several key sets, each attended alone: ``parts``
    is a list of ``(o, m, l)``, o [B, H, D] the part's own softmax
    output, m and l [B, H] its running max and its sum of ``exp(s -
    m)`` (0 and ``NEG_INF`` for an empty part). float32 -> float32."""
    m = parts[0][1]
    for _, m_i, _ in parts[1:]:
        m = jnp.maximum(m, m_i)
    weights = [l_i * jnp.exp(m_i - m) for _, m_i, l_i in parts]
    total = sum(weights)
    out = sum(o_i * w[..., None] for (o_i, _, _), w in zip(parts, weights))
    return jnp.where(total[..., None] > 0,
                     out / jnp.maximum(total, 1e-30)[..., None], 0.0)
