"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880 §4, on
Hyper-Connections, arXiv:2409.19606): a token's residual is ``n`` STREAMS
``X [n, C]`` and not one vector, and every sublayer (attention, FFN) reads
its input out of them and writes its output back through three maps that
are computed FROM the streams, a token a sublayer:

- ``stream_maps``: ``r = rsqrt(mean(vec(X)^2) + norm_eps)`` over all ``n
  C`` lanes; ``m = alpha * (r vec(X) Phi) + b`` with ``Phi`` [n C, 2n +
  n^2], ``alpha`` THREE scalars (one for each part of ``m = [m_pre (n) |
  m_post (n) | m_res (n^2)]``) and ``b`` [2n + n^2]; ``H_pre =
  sigmoid(m_pre)``, ``H_post = 2 sigmoid(m_post)``, ``M_0 =
  exp(clamp(mat(m_res)))`` and ``iters`` rounds of ``M <- M / (rowsum(M) +
  eps)``, ``M <- M / (colsum(M) + eps)`` (Sinkhorn-Knopp: after 20 the
  columns of ``H_res`` sum to 1 and its rows to within ~1e-6 of it, or
  ~1e-3 where it lies near a permutation, whose rounds converge slowly).
  Everything here is float32, the product with ``Phi`` at full float32
  passes as the router's logits are.
- ``mix_in``: the sublayer's input ``h = sum_i H_pre[i] X[i]``.
- ``mix_out``: ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``.

With ``n`` = 1 and unit maps these are ``x`` and ``x + y``: the plain
residual.

THE STREAMS' LAYOUT: a token's streams lie SIDE BY SIDE IN THE LANES, ``X``
[..., n C] with stream ``i`` in lanes ``[i C, (i + 1) C)``, and never as
[..., n, C]: a second-minor dimension of 4 is padded to a sublane tile on
the chip (16 rows in bf16: four times the bytes, in HBM and in every pass
over them). ``Phi`` is held TRANSPOSED, ``phi`` [2n + n^2, n C] (24 x
14,336 at the published widths: 24 sublanes of whole lane tiles, where
[14,336, 24] would pad every row of it to a lane tile).

NO KERNEL, and why (my chip runs, PR 50; PERF.md section 5): ONE Mosaic
kernel a sublayer boundary (``mix_out`` of one sublayer, then
``stream_maps`` and ``mix_in`` of the next: the streams read once and
written once, the maps' product on the VPU, the Sinkhorn rounds a loop
over sixteen per-token columns) was built, agreed with these functions on
the chip, and lost to them at both shapes that matter: in the decode
program of 32 slots 13.287 ms a step against 13.149 with XLA's fusions
(which read ``phi``'s slice in place where a kernel's operand is a copy of
it), and at a prefill chunk's 512 rows 0.311 ms a boundary against 0.075
(XLA at 62 % of the memory roof). So these are the whole of it, on every
platform.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _streams(X, n: int):
    """The n streams [..., C] of X [..., n C], as float32."""
    C = X.shape[-1] // n
    return [X[..., i * C:(i + 1) * C].astype(jnp.float32) for i in range(n)]


def stream_maps(X, n: int, phi, alpha, bias, *, iters: int, eps: float,
                norm_eps: float, clamp: Tuple[float, float]):
    """X [..., n C] -> ``(H_pre [..., n], H_post [..., n], H_res [..., n,
    n])``, float32 (module docstring). phi [2n + n^2, n C], alpha [3],
    bias [2n + n^2], float32."""
    lead = X.shape[:-1]
    f32 = jnp.float32
    v = X.astype(f32)
    r = jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + norm_eps)
    m = jnp.einsum("...k,mk->...m", v, phi.astype(f32),
                   precision=jax.lax.Precision.HIGHEST) * r
    m = m * jnp.repeat(alpha.astype(f32), jnp.asarray([n, n, n * n]),
                       total_repeat_length=2 * n + n * n) + bias.astype(f32)
    M = jnp.exp(jnp.clip(m[..., 2 * n:], *clamp)).reshape(*lead, n, n)
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)
    return (jax.nn.sigmoid(m[..., :n]),
            2.0 * jax.nn.sigmoid(m[..., n:2 * n]), M)


def mix_in(X, h_pre):
    """X [..., n C], H_pre [..., n] -> h [..., C] in X's dtype: a float32
    sum, stream 0 first."""
    h = sum(h_pre[..., i, None] * x
            for i, x in enumerate(_streams(X, h_pre.shape[-1])))
    return h.astype(X.dtype)


def mix_out(X, y, h_post, h_res):
    """X [..., n C], y [..., C], H_post [..., n], H_res [..., n, n] ->
    X' [..., n C] in X's dtype: float32 sums, ``y``'s term first and
    then stream 0, 1, ..."""
    n = h_post.shape[-1]
    xs, yf = _streams(X, n), y.astype(jnp.float32)
    rows = [h_post[..., i, None] * yf
            + sum(h_res[..., i, j, None] * xs[j] for j in range(n))
            for i in range(n)]
    return jnp.concatenate(rows, axis=-1).astype(X.dtype)


def sum_streams(X, n: int):
    """X [..., n C] -> the streams' sum [..., C] in X's dtype (how they
    leave the last layer), a float32 sum."""
    return sum(_streams(X, n)).astype(X.dtype)
