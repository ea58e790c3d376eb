"""Rotary position embeddings (RoPE), Llama-3 style, with YaRN scaling.

Frequencies are precomputed once per model (static shapes keep the table out
of the jit trace); application is pure elementwise VPU work that XLA fuses
into the attention projections.

A model whose layer kinds turn by different frequencies (window layers by
the default ones, full layers by YaRN's) keeps the inverse frequencies a
kind, ``[kinds, head_dim//2]``, and computes the angles from the positions
(``apply_rope_of_kind``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN (Peng et al., arXiv:2309.00071) as the published configs
    state it (``rope_type: yarn``): the context is stretched by
    ``factor`` beyond ``original_max_position``; lanes that turn more
    than ``beta_fast`` times over the original context keep their
    frequency, lanes that turn fewer than ``beta_slow`` times are
    slowed by ``factor``, and a linear ramp joins the two. cos and sin
    are both multiplied by ``attention_factor`` (``None``: the paper's
    ``0.1 ln(factor) + 1``), which scales q.k by its square."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    @property
    def cos_sin_scale(self) -> float:
        if self.attention_factor is not None:
            return float(self.attention_factor)
        return 0.1 * math.log(self.factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float,
                  yarn: Optional[YarnScaling]) -> jax.Array:
    """[head_dim//2] inverse frequencies. Without ``yarn`` lane ``i``
    turns ``theta**(-2i/head_dim)`` a position; with it, that where the
    lane makes at least ``beta_fast`` turns over the original context,
    that over ``factor`` where it makes at most ``beta_slow``, and in
    between the two mixed by a ramp that is linear in ``i``."""
    half = head_dim // 2
    base = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))
    if yarn is None:
        return base

    def lane_of(turns: float) -> float:
        # the (real-valued) lane that makes ``turns`` turns over the
        # original context
        return (head_dim * math.log(yarn.original_max_position
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(lane_of(yarn.beta_fast)), 0)
    high = min(math.ceil(lane_of(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001            # a step, not a division by zero
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return base * (1.0 - ramp) + base / yarn.factor * ramp


def rope_frequencies(head_dim: int, max_seq_len: int, *,
                     theta: float = 500_000.0,
                     scaling_factor: Optional[float] = None,
                     yarn: Optional[YarnScaling] = None) -> jax.Array:
    """[max_seq_len, head_dim//2] complex-free cos/sin basis angles."""
    inv_freq = yarn_inv_freq(head_dim, theta, yarn)
    pos = jnp.arange(max_seq_len, dtype=jnp.float32)
    if scaling_factor is not None:
        pos = pos / scaling_factor
    return jnp.outer(pos, inv_freq)  # [S, D/2]


def _rotate(x: jax.Array, ang: jax.Array, scale=None) -> jax.Array:
    """x [..., S, H, D] turned by ``ang`` [..., S, 1, D/2]; cos and sin
    times ``scale`` where one is given."""
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale is not None:
        with jax.named_scope("rope_yarn"):
            cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope(x: jax.Array, angles: jax.Array,
               positions: Optional[jax.Array] = None) -> jax.Array:
    """Rotate q or k. x: [..., S, H, D]; angles: [max_S, D/2].

    positions: optional [.., S] int32 absolute positions (for sequence-
    parallel shards and decode steps); defaults to 0..S-1.
    """
    seq_len = x.shape[-3]
    if positions is None:
        ang = angles[:seq_len]                      # [S, D/2]
        ang = ang[None, :, None, :]                 # [1, S, 1, D/2]
    else:
        ang = angles[positions]                     # [..., S, D/2]
        ang = ang[..., :, None, :]                  # [..., S, 1, D/2]
    return _rotate(x, ang)


def apply_rope_of_kind(x: jax.Array, inv_freq: jax.Array, scales: jax.Array,
                       kind, positions: Optional[jax.Array] = None
                       ) -> jax.Array:
    """``apply_rope`` for a model whose layer KINDS turn by different
    frequencies: ``inv_freq`` [kinds, D/2] and ``scales`` [kinds] (the
    factor on cos and sin: YaRN's attention factor, 1 for a kind without
    one), of which ``kind`` (an int32 scalar, it may be traced: a layer
    scan hands it down) picks a row. The angles are computed from the
    positions, ``position * inv_freq`` in float32, which is what a table
    holds: a table a kind would be a constant of ``kinds * max_S * D/2``
    floats baked into every program that closes over it (8 MB at 16,384
    positions, in each of an engine's ~70 programs)."""
    if positions is None:
        positions = jnp.arange(x.shape[-3])
    ang = positions.astype(jnp.float32)[..., None] * inv_freq[kind]
    return _rotate(x, ang[..., :, None, :], scales[kind])
