"""Generation by diffusion over blocks: the rule that fills a block.

A block of ``n`` positions starts as mask ids (but for what the prompt
gave of it) and is filled over denoising passes. A pass scores every
position of the block at once; at each MASKED position it proposes a
token ``x0`` (the engine's own sampler) with its probability as
confidence, and this module decides which proposals stay
(``unmask_step``). A token once placed is never masked again, and a given
position (the prompt's) is never rewritten.

- ``low_confidence_static``: pass ``k`` of the block places its QUOTA
  (``transfer_quotas``: ``n / steps`` a pass, the remainder to the early
  passes) of the most confident proposals;
- ``low_confidence_dynamic``: every proposal surer than the threshold,
  or, where those are fewer than the quota, the quota's most confident.

Ties in confidence go to the earlier position (the published procedure
takes ``torch.topk``, which leaves ties open). The quota never exceeds
what is still masked, so a block whose first positions the prompt gave
is done in fewer passes. Pure ``jax.numpy``: the engine's decode program
runs it on the device (``llm/engine.py``), the tests on crafted logits.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def transfer_quotas(block_length: int, steps: int) -> Tuple[int, ...]:
    """Tokens each of a block's ``steps`` denoising passes places."""
    base, more = divmod(block_length, steps)
    return tuple(base + (k < more) for k in range(steps))


def unmask_step(block, x0, confidence, passes, *, mask_id: int,
                quotas: Tuple[int, ...], threshold: float, dynamic: bool):
    """One denoising pass's verdict. block [B, n] int32 (``mask_id``
    where nothing stands yet), x0 [B, n] the proposals, confidence
    [B, n] float32, passes [B] the denoising passes the block has had.
    -> (the block with the kept proposals in, ``placed`` [B, n] bool,
    ``by_confidence`` [B, n] bool: placed by the threshold where the
    quota alone would have left a mask)."""
    n = block.shape[-1]
    masked = block == mask_id
    conf = jnp.where(masked, confidence.astype(jnp.float32), -jnp.inf)
    quota = jnp.asarray(quotas, jnp.int32)[
        jnp.clip(passes, 0, len(quotas) - 1)]
    quota = jnp.minimum(quota, jnp.sum(masked, axis=-1))
    # each position's rank by confidence, the surest 0, ties by position
    order = jnp.argsort(-conf, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    by_quota = masked & (rank < quota[:, None])
    placed = by_quota
    if dynamic:
        sure = masked & (conf > threshold)
        enough = jnp.sum(sure, axis=-1) >= quota
        placed = jnp.where(enough[:, None], sure, by_quota)
    return (jnp.where(placed, x0.astype(block.dtype), block), placed,
            placed & ~by_quota)


def confidence(logits, x0, temps):
    """A proposal's confidence: the probability of x0 [B, n] under the
    softmax of logits [B, n, V] (scaled by the slot's temperature where
    it samples; greedy at temperature 0: the logits as they are)."""
    scale = jnp.where(temps > 0.0, 1.0 / jnp.maximum(temps, 1e-6), 1.0)
    scaled = logits * scale[:, None, None]
    chosen = jnp.take_along_axis(scaled, x0[..., None], axis=-1)[..., 0]
    return jnp.exp(chosen - jax.nn.logsumexp(scaled, axis=-1))
