"""Mean next-token cross-entropy from a reference forward, row by row.

``jax.lax.map`` over the batch keeps one sequence's logits alive at a
time: a float32 [16, 1024, 50257] logits array is 3.3 GB and would not
sit beside a training state; one row is 0.2 GB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mean_cross_entropy(forward, params, tokens, targets):
    """``forward(params, tokens[1, S]) -> logits [1, S, V]``."""

    def row(args):
        tok, tgt = args
        logits = forward(params, tok[None])[0]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tgt[:, None], axis=-1))

    return jnp.mean(jax.lax.map(row, (tokens, targets)))
