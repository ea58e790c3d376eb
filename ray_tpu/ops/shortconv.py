"""The gated short convolution's filter (published ``lfm2`` / ``lfm2_moe``):
a depthwise causal filter of ``K`` taps a channel over ``g = B * x``,

    c_t = sum_{j=0..K-1} w[:, j] * g_{t-(K-1)+j}        (g before 0 is 0)

with tap ``K - 1`` on the current position, no bias and no activation. The
ONLY thing a layer keeps between calls is the last ``K - 1`` rows of ``g``
(``K`` 3: two rows of the model's width, 8 KB a slot a layer in bf16 at
2,048 channels): no decay, no scan, no matrix state. ONE function serves a prefill, a chunk
and the one-token step (``T`` 1: the state shifted by a row, ``g`` placed
last, ``K`` multiply-adds a channel, every slot's row rewritten). Plain
``jax.numpy``: a step moves 2 MB of state for 8 layers x 32 slots beside
33.6 MB of projections a layer, and XLA fuses the filter with the gates
around it (PERF.md, PR 61).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def gated_conv(g: jax.Array, state: jax.Array, weight: jax.Array,
               lengths: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """The filter over this call's T positions (a prefill, a chunk; T 1:
    a decode step). g [B, T, C]; ``state`` [B, K-1, C]: the rows of ``g``
    just before this call, oldest first (zeros at a sequence's start; what
    the call before left, for a later chunk or step); weight [C, K];
    ``lengths`` [B]: each row's TRUE positions of the T (None: all).
    -> (c [B, T, C] in g's dtype, summed in float32; the state after each
    row's ``lengths`` positions [B, K-1, C] in the state's dtype: the last
    K-1 true rows, reaching back into the incoming state where a row is
    shorter than that, so padding behind a row's length shifts nothing)."""
    T = g.shape[1]
    K = weight.shape[1]
    full = jnp.concatenate([state.astype(g.dtype), g], axis=1)
    w = weight.astype(jnp.float32)
    acc = sum(full[:, j:j + T].astype(jnp.float32) * w[:, j]
              for j in range(K))
    if lengths is None:
        new = full[:, T:]
    else:
        new = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
            f, n, K - 1, axis=0))(full, lengths.astype(jnp.int32))
    return acc.astype(g.dtype), new.astype(state.dtype)
