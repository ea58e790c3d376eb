"""``ops/shortconv.py``: the gated short convolution's causal depthwise
filter for a prefill and its one-token step, against three shifted
products and a position at a time in numpy float64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import shortconv


def draw(seed, B, T, C, K=3):
    k = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k[0], (B, T, C), jnp.float32),
            jax.random.normal(k[1], (B, K - 1, C), jnp.float32),
            jax.random.normal(k[2], (C, K), jnp.float32))


def by_position(g, state, w, lengths=None):
    """float64, a position at a time: tap K-1 on the current position; the
    state after a row's own length, reaching back into the incoming one."""
    g, state, w = (np.asarray(v, np.float64) for v in (g, state, w))
    B, T, C = g.shape
    K = w.shape[1]
    out, new = np.zeros((B, T, C)), np.zeros_like(state)
    for b in range(B):
        seen = list(state[b])                    # oldest first
        for t in range(T):
            rows = seen[-(K - 1):] + [g[b, t]]
            out[b, t] = sum(w[:, j] * rows[j] for j in range(K))
            if lengths is None or t < int(lengths[b]):
                seen.append(g[b, t])
        new[b] = np.stack(seen[-(K - 1):])
    return out, new


def test_the_filter_is_three_shifted_products():
    g, _, w = draw(0, 2, 9, 8)
    zeros = jnp.zeros((2, 2, 8), jnp.float32)
    c, state = shortconv.gated_conv(g, zeros, w)
    padded = np.pad(np.asarray(g), ((0, 0), (2, 0), (0, 0)))
    want = sum(padded[:, j:j + 9] * np.asarray(w)[:, j] for j in range(3))
    np.testing.assert_allclose(c, want, atol=1e-6)
    np.testing.assert_allclose(state, g[:, -2:], atol=0)


@pytest.mark.parametrize("K", [2, 3, 4])
def test_a_chunk_from_a_carried_state_equals_the_whole(K):
    g, state, w = draw(K, 2, 13, 8, K)
    whole, end = shortconv.gated_conv(g, state, w)
    first, mid = shortconv.gated_conv(g[:, :5], state, w)
    second, last = shortconv.gated_conv(g[:, 5:], mid, w)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=1e-6)
    np.testing.assert_allclose(last, end, atol=0)
    want, want_state = by_position(g, state, w)
    np.testing.assert_allclose(whole, want, atol=1e-5)
    np.testing.assert_allclose(end, want_state, atol=0)


def test_short_rows_and_padding_leave_the_right_state():
    """Rows of length 0, 1, 2 and 5 in a bucket of 8: the state after each
    is its last two TRUE rows, the incoming state's where the row is
    shorter than two; the padding behind a length shifts nothing."""
    g, state, w = draw(7, 4, 8, 8)
    lengths = jnp.asarray([0, 1, 2, 5], jnp.int32)
    c, new = shortconv.gated_conv(g, state, w, lengths)
    want, want_state = by_position(g, state, w, lengths)
    np.testing.assert_allclose(new, want_state, atol=0)
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[1], jnp.stack([state[1, 1], g[1, 0]]))
    np.testing.assert_array_equal(new[2], g[2, :2])
    np.testing.assert_array_equal(new[3], g[3, 3:5])
    for b, n in enumerate(lengths):            # the true positions' outputs
        np.testing.assert_allclose(c[b, :n], want[b, :n], atol=1e-5)
    # without lengths the padding is taken for positions
    _, through = shortconv.gated_conv(g, state, w)
    assert not np.allclose(through[3], new[3])


def test_the_step_equals_the_prefills_next_position():
    """A decode step is the same function over ONE position: from the
    state the step before left it gives the whole prefill's next output,
    and shifts the state by a row with ``g`` placed last."""
    g, state, w = draw(11, 3, 6, 16)
    whole, _ = shortconv.gated_conv(g, state, w)
    rows = state
    for t in range(6):
        c, new = shortconv.gated_conv(g[:, t:t + 1], rows, w)
        np.testing.assert_allclose(c[:, 0], whole[:, t], atol=1e-6)
        np.testing.assert_array_equal(new[:, 0], rows[:, 1])
        np.testing.assert_array_equal(new[:, 1], g[:, t])
        rows = new
    np.testing.assert_array_equal(rows, g[:, -2:])


def test_bf16_sums_in_float32_and_keeps_the_dtypes():
    g, state, w = (a.astype(jnp.bfloat16) for a in draw(5, 2, 7, 128))
    c, new = shortconv.gated_conv(g, state, w, jnp.asarray([7, 3]))
    assert c.dtype == new.dtype == jnp.bfloat16 and new.shape == (2, 2, 128)
    want, _ = by_position(g.astype(jnp.float32), state.astype(jnp.float32),
                          w.astype(jnp.float32))
    np.testing.assert_allclose(c.astype(jnp.float32), want, atol=0.06)
    c1, new1 = shortconv.gated_conv(g[:, :1], state, w)
    np.testing.assert_array_equal(c1, c[:, :1])
    assert new1.dtype == jnp.bfloat16
