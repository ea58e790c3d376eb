"""``ops/ssm1.py``: the Mamba-1 decode update and prefill scan, kernel
(interpreted on the CPU) and XLA twin, against the recurrence written a
position at a time in numpy float64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm1

IMPLS = ("xla", "pallas")


def draw(seed, B, T, W, N):
    k = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(k[0], (B, T, W), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, W)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (N, W), minval=0.0, maxval=2.5))
    Bm, Cm = (jax.random.normal(k[i], (B, T, N), jnp.float32) for i in (3, 4))
    S0 = jax.random.normal(k[5], (B, N, W), jnp.float32)
    return u, dt, a, Bm, Cm, S0


def by_position(u, dt, a, Bm, Cm, S0, lengths=None):
    """float64, a position at a time, the decay as the [N, W] matrix it is."""
    u, dt, a, Bm, Cm, S = (np.asarray(v, np.float64)
                           for v in (u, dt, a, Bm, Cm, S0))
    B, T, W = u.shape
    y = np.zeros((B, T, W))
    for b in range(B):
        n = T if lengths is None else int(lengths[b])
        for t in range(n):
            decay = np.exp(dt[b, t][None, :] * a)                  # [N, W]
            S[b] = decay * S[b] + Bm[b, t][:, None] * (dt[b, t] * u[b, t])
            y[b, t] = (S[b] * Cm[b, t][:, None]).sum(0)
    return y, S


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("shape", [(2, 37, 24, 6), (1, 160, 256, 16)],
                         ids=["odd", "tiles"])
def test_scan_matches_the_recurrence(impl, shape):
    B, T, W, N = shape
    args = draw(1, B, T, W, N)
    y, S = ssm1.selective_scan(*args, impl=impl)
    want_y, want_S = by_position(*args)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(S, want_S, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", IMPLS)
def test_scan_stops_at_each_rows_length(impl):
    args = draw(2, 3, 40, 24, 6)
    lengths = jnp.asarray([40, 17, 1], jnp.int32)
    y, S = ssm1.selective_scan(*args, lengths, impl=impl)
    want_y, want_S = by_position(*args, lengths=lengths)
    np.testing.assert_allclose(S, want_S, rtol=2e-4, atol=2e-4)
    for b, n in enumerate(np.asarray(lengths)):
        np.testing.assert_allclose(y[b, :n], want_y[b, :n], rtol=2e-4,
                                   atol=2e-4)


@pytest.mark.parametrize("impl", IMPLS)
def test_state_carried_across_chunks_is_one_long_scan(impl):
    u, dt, a, Bm, Cm, S0 = draw(3, 2, 150, 24, 6)
    y_all, S_all = ssm1.selective_scan(u, dt, a, Bm, Cm, S0, impl=impl)
    S, ys = S0, []
    for lo, hi in ((0, 64), (64, 128), (128, 150)):
        y, S = ssm1.selective_scan(u[:, lo:hi], dt[:, lo:hi], a,
                                   Bm[:, lo:hi], Cm[:, lo:hi], S, impl=impl)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_all, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(S, S_all, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("layer", [0, 2])
def test_state_update_makes_the_decay_it_is_handed_as_a_matrix(impl, layer):
    """The kernel makes ``exp(dt A)`` itself: equal to the update given
    the [B, N, W] decay, in the layer's rows alone."""
    L, B, W, N = 3, 4, 24, 6
    u, dt, a, Bm, Cm, _ = draw(4, B, 1, W, N)
    stack = jax.random.normal(jax.random.key(9), (L, B, N, W), jnp.float32)
    dt1, dtu = dt[:, 0], dt[:, 0] * u[:, 0]
    new, y = jax.jit(lambda s, l: ssm1.state_update(
        s, l, a, dt1, dtu, Bm[:, 0], Cm[:, 0], impl=impl))(
            stack, jnp.int32(layer))
    decay = np.exp(np.asarray(dt1, np.float64)[:, None, :]
                   * np.asarray(a, np.float64)[None])          # [B, N, W]
    want = (decay * np.asarray(stack[layer], np.float64)
            + np.asarray(Bm[:, 0], np.float64)[:, :, None]
            * np.asarray(dtu, np.float64)[:, None, :])
    np.testing.assert_allclose(new[layer], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        y, (want * np.asarray(Cm[:, 0], np.float64)[:, :, None]).sum(1),
        rtol=1e-5, atol=1e-5)
    others = [i for i in range(L) if i != layer]
    np.testing.assert_array_equal(new[jnp.asarray(others)],
                                  stack[jnp.asarray(others)])


def test_state_update_is_one_position_of_the_scan():
    u, dt, a, Bm, Cm, S0 = draw(5, 4, 1, 24, 6)
    y_scan, S_scan = ssm1.selective_scan(u, dt, a, Bm, Cm, S0, impl="xla")
    new, y = ssm1.state_update(S0[None], 0, a, dt[:, 0], dt[:, 0] * u[:, 0],
                               Bm[:, 0], Cm[:, 0], impl="pallas")
    np.testing.assert_allclose(new[0], S_scan, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, y_scan[:, 0], rtol=1e-5, atol=1e-5)


def test_the_kernel_refuses_a_state_that_is_not_float32():
    u, dt, a, Bm, Cm, S0 = draw(6, 2, 1, 24, 6)
    with pytest.raises(ValueError, match="float32"):
        ssm1.state_update(S0[None].astype(jnp.bfloat16), 0, a, dt[:, 0],
                          dt[:, 0], Bm[:, 0], Cm[:, 0], impl="pallas")
