"""``ops/mhc.py``: the residual streams' maps against a numpy Sinkhorn,
what they are bounded by, the read and the write against ``einsum``s, and
one stream as the plain residual."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import mhc

KW = dict(iters=20, eps=1e-6, norm_eps=1e-6, clamp=(-30.0, 30.0))
N, C = 4, 128
W = 2 * N + N * N


def weights(seed=0, b_res=3.0, alpha=(1.0, 0.7, 1.3)):
    k = jax.random.split(jax.random.key(seed), 2)
    phi = jax.random.normal(k[0], (W, N * C)) * (N * C) ** -0.5
    bias = jnp.concatenate([jnp.zeros(2 * N), b_res * jnp.eye(N).reshape(-1)]
                           ) + 0.1 * jax.random.normal(k[1], (W,))
    return phi, jnp.asarray(alpha), bias


def numpy_maps(X, phi, alpha, bias, iters=20, eps=1e-6, norm_eps=1e-6,
               clamp=(-30.0, 30.0)):
    """The maps in float64, written from the equations: no code shared
    with ``ops/mhc.py``."""
    v = np.asarray(X, np.float64)
    r = 1.0 / np.sqrt((v ** 2).mean(-1, keepdims=True) + norm_eps)
    m = (v * r) @ np.asarray(phi, np.float64).T
    a = np.asarray(alpha, np.float64)
    m = m * np.concatenate([np.full(N, a[0]), np.full(N, a[1]),
                            np.full(N * N, a[2])]) + np.asarray(bias)
    M = np.exp(np.clip(m[:, 2 * N:], *clamp)).reshape(-1, N, N)
    for _ in range(iters):
        M = M / (M.sum(-1, keepdims=True) + eps)
        M = M / (M.sum(-2, keepdims=True) + eps)
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    return sig(m[:, :N]), 2 * sig(m[:, N:2 * N]), M


# b_res 0: Sinkhorn converges fast and H_res is doubly stochastic to 1e-5
# in the median token (the worst of 300 to 1e-4); b_res 3 (the seeded
# value): H_res lies near the identity, where 20 rounds leave the rows up
# to a few percent short (the columns are normalised last and are exact):
# both are the numpy Sinkhorn's own numbers
@pytest.mark.parametrize("b_res,row_tol", [(0.0, 1e-5), (3.0, 1e-2)])
def test_maps_are_the_numpy_sinkhorns(b_res, row_tol):
    phi, alpha, bias = weights(b_res=b_res, alpha=(1.0, 0.7, 1.0))
    X = jax.random.normal(jax.random.key(5), (300, N * C)) * 3.0
    pre, post, res = mhc.stream_maps(X, N, phi, alpha, bias, **KW)
    want = numpy_maps(X, phi, alpha, bias)
    for got, ref in zip((pre, post, res), want):
        np.testing.assert_allclose(got, ref, atol=1e-5)
    assert float(pre.min()) > 0 and float(pre.max()) < 1
    assert float(post.min()) > 0 and float(post.max()) < 2
    assert float(res.min()) >= 0
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)      # columns
    short = np.abs(np.asarray(res.sum(-1)) - 1).max(-1)           # rows
    assert np.median(short) < row_tol and short.max() < 10 * row_tol


@pytest.mark.parametrize("forced", [100.0, -100.0])
def test_the_clamp_keeps_a_forced_m_res_finite(forced):
    phi, alpha, bias = weights()
    bias = bias.at[2 * N:].set(forced).at[2 * N + 1].set(-forced)
    X = jax.random.normal(jax.random.key(1), (8, N * C))
    maps = mhc.stream_maps(X, N, phi, alpha, bias, **KW)
    assert all(bool(jnp.all(jnp.isfinite(m))) for m in maps)
    # without it exp(100) is inf in float32 and the rounds divide inf by inf
    loose = mhc.stream_maps(X, N, phi, alpha, bias,
                            **{**KW, "clamp": (-1e9, 1e9)})
    assert not bool(jnp.all(jnp.isfinite(loose[2])))


def test_one_stream_with_unit_maps_is_the_plain_residual():
    k = jax.random.split(jax.random.key(2), 2)
    x = jax.random.normal(k[0], (3, 5, C)).astype(jnp.bfloat16)
    y = jax.random.normal(k[1], (3, 5, C)).astype(jnp.bfloat16)
    one = jnp.ones((3, 5, 1))
    np.testing.assert_array_equal(mhc.mix_in(x, one), x)
    np.testing.assert_array_equal(mhc.mix_out(x, y, one, one[..., None]),
                                  x + y)
    np.testing.assert_array_equal(mhc.sum_streams(x, 1), x)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_read_and_the_write_are_the_equations(dtype):
    """``h = sum_i H_pre[i] X[i]`` and ``X'[i] = sum_j H_res[i, j] X[j] +
    H_post[i] y`` on streams laid side by side in the lanes, against
    ``einsum``s over [rows, n, C]; float32 sums, the result in X's dtype."""
    phi, alpha, bias = weights()
    k = jax.random.split(jax.random.key(9), 2)
    X = jax.random.normal(k[0], (2, 6, N * C)).astype(dtype)
    y = jax.random.normal(k[1], (2, 6, C)).astype(dtype)
    pre, post, res = mhc.stream_maps(X, N, phi, alpha, bias, **KW)
    Xs = X.astype(jnp.float32).reshape(2, 6, N, C)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2     # a bf16 ulp at |x| ~ 4
    h = mhc.mix_in(X, pre)
    assert h.dtype == dtype and h.shape == (2, 6, C)
    np.testing.assert_allclose(h.astype(jnp.float32),
                               jnp.einsum("bsn,bsnc->bsc", pre, Xs), atol=tol)
    out = mhc.mix_out(X, y, post, res)
    assert out.dtype == dtype and out.shape == X.shape
    want = (jnp.einsum("bsij,bsjc->bsic", res, Xs)
            + post[..., None] * y.astype(jnp.float32)[:, :, None])
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.reshape(2, 6, N * C), atol=tol)
    np.testing.assert_allclose(mhc.sum_streams(X, N).astype(jnp.float32),
                               Xs.sum(2), atol=2 * tol)
