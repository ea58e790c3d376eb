"""``ops/ssm.py`` (the convolution with a carried window, the chunked
scan, the decode batch's state update and its kernel) against the
recurrence a position at a time of ``benchmark/reference/nemotron_h.py``,
and the gate-less relu^2 expert in a latent of ``ops/moe_dispatch.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from ray_tpu.ops import ssm
from ray_tpu.ops.moe_dispatch import dropless_expert_ffn, gmm_tiling

F32 = jnp.float32
B, H, P, G, N = 2, 8, 8, 2, 16


def _inputs(T, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (B, T, H, P), F32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H), F32) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), F32, 0.0, 2.7))
    Bm = jax.random.normal(k[3], (B, T, G, N), F32)
    Cm = jax.random.normal(k[4], (B, T, G, N), F32)
    s0 = jax.random.normal(k[5], (B, H, P, N), F32)
    return x, dt, a, Bm, Cm, s0


def _chunked(x, dt, a, Bm, Cm, s0, **kw):
    y, s = ssm.chunked_scan(x, dt, a, Bm, Cm, ssm.state_from_heads(s0, G),
                            dtype=F32, **kw)
    return y, ssm.state_to_heads(s, P)


def test_state_layout_round_trip():
    s = jax.random.normal(jax.random.key(0), (3, H, P, N))
    packed = ssm.state_from_heads(s, G)
    assert packed.shape == (3, G, N, (H // G) * P)
    np.testing.assert_array_equal(ssm.state_to_heads(packed, P), s)
    # lane (h % hg) * P + p of group h // hg, sublane n
    assert packed[1, 1, 5, 2 * P + 3] == s[1, (H // G) + 2, 3, 5]


@pytest.mark.parametrize("T,chunk", [(21, 8), (8, 8), (5, 8), (37, 16)])
def test_chunked_scan_is_the_positional_recurrence(T, chunk):
    """Lengths that are no multiple of the chunk, from an initial state."""
    x, dt, a, Bm, Cm, s0 = _inputs(T)
    want_y, want_s = ref.recurrence(x, dt, a, Bm, Cm, s0)
    y, s = _chunked(x, dt, a, Bm, Cm, s0, chunk=chunk)
    np.testing.assert_allclose(y, want_y, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-4, rtol=2e-4)


def test_two_chunked_calls_are_one():
    """A chunked prefill: the second call starts from the state the
    first left, at a cut that is no multiple of the scan's chunk."""
    x, dt, a, Bm, Cm, s0 = _inputs(29, seed=1)
    y, s = _chunked(x, dt, a, Bm, Cm, s0, chunk=8)
    cut = 13
    first = [v[:, :cut] for v in (x, dt, Bm, Cm)]
    rest = [v[:, cut:] for v in (x, dt, Bm, Cm)]
    y1, s1 = _chunked(first[0], first[1], a, first[2], first[3], s0, chunk=8)
    y2, s2 = _chunked(rest[0], rest[1], a, rest[2], rest[3], s1, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(s2, s, atol=2e-4, rtol=2e-4)


def test_chunked_scan_stops_each_row_at_its_length():
    x, dt, a, Bm, Cm, s0 = _inputs(24, seed=2)
    lengths = jnp.asarray([17, 6])
    y, s = _chunked(x, dt, a, Bm, Cm, s0, chunk=8, lengths=lengths)
    for r, n in enumerate([17, 6]):
        want_y, want_s = ref.recurrence(
            *(v[r:r + 1, :n] for v in (x, dt)), a,
            *(v[r:r + 1, :n] for v in (Bm, Cm)), s0[r:r + 1])
        np.testing.assert_allclose(y[r, :n], want_y[0], atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(s[r], want_s[0], atol=2e-4, rtol=2e-4)
    # run through the padding, the state moves on
    _, through = _chunked(x, dt, a, Bm, Cm, s0, chunk=8)
    assert float(jnp.max(jnp.abs(through[1] - s[1]))) > 1e-2


def test_causal_conv_carries_its_window_and_stops_at_lengths():
    C, K, T = 12, 4, 11
    k = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(k[0], (B, T, C), F32)
    w = jax.random.normal(k[1], (C, K), F32)
    b = jax.random.normal(k[2], (C,), F32)
    want = jax.nn.silu(ref.causal_conv(x, w, b))
    zero = jnp.zeros((B, K - 1, C), F32)
    out, win = ssm.causal_conv(x, zero, w, b)
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_array_equal(win, x[:, -3:])
    # two calls are one, the second from the first's window
    o1, w1 = ssm.causal_conv(x[:, :4], zero, w, b)
    o2, w2 = ssm.causal_conv(x[:, 4:], w1, w, b)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), want, atol=1e-5)
    np.testing.assert_array_equal(w2, win)
    # a row's window stops at its length (2: the window still holds a zero)
    _, wl = ssm.causal_conv(x, zero, w, b, jnp.asarray([7, 2]))
    np.testing.assert_array_equal(wl[0], x[0, 4:7])
    np.testing.assert_array_equal(wl[1, 1:], x[1, :2])
    np.testing.assert_array_equal(wl[1, 0], 0 * x[1, 0])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_state_step_is_one_position_of_the_recurrence(impl):
    """A decode batch's update, kernel (interpreted) and twin alike, on
    the rows of layer 1 of a stack of 3 layers' rows: the other layers'
    rows are left as they were."""
    x, dt, a, Bm, Cm, s0 = _inputs(1, seed=4)
    want_y, want_s = ref.recurrence(x, dt, a, Bm, Cm, s0)
    rows = jax.random.normal(jax.random.key(5), (3 * B, H, P, N), F32)
    rows = rows.at[B:2 * B].set(s0)
    stack = ssm.state_from_heads(rows, G)
    W = (H // G) * P
    decay = jnp.repeat(jnp.exp(dt[:, 0] * a), P, -1).reshape(B, G, W)
    dtx = (dt[:, 0, :, None] * x[:, 0]).reshape(B, G, W)
    new, y = jax.jit(ssm.state_step, static_argnums=(1,),
                     static_argnames=("impl",))(
        stack, B, decay, dtx, Bm[:, 0], Cm[:, 0], impl=impl)
    np.testing.assert_allclose(y.reshape(B, H, P), want_y[:, 0], atol=1e-5,
                               rtol=1e-5)
    got = ssm.state_to_heads(new, P)
    np.testing.assert_allclose(got[B:2 * B], want_s, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(got[:B], rows[:B])
    np.testing.assert_array_equal(got[2 * B:], rows[2 * B:])


def test_state_kernel_refuses_a_state_that_is_not_float32():
    """"pallas" does not fall through to the twin on another dtype."""
    x, dt, a, Bm, Cm, s0 = _inputs(1, seed=4)
    stack = ssm.state_from_heads(s0, G).astype(jnp.bfloat16)
    W = (H // G) * P
    decay = jnp.ones((B, G, W), F32)
    with pytest.raises(ValueError, match="float32"):
        ssm.state_step(stack, 0, decay, decay, Bm[:, 0], Cm[:, 0],
                       impl="pallas")
    new, _ = ssm.state_step(stack, 0, decay, decay, Bm[:, 0], Cm[:, 0],
                            impl="xla")
    assert new.dtype == jnp.bfloat16


@pytest.mark.parametrize("held", [None, (2, 3)])
def test_gateless_relu2_experts_in_a_latent(held):
    """``e_gate`` None and ``expert_input``: the router reads x, the
    experts read the latent rows and are ``relu(u W1)^2 W2``; with a
    share, what the absent experts would add is left out."""
    T, D, L, F, E, K = 10, 12, 6, 9, 8, 3
    k = jax.random.split(jax.random.key(6), 6)
    x = jax.random.normal(k[0], (T, D), F32)
    u = jax.random.normal(k[1], (T, L), F32)
    router = jax.random.normal(k[2], (D, E), F32)
    bias = 0.1 * jax.random.normal(k[3], (E,), F32)
    first, n = held or (0, E)
    w1 = jax.random.normal(k[4], (n, L, F), F32)
    w2 = jax.random.normal(k[5], (n, F, L), F32)
    out, load, experts, _ = dropless_expert_ffn(
        x, router, None, w1, w2, top_k=K, norm_topk_prob=True, dtype=F32,
        sigmoid_bias=bias, weight_scale=2.5, held=held, expert_input=u)
    scores = jax.nn.sigmoid(x @ router)
    chosen = jax.lax.top_k(scores + bias, K)[1]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    picked = jnp.take_along_axis(scores, chosen, -1)
    picked = 2.5 * picked / picked.sum(-1, keepdims=True)
    want = jnp.zeros((T, L))
    for t in range(T):
        for j in range(K):
            e = int(chosen[t, j]) - first
            if 0 <= e < n:
                want = want.at[t].add(picked[t, j] * (
                    jnp.square(jax.nn.relu(u[t] @ w1[e])) @ w2[e]))
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)
    assert out.shape == (T, L) and int(load.sum()) == T * K


@pytest.mark.parametrize("tokens", [64, 32, 512, 2 * 1088])
def test_gmm_tiling_holds_at_the_latent_experts_widths(tokens):
    """K = 1,024, N = 2,688 = 21 x 128 and its transpose at top-22: a
    decode step's 1,408 rows, the buckets', a chunk's and the check's."""
    m = tokens * 22
    for k, n in ((1024, 2688), (2688, 1024)):
        tm, tk, tn = gmm_tiling(m, k, n, 2)
        assert m % tm == 0 and k % tk == 0 and n % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        # an expert in at most two grid steps
        assert (k // tk) * (n // tn) <= 2
