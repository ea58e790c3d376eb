"""Ops layer: norms, rope, attention, ring attention (8 virtual devices)."""

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (apply_rope, attention, layer_norm, ring_attention,
                         rms_norm, rope_frequencies)
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.ring_attention import ring_attention_sharded


def test_rms_norm_matches_numpy():
    x = np.random.default_rng(0).normal(size=(2, 8, 16)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(16,)).astype(np.float32)
    out = rms_norm(jnp.asarray(x), jnp.asarray(w), eps=1e-5)
    expect = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(out, expect, rtol=1e-5)


def test_layer_norm_zero_mean_unit_var():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 32)), jnp.float32)
    out = layer_norm(x, jnp.ones((32,)), jnp.zeros((32,)))
    np.testing.assert_allclose(np.mean(np.asarray(out), -1), 0, atol=1e-5)
    np.testing.assert_allclose(np.var(np.asarray(out), -1), 1, atol=1e-3)


def test_rope_preserves_norm_and_relative_property():
    angles = rope_frequencies(8, 64, theta=10_000.0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 16, 2, 8)),
                    jnp.float32)
    out = apply_rope(x, angles)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # explicit positions == default positions
    pos = jnp.arange(16)[None, :]
    out2 = apply_rope(x, angles, pos)
    np.testing.assert_allclose(out, out2, rtol=1e-6)


def _naive_attention(q, k, v, causal=True):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    k = np.repeat(k, rep, axis=2)
    v = np.repeat(v, rep, axis=2)
    out = np.zeros_like(q)
    for b in range(B):
        for h in range(H):
            s = q[b, :, h] @ k[b, :, h].T / np.sqrt(D)
            if causal:
                mask = np.tril(np.ones((S, S), bool))
                s = np.where(mask, s, -1e30)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, :, h] = p @ v[b, :, h]
    return out


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kv_heads", [4, 2])
def test_reference_attention_vs_naive(causal, kv_heads):
    rng = np.random.default_rng(0)
    q = rng.normal(size=(2, 16, 4, 8)).astype(np.float32)
    k = rng.normal(size=(2, 16, kv_heads, 8)).astype(np.float32)
    v = rng.normal(size=(2, 16, kv_heads, 8)).astype(np.float32)
    out = attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                    causal=causal, use_flash=False)
    np.testing.assert_allclose(out, _naive_attention(q, k, v, causal),
                               rtol=2e-4, atol=2e-5)


def test_attention_grad_finite():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 4)), jnp.float32)

    def f(q):
        return attention(q, q, q, causal=True, use_flash=False).sum()

    g = jax.grad(f)(q)
    assert np.all(np.isfinite(np.asarray(g)))


def test_ring_attention_single_axis_matches_reference():
    """shard_map ring over sp=4 must equal full attention."""
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 32, 4, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)

    out = ring_attention_sharded(q, k, v, mesh, batch_axes=(), head_axis=None)
    expect = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_noncausal():
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 16, 2, 4)), jnp.float32)
    out = ring_attention_sharded(q, q, q, mesh, causal=False,
                                 batch_axes=(), head_axis=None)
    expect = reference_attention(q, q, q, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_gradients_match_reference():
    """Regression: stop_gradient on the online-softmax max broke grads."""
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 4)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 4)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 4)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(1, 32, 2, 4)), jnp.float32)

    def f_ring(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh, batch_axes=(),
                                       head_axis=None) * cot).sum()

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) * cot).sum()

    g_ring = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_blockwise_attention_matches_reference_fwd_and_grad():
    from ray_tpu.ops.attention import blockwise_attention
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 40, 4, 8)), jnp.float32)  # 40 % 16 != 0
    k = jnp.asarray(rng.normal(size=(2, 40, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 40, 2, 8)), jnp.float32)
    for causal in (True, False):
        out = blockwise_attention(q, k, v, causal=causal, block_k=16)
        expect = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)

    def f_blk(q):
        return blockwise_attention(q, k, v, block_k=16).sum()

    def f_ref(q):
        return reference_attention(q, k, v).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(f_blk)(q)),
                               np.asarray(jax.grad(f_ref)(q)),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Ulysses all-to-all context parallelism (SURVEY §5.7 requires both schemes)
# ---------------------------------------------------------------------------

def test_ulysses_attention_matches_reference():
    """shard_map Ulysses over sp=4 must equal full attention."""
    from jax.sharding import Mesh
    from ray_tpu.ops.ulysses import ulysses_attention_sharded
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(3)
    B, S, H, D = 2, 32, 4, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)

    out = ulysses_attention_sharded(q, k, v, mesh, batch_axes=(),
                                    head_axis=None)
    expect = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_matches_ring():
    from jax.sharding import Mesh
    from ray_tpu.ops.ulysses import ulysses_attention_sharded
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    u = ulysses_attention_sharded(q, k, v, mesh, batch_axes=(),
                                  head_axis=None)
    r = ring_attention_sharded(q, k, v, mesh, batch_axes=(),
                               head_axis=None)
    np.testing.assert_allclose(np.asarray(u), np.asarray(r),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_gradients_match_reference():
    from jax.sharding import Mesh
    from ray_tpu.ops.ulysses import ulysses_attention_sharded
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)

    def f_uly(q, k, v):
        return (ulysses_attention_sharded(q, k, v, mesh, batch_axes=(),
                                          head_axis=None) * cot).sum()

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) * cot).sum()

    gu = jax.grad(f_uly, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_ulysses_rejects_indivisible_heads():
    from jax.sharding import Mesh
    from ray_tpu.ops.ulysses import ulysses_attention_sharded
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    q = jnp.zeros((1, 32, 3, 8), jnp.float32)  # 3 heads, sp=4
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_sharded(q, q, q, mesh, batch_axes=(),
                                  head_axis=None)


# ---------------------------------------------------------------------------
# Explicit MoE expert all-to-all dispatch (VERDICT r1 #7)
# ---------------------------------------------------------------------------

def test_moe_alltoall_matches_einsum_dispatch():
    """The explicit all-to-all scheme must agree with the dense einsum
    scheme when capacity is ample (no drops on either side)."""
    from ray_tpu.models.moe import MoEConfig, MoEModel

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    mesh = build_mesh(MeshSpec.auto(8, sp=2, ep=2))
    cfg_a = MoEConfig.debug_moe(num_experts=4)
    cfg_a = dataclasses.replace(cfg_a, capacity_factor=4.0,
                                dtype=jnp.float32)
    cfg_b = dataclasses.replace(cfg_a, moe_dispatch="alltoall")

    model_a = MoEModel(cfg_a, mesh=mesh)
    model_b = MoEModel(cfg_b, mesh=mesh)
    params = model_a.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg_a.vocab_size, (2, 32)))

    with mesh:
        la, aux_a = model_a.apply_with_aux(params, tokens)
        lb, aux_b = model_b.apply_with_aux(params, tokens)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(float(aux_a), float(aux_b),
                               rtol=5e-2, atol=1e-3)


# ---------------------------------------------------------------------------
# Per-row positions: the serving prefills' masked attention (a slot
# cache, a gathered prefix), and one query token against a ragged cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["contiguous", "row_offsets",
                                  "one_query_token"])
def test_reference_attention_with_per_row_positions(case):
    from ray_tpu.ops.paged_attention import ragged_decode_attention_reference

    rng = np.random.default_rng(7)
    B, S, H, Hkv, D = 3, 24, 4, 2, 16
    T = 1 if case == "one_query_token" else 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    if case == "contiguous":
        # every row at 0..T-1 against its own first T keys: no positions
        k, v = k[:, :T], v[:, :T]
        pos_q = pos_k = jnp.broadcast_to(jnp.arange(T), (B, T))
        want = reference_attention(q, k, v)
    elif case == "row_offsets":
        # each row sits at its own offset into the keys, and drops its
        # own tail of them by a position past every query
        pos_q = jnp.asarray([0, 5, 16])[:, None] + jnp.arange(T)[None, :]
        pos_k = jnp.where(jnp.arange(S)[None, :]
                          < jnp.asarray([8, 13, 24])[:, None],
                          jnp.arange(S)[None, :], 2 ** 30)
        want = jnp.concatenate([
            reference_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                positions_q=pos_q[b], positions_k=pos_k[b])
            for b in range(B)])
    else:
        lengths = jnp.asarray([1, 13, 24], jnp.int32)
        pos_q, pos_k = (lengths - 1)[:, None], jnp.arange(S)
        want = ragged_decode_attention_reference(q[:, 0], k, v,
                                                 lengths)[:, None]
    out = reference_attention(q, k, v, positions_q=pos_q, positions_k=pos_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# Pallas flash attention (interpret mode: real kernel logic on CPU)
# ---------------------------------------------------------------------------

def test_flash_attention_matches_reference():
    from ray_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(0)
    B, S, H, Hkv, D = 2, 256, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    for causal in (True, False):
        out = flash_attention(q, k, v, causal, 128, 128, True)
        expect = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_ragged_seq():
    """Sequence not a multiple of the k block: tail-block masking."""
    from ray_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 192, 2, 16)), jnp.float32)
    out = flash_attention(q, q, q, True, 128, 128, True)
    expect = reference_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_gradients_match_reference():
    from ray_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(2)
    B, S, H, D = 1, 128, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, True, 64, 64, True) ** 2).sum()

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_llama_flash_impl_matches_ring_default():
    """attention_impl='flash' (sp==1) must produce the same loss as the
    reference/blockwise path the other impls use on one device."""
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    cfg_kw = dict(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_dim=128, max_seq_len=128, remat=False)
    m_ring = LlamaModel(LlamaConfig(attention_impl="ring", **cfg_kw))
    m_flash = LlamaModel(LlamaConfig(attention_impl="flash", **cfg_kw))
    params = m_ring.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 128, (2, 128)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    l_ring = m_ring.loss(params, tokens, targets)
    l_flash = m_flash.loss(params, tokens, targets)
    np.testing.assert_allclose(float(l_ring), float(l_flash),
                               rtol=1e-3, atol=1e-4)


def test_llama_flash_rejects_sp_mesh():
    from jax.sharding import Mesh
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("sp",))
    cfg = LlamaConfig.debug()
    cfg = dataclasses.replace(cfg, attention_impl="flash")
    with pytest.raises(ValueError, match="flash"):
        LlamaModel(cfg, mesh=mesh)
